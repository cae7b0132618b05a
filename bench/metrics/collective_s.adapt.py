"""Device seconds per adaptive step in collective operations (halo legs,
migration all_to_all, k-section and PCG reductions), from the profiler
trace, averaged over the chips."""


def read(rec):
    t, steps = rec.get("trace"), rec.get("steps")
    if not t or not steps or not t["collective_s"]:
        return None
    return t["collective_s"] / len(steps)
