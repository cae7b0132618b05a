"""Share of the traced window in which no operation ran on the device
(profiler trace, busy intervals merged, averaged over the chips)."""


def read(rec):
    t = rec.get("trace")
    if not t or "steps" not in rec:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
