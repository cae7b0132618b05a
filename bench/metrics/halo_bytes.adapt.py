"""Wire bytes of one PCG matvec's halo exchange, both legs
(StepStats.comm_halo_bytes), averaged over the steps."""


def read(rec):
    steps = rec.get("steps")
    return sum(s["halo_bytes"] for s in steps) / len(steps) if steps else None
