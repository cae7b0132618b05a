"""PCG iterations per adaptive step (StepStats.cg_iters)."""


def read(rec):
    steps = rec.get("steps")
    return sum(s["cg_iters"] for s in steps) / len(steps) if steps else None
