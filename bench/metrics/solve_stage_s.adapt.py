"""Seconds of the solve stage per adaptive step (AdaptiveSession on_stage,
the stage clock stops once its outputs are ready on the device)."""


def read(rec):
    steps = rec.get("steps")
    return sum(s["t_solve"] for s in steps) / len(steps) if steps else None
