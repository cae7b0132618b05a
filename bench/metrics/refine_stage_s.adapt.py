"""Seconds of marking and mesh refinement per adaptive step (host numpy),
from AdaptiveSession on_stage."""


def read(rec):
    steps = rec.get("steps")
    if not steps:
        return None
    return sum(s["t_mark"] + s["t_adapt_mesh"] for s in steps) / len(steps)
