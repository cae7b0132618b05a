"""Seconds of the balance stage per adaptive step (rebalance, halo plan
and owned packing), from AdaptiveSession on_stage."""


def read(rec):
    steps = rec.get("steps")
    return sum(s["t_balance"] for s in steps) / len(steps) if steps else None
