"""Seconds per adaptive step that JAX spends tracing, lowering and
compiling or loading programs from its compile cache, from the
jax.monitoring duration events inside the window."""


def read(rec):
    steps = rec.get("steps")
    return rec["jit_s"] / len(steps) if steps else None
