"""Seconds JAX spent tracing, lowering and compiling during set-up, from
its ``jax.monitoring`` compile-duration events. Moves ``setup_s``."""

UNIT = "s"


def read(r):
    return r["compile_s"] if r["compile_s"] > 0 else None
