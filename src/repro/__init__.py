"""repro — production-grade JAX framework reproducing CowClip (AAAI 2023):
large-batch CTR training via adaptive column-wise gradient clipping, extended
to LM-scale embedding tables, multi-pod pjit distribution, and a fused
CowClip+L2+Adam row update for the embedding-update hot path."""

__version__ = "1.0.0"
