"""repro.kernels — the paper's compute hot-spots.

cowclip/ : fused CowClip + L2 + Adam embedding-row update (bandwidth-bound),
           jit'd jnp that XLA compiles on every backend: ops.py (the jit'd
           wrappers the train steps call), ref.py (their row math and the
           packed moment form)
wkv6/    : chunked RWKV-6 linear-attention scan (MXU-bound), the one Pallas
           kernel: wkv6.py (pl.pallas_call + BlockSpec), ops.py (jit'd
           wrapper), ref.py (pure-jnp oracle). The wrapper runs the oracle
           unless the caller asks for the kernel (``use_kernel=True``); a
           kernel asked for runs compiled on a TPU and in Pallas interpret
           mode elsewhere.
"""

import jax


def interpret_off_tpu() -> bool:
    """Pallas ``interpret`` flag for a kernel a caller asked for: off a TPU
    the kernel body runs as plain jnp (a correctness harness, slow), on a
    TPU it compiles."""
    return jax.default_backend() != "tpu"
