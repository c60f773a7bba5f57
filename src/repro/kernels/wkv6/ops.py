"""jit'd public wrapper for the chunked WKV6 scan.

Runs the jnp oracle unless the caller asks for the kernel; a kernel asked
for compiles on a TPU and runs in interpret mode elsewhere."""

from __future__ import annotations

from functools import partial

import jax

from .. import interpret_off_tpu
from .ref import wkv6_reference as reference
from .wkv6 import chunked_wkv6


@partial(jax.jit, static_argnames=("chunk", "use_kernel"))
def wkv6(r, k, v, w, u, *, chunk=16, use_kernel=False):
    if not use_kernel:
        return reference(r, k, v, w, u)
    return chunked_wkv6(r, k, v, w, u, chunk=chunk,
                        interpret=interpret_off_tpu())
