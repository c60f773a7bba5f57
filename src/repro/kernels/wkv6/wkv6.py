"""Pallas TPU kernel: chunked RWKV-6 WKV scan (linear attention with
data-dependent per-channel decay).

The naive formulation is a length-S sequential scan of rank-1 state updates —
zero MXU utilization and S HBM round-trips for the [N, N] state. The chunked
reformulation (flash-linear-attention lineage) turns a chunk of L steps into
three [L, N] x [N, L|N] matmuls:

  P_i   = prod_{l<=i} w_l                      (per-channel cumprod, in VMEM)
  A     = (r .* P_prev/Pref) @ (k .* Pref/P)^T (intra-chunk, strictly causal)
  y     = mask(A) @ V + (r .* P_prev) @ S_0 + (r.u.k) v   (bonus diag term)
  S_L   = diag(P_last) S_0 + (k .* P_last/P)^T @ V        (inter-chunk carry)

Grid: (B*H parallel, n_chunks sequential); the [N, N] f32 state lives in a
VMEM scratch buffer that persists across the chunk dimension — one HBM
round-trip per chunk tile instead of per token.

Numerics: exponent factors are computed against a mid-chunk per-channel
reference (Pref = exp(cum/2)) and clamped to +-CLAMP; exact whenever the
per-channel total decay within a chunk stays above exp(-2*CLAMP). With the
default L=16 this covers the decay range RWKV-6 realizes in practice
(w = exp(-exp(wlog)), wlog ~ N(-0.6, 1)); tests sample that distribution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = 25.0


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sfin_ref, state,
            *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _reset():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0].astype(jnp.float32)          # [L, N]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)          # decay in (0, 1)
    u = u_ref[0].astype(jnp.float32)          # [1, N] bonus

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum); HIGHEST keeps it at f32 accuracy on the MXU
    cum = jax.lax.dot(
        (t_idx >= j_idx).astype(jnp.float32), logw,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                          # [L, N]
    cum_prev = cum - logw                     # exclusive
    # static 2-D slices throughout: Mosaic lowers no dynamic_slice, and a
    # negative index (cum[-1]) becomes one
    cum_last = cum[chunk - 1:chunk, :]        # [1, N]
    cref = 0.5 * cum_last                     # mid-chunk reference

    r_hat = r * jnp.exp(
        jnp.minimum(jnp.maximum(cum_prev - cref, -CLAMP), CLAMP))
    k_hat = k * jnp.exp(jnp.minimum(jnp.maximum(cref - cum, -CLAMP), CLAMP))

    # intra-chunk, strictly causal (j < t)
    a = jax.lax.dot_general(
        r_hat, k_hat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [L, L]
    a = jnp.where(t_idx > j_idx, a, 0.0)

    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)   # [L, 1] u-term

    s0 = state[...]                            # [N, N]
    y = (
        a @ v
        + (r * jnp.exp(cum_prev)) @ s0
        + bonus * v
    )

    # inter-chunk state carry: exponents <= 0, always safe
    k_tail = k * jnp.exp(cum_last - cum)
    state[...] = jnp.exp(cum_last).T * s0 + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[0] = y.astype(y_ref.dtype)
    sfin_ref[0] = state[...].astype(sfin_ref.dtype)


def chunked_wkv6(
    r: jnp.ndarray,   # [BH, S, N]
    k: jnp.ndarray,   # [BH, S, N]
    v: jnp.ndarray,   # [BH, S, N]
    w: jnp.ndarray,   # [BH, S, N] per-step decay in (0, 1)
    u: jnp.ndarray,   # [BH, N] bonus
    *,
    chunk: int = 16,
    interpret: bool = False,
):
    """Returns (y [BH, S, N], final_state [BH, N, N])."""
    bh, s, n = r.shape
    if s % chunk:
        raise ValueError(f"seq len {s} must be a multiple of chunk {chunk}")
    n_chunks = s // chunk

    seq_block = pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0))
    # u as [bh, 1, n]: a (1, n) block of a [bh, n] array breaks the TPU's
    # rule that a block's last two dims divide by (8, 128) or span the array
    u_block = pl.BlockSpec((1, 1, n), lambda b, c: (b, 0, 0))
    sfin_block = pl.BlockSpec((1, n, n), lambda b, c: (b, 0, 0))

    y, sfin = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(bh, n_chunks),
        in_specs=[seq_block, seq_block, seq_block, seq_block, u_block],
        out_specs=[seq_block, sfin_block],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, n), r.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(r, k, v, w, u[:, None, :])
    return y, sfin
