"""Pallas TPU kernel: fused CowClip + coupled-L2 + Adam embedding update.

The paper's training hot spot is the embedding optimizer chain — 99.9% of all
parameters flow through clip → L2 → Adam → apply every step. Executed as
separate XLA ops this is five HBM round-trips over three table-sized arrays
(w, m, v) plus the gradient; fused in one kernel it is a single
read-modify-write pass: per grid step, one ``[BLOCK_ROWS, D]`` tile of each
of (w, g, m, v) streams HBM -> VMEM, the whole update happens in VMEM/VREGs,
and (w, m, v) stream back. Arithmetic intensity is O(1) FLOP/byte — this is
a pure bandwidth kernel, so minimizing HBM traffic IS the optimization
(DESIGN.md §3 hardware adaptation).

Row-parallel: an id's embedding row never interacts with another row
(CowClip's per-id threshold), so the grid tiles rows; the row dim maps to
TPU sublanes and the feature dim to the 128-wide lanes. All math in f32.

Step math (one row, matching ``ref.py`` / ``core.cowclip`` + ``core.optim``):

    touched (cnt > 0):
        clip_t = cnt * max(r * ||w||, zeta)
        g     <- g * min(1, clip_t / ||g||)      # CowClip (Alg. 1)
        g     <- g + l2 * w                      # coupled L2 (paper setup)
        m     <- b1*m + (1-b1)*g ;  v <- b2*v + (1-b2)*g^2
        w     <- w - lr * (m/(1-b1^t)) / (sqrt(v/(1-b2^t)) + eps)
    absent (cnt == 0):
        w     <- w * (1 - lr*l2) ;  m, v unchanged    # geometric L2 decay
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.optim import decay_factor


def _kernel(bc_ref, w_ref, g_ref, cnt_ref, m_ref, v_ref,
            w_out, m_out, v_out, *, r, zeta, lr, l2, b1, b2, eps, do_clip,
            factor):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    # counts arrive as a [BLOCK_ROWS, 1] column and the row norms keep their
    # reduced axis, so every per-row scalar broadcasts over D as it is: the
    # TPU compiler has no layout for reshaping a 1-D row vector to a column
    cnt = cnt_ref[...].astype(jnp.float32)          # [BLOCK_ROWS, 1]
    bc1 = bc_ref[0, 0]                              # 1/(1-b1^t)
    bc2 = bc_ref[0, 1]                              # 1/(1-b2^t)

    if do_clip:
        gnorm = jnp.sqrt(jnp.sum(g * g, axis=-1, keepdims=True))
        wnorm = jnp.sqrt(jnp.sum(w * w, axis=-1, keepdims=True))
        clip_t = cnt * jnp.maximum(r * wnorm, zeta)
        g = g * jnp.minimum(1.0, clip_t / (gnorm + 1e-30))

    gl = g + l2 * w
    m2 = b1 * m + (1.0 - b1) * gl
    v2 = b2 * v + (1.0 - b2) * gl * gl
    upd = (m2 * bc1) / (jnp.sqrt(v2 * bc2) + eps)
    touched = cnt > 0.0
    w = jnp.where(touched, w - lr * upd, w * factor)
    m = jnp.where(touched, m2, m)
    v = jnp.where(touched, v2, v)

    w_out[...] = w.astype(w_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def default_block_rows(dim: int) -> int:
    """Rows per block: ~2 MB of VMEM across the 7 resident [rows, D] f32
    tiles, and a multiple of 8 (the TPU's sublane count)."""
    return max(8, min(1024, (1 << 19) // max(dim, 1)))


def cowclip_adam_update(
    w: jnp.ndarray,          # [V, D] table
    g: jnp.ndarray,          # [V, D] task-loss gradient
    cnt: jnp.ndarray,        # [V]    per-id batch occurrence counts
    m: jnp.ndarray,          # [V, D] Adam first moment
    v: jnp.ndarray,          # [V, D] Adam second moment
    step: jnp.ndarray,       # scalar int32, 1-based
    *,
    r: float = 1.0,
    zeta: float = 1e-5,
    lr: float = 1e-4,
    l2: float = 1e-5,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    clip: bool = True,
    block_rows: int = 0,
    interpret: bool = False,
):
    """Fused CowClip+L2+Adam. Returns (w_new, m_new, v_new).

    ``block_rows`` defaults to ``default_block_rows(D)``; a block shorter
    than the table is a multiple of 8 rows on a TPU, one as long is any
    length."""
    vocab, dim = w.shape
    block_rows = min(block_rows or default_block_rows(dim), vocab)
    n_blocks = pl.cdiv(vocab, block_rows)

    t = step.astype(jnp.float32)
    bc = jnp.stack(
        [1.0 / (1.0 - b1**t), 1.0 / (1.0 - b2**t)]
    ).reshape(1, 2)

    kernel = functools.partial(
        _kernel, r=r, zeta=zeta, lr=lr, l2=l2, b1=b1, b2=b2, eps=eps,
        # paper: 1-dim LR-stream tables are exempt from CowClip (matches
        # core.cowclip.cowclip_table and ref.py)
        do_clip=clip and dim >= 2,
        factor=decay_factor(lr, l2),
    )
    row_block = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    cnt_block = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    bc_block = pl.BlockSpec((1, 2), lambda i: (0, 0))

    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[bc_block, row_block, row_block, cnt_block, row_block, row_block],
        out_specs=[row_block, row_block, row_block],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(bc, w, g, cnt.reshape(vocab, 1), m, v)
