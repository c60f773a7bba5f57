"""Pallas TPU kernels: sparse unique-id CowClip+L2+Adam embedding update.

The dense fused kernel (``cowclip.py``) still streams the full ``[vocab,
dim]`` table plus both Adam moments through HBM every step, although a batch
touches only its unique ids. These kernels restrict the whole update to the
``[cap, dim]`` rows of the batch's unique-id slots, making optimizer HBM
traffic O(batch) instead of O(vocab) — the layout production CTR systems use
(arXiv:2201.05500 §4, arXiv:2209.05310 §6).

The logical pipeline is **gather -> lazy-decay catch-up -> CowClip -> Adam ->
scatter**, split in two because the task-loss gradient is computed (by the
model's backward pass) *between* the catch-up and the clip — the forward
must see rows with their pending L2 decay applied or the two paths diverge:

* ``sparse_gather_catchup``: XLA gathers each slot's (w, m, v, last_step)
  row into contiguous ``[cap, dim]`` slabs; the kernel applies every row's
  missed decay-only steps in closed form — ``w *= (1 - lr*l2)**k`` for k
  pending steps, O(1) in k, moments held (ids absent from a batch still
  decay under coupled L2 — paper's zeta discussion).
* ``sparse_update_scatter``: the dense fused kernel runs CowClip (per-id
  count-scaled adaptive threshold) -> coupled L2 -> Adam on the slabs, and
  XLA scatters the new rows back into the tables. Rows of absent ids are
  never written.

Layout: the kernels tile the slabs in ``(block_rows, dim)`` blocks — a
multiple of 8 rows, or the whole slab — with per-slot scalars as
``[cap, 1]`` columns. A kernel that moved one table row per grid step would
need ``(1, dim)`` blocks or a one-row DMA out of HBM, and the TPU compiler
accepts neither at CTR widths (dim 10 and 1 are not multiples of its
(8, 128) tiling), so the row movement is XLA's gather and scatter.

The moment tables come ``[rows, dim]`` or packed (``ref.pack_rows``); the
row movement reads the form from the table's shape.

Pad slots (capacity > n_unique, count 0) carry out-of-range uids: their
gathered rows are garbage that nothing reads, and the scatter drops them
(``mode="drop"``, with count-0 slots forced out of range).

Shard-offset awareness: both functions take a ``row_offset`` subtracted
from every uid before the gather and the scatter, so a model-shard of a
row-partitioned table (repro.embed.sharded_sparse) can feed *global* ids
against its local ``[rows_per_shard, dim]`` block. Every *real* slot's uid
minus the offset must be in ``[0, rows)`` (guaranteed when the caller owns
those ids).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .cowclip import cowclip_adam_update, default_block_rows
from .ref import gather_rows, scatter_rows


def _catchup_kernel(lim_ref, w_ref, ls_ref, w_out, *, factor):
    lim = lim_ref[0, 0]                           # catch up through this step
    # closed form: k pending decay-only steps collapse to one multiply
    # (w *= factor**k, moments untouched); k == 0 multiplies by exactly 1.0
    # so an already-caught-up row passes through bit-identically
    k = jnp.maximum(lim - ls_ref[...], 0).astype(jnp.float32)   # [rows, 1]
    scale = jnp.where(k > 0, factor**k, 1.0)
    w_out[...] = w_ref[...].astype(jnp.float32) * scale


def sparse_gather_catchup(
    w: jnp.ndarray,           # [rows, dim] table (or one shard of it)
    m: jnp.ndarray,           # [rows, dim] or packed Adam first moment
    v: jnp.ndarray,           # [rows, dim] or packed Adam second moment
    last_step: jnp.ndarray,   # [rows] int32 step each row was last updated
    uids: jnp.ndarray,        # [cap] int32 slot uids (pads out of range)
    step: jnp.ndarray,        # scalar int32 t: catch rows up through t-1
    *,
    lr: float,
    l2: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    row_offset=0,             # subtracted from uids: shard's first global row
    block_rows: int = 0,
    interpret: bool = False,
):
    """Gather + closed-form decay catch-up, O(1) in pending depth.
    Returns f32 (w_rows, m_rows, v_rows); m/v rows are gathered unchanged
    (decay-only steps never move the Adam moments). b1/b2/eps are accepted
    for hyper-dict compatibility with the update."""
    from ...core.optim import decay_factor

    del b1, b2, eps
    loc = uids - row_offset
    cap, dim = uids.shape[0], w.shape[1]
    block_rows = min(block_rows or default_block_rows(dim), cap)
    rows = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    col = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    lim = jnp.reshape(step - 1, (1, 1)).astype(jnp.int32)

    w_rows = pl.pallas_call(
        functools.partial(_catchup_kernel, factor=decay_factor(lr, l2)),
        grid=(pl.cdiv(cap, block_rows),),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), rows, col],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((cap, dim), jnp.float32),
        interpret=interpret,
    )(lim, w[loc], last_step[loc].astype(jnp.int32)[:, None])
    return (w_rows, gather_rows(m, loc, dim).astype(jnp.float32),
            gather_rows(v, loc, dim).astype(jnp.float32))


def sparse_update_scatter(
    w: jnp.ndarray,           # [rows, dim] table or shard
    m: jnp.ndarray,           # [rows, dim] or packed Adam first moment
    v: jnp.ndarray,           # [rows, dim] or packed Adam second moment
    uids: jnp.ndarray,        # [cap] int32 slot uids
    counts: jnp.ndarray,      # [cap] f32 per-slot batch counts (0 on pads)
    w_rows: jnp.ndarray,      # [cap, dim] caught-up rows (f32)
    g_rows: jnp.ndarray,      # [cap, dim] task-loss gradient on rows
    m_rows: jnp.ndarray,      # [cap, dim] caught-up first moment rows
    v_rows: jnp.ndarray,      # [cap, dim] caught-up second moment rows
    step: jnp.ndarray,        # scalar int32 t, 1-based
    *,
    r: float = 1.0,
    zeta: float = 1e-5,
    lr: float = 1e-4,
    l2: float = 1e-5,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    clip: bool = True,
    row_offset=0,             # subtracted from uids: shard's first global row
    block_rows: int = 0,
    interpret: bool = False,
):
    """Fused CowClip+L2+Adam on the slot rows, scattered into the tables.
    Returns updated (w, m, v) full tables; rows of ids absent from the
    batch are not touched (their decay stays pending)."""
    w_new, m_new, v_new = cowclip_adam_update(
        w_rows, g_rows, counts, m_rows, v_rows, step, r=r, zeta=zeta, lr=lr,
        l2=l2, b1=b1, b2=b2, eps=eps, clip=clip, block_rows=block_rows,
        interpret=interpret)
    # pad slots (count 0) are forced out of range: with a row_offset the
    # raw pad uid (vocab) minus the offset could otherwise land in range
    keep = counts > 0
    loc = uids - row_offset
    return (w.at[jnp.where(keep, loc, w.shape[0])].set(
                w_new.astype(w.dtype), mode="drop"),
            scatter_rows(m, loc, m_new, keep),
            scatter_rows(v, loc, v_new, keep))
