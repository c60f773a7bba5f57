"""Sharded+sparse hybrid placement: per-shard unique-id dedup math.

The ``sharded`` placement (repro.embed.sharded) scales memory — each device
owns ``rows_per_shard = ceil(vocab / n_model)`` table rows — but its
optimizer update is still *dense per shard*: every step streams all
``rows_per_shard`` rows of (w, m, v) through the update, although a CTR
batch touches only its unique ids (PAPER.md's id-frequency argument; the
waste Zhao et al. 2022, arXiv:2201.05500, show dominates at production
vocabs). This module restricts the per-shard update to the batch ids the
shard owns, composing the two prior placements:

* Each model-shard dedups the *global* batch's ids that map to its rows
  into a static-capacity unique set (capacity O(batch), padded), staged so
  the "data" collective carries unique ids rather than the raw batch: each
  data slice first dedups its own column with counts
  (``slice_unique_counts``), the per-slice (uids, counts) pairs are
  all-gathered over "data" inside the ``shard_map``, and each model shard
  dedups the owned subset of the union with the counts summed per slot
  (``owned_unique_weighted`` — identical slots/counts/overflow to the
  single-stage ``owned_unique_local`` oracle). Every data slice of a shard
  agrees on the slots without a dedicated collective and the sort stays
  out of the SPMD partitioner.
* After the backward, the touched rows are gathered from the *raw* shard,
  their pending coupled-L2 decay applied in one closed-form multiply
  (``w *= (1 - lr*l2)**k`` via the per-row ``last_step`` — the sparse
  path's lazy-decay contract, O(1) in pending depth), then the fused
  CowClip/L2/Adam row update runs and scatters back — row-local and
  collective-free, exactly like the dense per-shard update it replaces
  (``update_phase``).
* **Overflow** (more distinct owned ids than capacity — impossible at the
  default ``capacity = min(batch, rows_per_shard)``): the shard falls back
  to the dense per-shard update for that step (closed-form catch-up of
  *all* its rows, then ``shard_update``), so the hybrid stays exact instead
  of dropping gradient contributions the way the single-device sparse path
  does. The fallback is per (field, shard) and is reported/logged by the
  train step.

Forward lookup and row-grad/count assembly reuse ``repro.embed.sharded``'s
masked-psum building blocks (``decayed_lookup_partial`` + psum over
"model"; ``rowgrad_slots``/``counts_partial`` + psum over "data"). The
forward reads the *raw* tables and applies each row's pending decay inline
during the gather — nothing is scattered into the shard before the lookup,
so the tower forward/backward has no data-dependence on the update path's
dedup or collectives and XLA is free to overlap them (the train step issues
the dedup all-gathers before the forward and every row-grad psum before any
row update).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.optim import decay_catchup_rows
from ..kernels.cowclip import ops as cc_ops
from .sharded import RowShardPlan, shard_update


def shard_capacity(plan: RowShardPlan, batch: int, unique_capacity: int = 0) -> int:
    """Static per-shard unique-set capacity for one field.

    ``unique_capacity <= 0`` selects the exact default
    ``min(batch, rows_per_shard)`` — a shard can never see more distinct
    owned ids than the batch holds or than it has rows, so overflow is
    impossible. A positive value caps memory at the price of overflow
    fallbacks (see module docstring).
    """
    exact = min(batch, plan.rows_per_shard)
    if unique_capacity <= 0:
        return max(1, exact)
    return max(1, min(unique_capacity, exact))


class ShardUniqueSets(NamedTuple):
    """Per-shard static-capacity dedup of one field's global batch column.

    local_rows: [n_shards, capacity] int32 — owned ids' *local* rows on
                their shard, ascending by id; pad slots hold
                ``rows_per_shard`` (out of range -> gathers clip, scatters
                with ``mode='drop'`` skip).
    counts:     [n_shards, capacity] float32 global batch occurrence count
                per slot (CowClip's ``cnt``; 0 on pads).
    overflow:   [n_shards] bool — shard had more distinct owned ids than
                capacity and must take the dense fallback this step.
    """

    local_rows: jnp.ndarray
    counts: jnp.ndarray
    overflow: jnp.ndarray


def shard_unique_sets(ids_col: jnp.ndarray, plan: RowShardPlan,
                      capacity: int) -> ShardUniqueSets:
    """Dedup one field's global batch column per owning shard, all shards at
    once — the host-level (outside-``shard_map``) view of the dedup, used by
    tests and benchmarks to compute expected slot assignments.

    The train step itself does NOT use this: it calls
    ``owned_unique_local`` *inside* the shard_map instead, where each device
    dedups only the ids its own shard owns. Besides scaling better (one
    unique per device instead of ``n_shards``), that keeps the sort out of
    the XLA SPMD partitioner, which (as of jax 0.4.x on CPU) miscompiles a
    traced ``jnp.unique`` whose output feeds a ``shard_map``.
    """
    from ..models.embedding import unique_owned_ids

    shard = plan.shard_of(ids_col)
    locs, cnts, ovfs = [], [], []
    for s in range(plan.n_shards):
        uids, counts, overflow = unique_owned_ids(
            ids_col, shard == s, plan.vocab, capacity)
        locs.append(_local_rows(uids, plan))
        cnts.append(counts)
        ovfs.append(overflow)
    return ShardUniqueSets(jnp.stack(locs), jnp.stack(cnts), jnp.stack(ovfs))


def _local_rows(uids: jnp.ndarray, plan: RowShardPlan) -> jnp.ndarray:
    """Owned uids -> local rows; pads (uid == vocab) map out of *local*
    range explicitly (the local_row of the sentinel can land in range —
    e.g. ``vocab % n_shards`` under "mod")."""
    return jnp.where(uids < plan.vocab, plan.local_row(uids),
                     plan.rows_per_shard).astype(jnp.int32)


def owned_unique_local(ids_col: jnp.ndarray, plan: RowShardPlan,
                       capacity: int, axis_name: str = "model"):
    """Per-device dedup of the ids this shard owns, inside ``shard_map``.

    ``ids_col`` is the *global* batch column (all-gather the batch's int32
    ids over "data" first — a few KB). Every data slice of a model-shard
    runs the identical computation, so the slot assignment is replicated
    without a dedicated collective, and the sort never crosses devices.

    The train step now uses the staged ``slice_unique_counts`` ->
    all-gather -> ``owned_unique_weighted`` pipeline instead (same slots,
    smaller "data" collective); this single-stage form remains the oracle
    the staged one is tested against.

    Returns ``(local_rows [capacity], counts [capacity], overflow bool)``
    with the ``ShardUniqueSets`` slot conventions.
    """
    from ..models.embedding import unique_owned_ids

    r = jax.lax.axis_index(axis_name)
    uids, counts, overflow = unique_owned_ids(
        ids_col, plan.shard_of(ids_col) == r, plan.vocab, capacity)
    return _local_rows(uids, plan), counts, overflow


def slice_unique_counts(ids_col: jnp.ndarray, vocab: int, capacity: int):
    """Stage 1 of the staged dedup: one data slice's column deduplicated
    with occurrence counts, before any collective.

    ``capacity`` must be the exact ``min(len(ids_col), vocab)`` — a slice
    set that drops ids would silently lose gradient slots downstream (the
    per-*shard* capacity is the one that may be capped; its overflow has a
    dense fallback). Pads hold the ``vocab`` sentinel with count 0.
    """
    uids, counts = jnp.unique(ids_col, size=capacity, fill_value=vocab,
                              return_counts=True)
    real = uids < vocab
    return (uids.astype(jnp.int32),
            jnp.where(real, counts, 0).astype(jnp.float32))


def owned_unique_weighted(gids: jnp.ndarray, gcnts: jnp.ndarray,
                          plan: RowShardPlan, capacity: int,
                          axis_name: str = "model"):
    """Stage 2 of the staged dedup, inside ``shard_map``: the owned subset
    of the all-gathered per-slice unique sets, with the gathered counts
    summed per slot.

    ``gids``/``gcnts`` are the "data"-axis concatenation of every slice's
    ``slice_unique_counts`` output (an id two slices share appears twice;
    its counts add). Slots, counts, and the overflow flag are exactly those
    ``owned_unique_local`` computes from the raw gathered batch — the
    staged form just moves the O(batch) sort before the collective so the
    all-gather carries unique ids, and hands phase 2 a slot set compatible
    with ``rowgrad_slots``'s O(capacity) gradient assembly.

    Returns ``(local_rows [capacity], counts [capacity], overflow bool)``.
    """
    r = jax.lax.axis_index(axis_name)
    owned = (plan.shard_of(gids) == r) & (gids < plan.vocab)
    masked = jnp.where(owned, gids, plan.vocab)
    uids, inv = jnp.unique(masked, size=capacity + 1, fill_value=plan.vocab,
                           return_inverse=True)
    counts = jax.ops.segment_sum(
        jnp.where(owned, gcnts, 0.0), inv.reshape(-1),
        num_segments=capacity + 1)
    real = uids < plan.vocab
    counts = jnp.where(real, counts, 0.0)
    overflow = uids[capacity] < plan.vocab
    return (_local_rows(uids[:capacity], plan),
            counts[:capacity].astype(jnp.float32), overflow)


def full_counts_from_gathered(gids: jnp.ndarray, gcnts: jnp.ndarray,
                              plan: RowShardPlan,
                              axis_name: str = "model") -> jnp.ndarray:
    """CowClip's per-local-row global counts ``[rows_per_shard]`` for the
    dense fallback branch, from the all-gathered slice unique sets — the
    staged replacement for ``psum(counts_partial(...), "data")`` (the
    gathered sets already cover the global batch, so no extra collective).
    """
    r = jax.lax.axis_index(axis_name)
    owned = (plan.shard_of(gids) == r) & (gids < plan.vocab)
    local = jnp.where(owned, plan.local_row(gids), plan.rows_per_shard)
    return jax.ops.segment_sum(jnp.where(owned, gcnts, 0.0), local,
                               num_segments=plan.rows_per_shard)


def rowgrad_slots(g_col: jnp.ndarray, ids_col: jnp.ndarray,
                  plan: RowShardPlan, uloc: jnp.ndarray,
                  axis_name: str = "model") -> jnp.ndarray:
    """This data slice's contribution to the ``[capacity, dim]`` row
    gradient on the slot set ``uloc``; ``psum`` over "data" completes it.

    The slot-level transpose of the masked lookup: each owned batch id is
    located in the (ascending, pad=``rows_per_shard``) slot set by binary
    search and its cotangent segment-summed onto the slot — O(batch +
    capacity) work and memory, against ``rowgrad_partial``'s
    O(rows_per_shard) full-row materialization. Only valid when the slot
    set cannot have overflowed (every owned id then has a slot; the train
    step guarantees this by routing overflow-capable fields through the
    full-row path).
    """
    from .sharded import owned_mask_and_rows

    capacity = uloc.shape[0]
    mine, local = owned_mask_and_rows(ids_col, plan, axis_name)
    slot = jnp.searchsorted(uloc, local).astype(jnp.int32)
    clipped = jnp.minimum(slot, capacity - 1)
    hit = mine & (jnp.take(uloc, clipped) == local)
    slot = jnp.where(hit, clipped, capacity)
    contrib = jnp.where(hit[:, None], g_col, jnp.zeros_like(g_col))
    return jax.ops.segment_sum(contrib, slot,
                               num_segments=capacity + 1)[:capacity]


# ---------------------------------------------------------------------------
# per-device (inside shard_map) phases
# ---------------------------------------------------------------------------


def catchup_depth_slots(ls, uloc, counts, t):
    """Max pending-decay depth over this shard's touched slots at step ``t``
    — the ``aux["catchup_depth_max"]`` diagnostic. A slot touched last step
    has depth 0; a first-touch slot has depth t-1. Pad slots (count 0)
    contribute 0."""
    safe = jnp.minimum(uloc, ls.shape[0] - 1)
    k = (t - 1) - jnp.take(ls, safe)
    return jnp.max(jnp.where(counts > 0, k, 0)).astype(jnp.int32)


def update_phase(w, m, v, ls, uloc, counts, overflow, g_slots, g_full,
                 cnt_full, t, *, clip=True, r=1.0, zeta=1e-5, lr=1e-4,
                 l2=1e-5, b1=0.9, b2=0.999, eps=1e-8):
    """Post-backward phase on one (field, group) shard, starting from the
    *raw* (w, m, v, ls) tensors — the forward never scatters into them
    (its lookup applies pending decay inline), so this phase owns the whole
    gather -> closed-form catch-up -> CowClip/L2/Adam -> scatter chain.

    Sparse branch: gather the touched rows and apply their pending decay in
    one closed-form multiply, take the psum'd row gradient at the touched
    slots — ``g_slots`` ([capacity, dim], from ``rowgrad_slots``) when
    overflow is statically impossible, else gathered from the full-row
    ``g_full`` — run CowClip -> coupled L2 -> Adam on the caught-up rows,
    scatter back into the raw tables (untouched rows stay byte-identical),
    and stamp ``last_step = t`` on the touched rows only (everything else
    keeps accruing lazy decay). Overflow branch: closed-form catch-up of the
    *whole* shard, then the dense per-shard ``shard_update``,
    ``last_step = t`` everywhere.

    Returns ``(new_w, new_m, new_v, new_ls)``. ``overflow`` may be the
    static ``False`` (capacity equals the exact per-shard default, so
    overflow is impossible — the fallback branch is then never traced);
    ``g_full``/``cnt_full`` are only read by the fallback machinery and may
    be None when overflow is impossible (``g_slots`` may in turn be None
    when it is not).
    """
    rows = w.shape[0]
    safe = jnp.minimum(uloc, rows - 1)
    adam_kw = dict(lr=lr, l2=l2, b1=b1, b2=b2, eps=eps)

    def sparse_branch(_):
        with jax.named_scope("row_gather_catchup"):
            w_rows, m_rows, v_rows = cc_ops.sparse_gather_catchup(
                w, m, v, ls, uloc, t, **adam_kw)
        g_rows = g_slots if g_slots is not None else g_full[safe]
        with jax.named_scope("row_update_scatter"):
            return cc_ops.sparse_update_scatter(
                w, m, v, ls, uloc, counts, w_rows, g_rows, m_rows, v_rows, t,
                r=r, zeta=zeta, clip=clip, **adam_kw)

    if overflow is False:
        return sparse_branch(None)

    def dense_branch(_):
        wc, mc, vc = decay_catchup_rows(w, m, v, ls, t - 1, **adam_kw)
        wc = wc.astype(w.dtype)
        w2, m2, v2 = shard_update(
            wc, g_full, cnt_full, mc, vc, t, clip=clip,
            r=r, zeta=zeta, **adam_kw)
        return w2, m2, v2, jnp.full_like(ls, t)

    return jax.lax.cond(overflow, dense_branch, sparse_branch, None)
