"""Embedding substrate: per-field tables, lookup, and batch id counts.

The embedding layer is where CowClip lives (99.9% of CTR model params, paper
Table 1). Layout decisions:

* One table per categorical field, ``[vocab_f, dim]`` — an id's vector is a
  *row* (the paper's "column"). Tables live under ``params["embed"]``.
* Forward lookup is ``jnp.take`` (gather); under pjit with row-sharded tables
  XLA partitions this into the standard all-gather-free dynamic-slice +
  all-reduce pattern.
* This module is the single-device substrate. Where tables *live* — dense,
  unique-id sparse, or row-sharded over a mesh — is the EmbeddingStore's
  decision (``repro.embed``); the explicit per-shard lookup/update math for
  the sharded placement is in ``repro.embed.sharded``.

Sparse unique-id layer
----------------------
A batch — even at the paper's 128K scale — touches only the ids that occur
in it, so the update path can work on ``[n_unique, dim]`` gathered rows
instead of streaming the whole ``[vocab, dim]`` table (the layout every
terabyte-scale CTR system uses; arXiv:2201.05500, arXiv:2209.05310).
``unique_ids`` deduplicates one field's batch column with a **static padded
capacity** (jit-stable shapes), through ``sort_unique``: three sorts, one
cumsum and elementwise ops, no scatter and no gather. Its output is
``jnp.unique(..., size=capacity, fill_value=vocab, return_inverse=True,
return_counts=True)``'s bit for bit, and so ``np.unique``'s sorted-ascending
ids with ``vocab`` pads:

* slots ``[0, n_unique)`` hold the batch's distinct ids ascending; padding
  slots hold ``vocab`` (one past the last row) so scatters with
  ``mode='drop'`` ignore them and their counts are 0.
* batch occurrence counts (CowClip's ``cnt``, Alg. 1 line 7) come out of the
  same dedup pass over the *unique set* — no ``[vocab]`` segment_sum.
* **overflow** (more distinct ids than ``capacity`` — impossible at the
  default ``capacity = min(batch, vocab)``): the ``capacity`` smallest ids
  are kept; dropped ids alias the last kept slot in the forward (their
  gradient lands there) and receive no update themselves. Overflow trades
  exactness for a hard memory bound; detect it via ``counts.sum() < batch``.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def init_field_tables(
    key: jax.Array,
    vocab_sizes: Sequence[int],
    dim: int,
    sigma: float = 1e-4,
    dtype=jnp.float32,
) -> dict:
    """N(0, sigma) tables, one per field (paper: sigma=1e-4 base, 1e-2 for
    CowClip's larger-init variant)."""
    keys = jax.random.split(key, len(vocab_sizes))
    return {
        f"field_{i}": (sigma * jax.random.normal(k, (v, dim))).astype(dtype)
        for i, (k, v) in enumerate(zip(keys, vocab_sizes))
    }


def lookup(tables: dict, ids: jnp.ndarray, dtype=None) -> jnp.ndarray:
    """Gather per-field embeddings.

    Args:
      tables: {"field_i": [vocab_i, dim]}
      ids:    [batch, n_fields] int32
      dtype:  optional activation dtype; the gathered rows are cast
              per-column before stacking (mixed precision: the f32 master
              tables stay put, only the [batch, dim] activations narrow,
              and the cast's transpose widens cotangents back to f32).
    Returns:
      [batch, n_fields, dim]
    """
    cols = [
        jnp.take(tables[f"field_{i}"], ids[:, i], axis=0)
        for i in range(ids.shape[1])
    ]
    if dtype is not None:
        cols = [c.astype(dtype) for c in cols]
    return jnp.stack(cols, axis=1)


class UniqueField(NamedTuple):
    """Static-size dedup of one field's batch ids (a pytree node).

    uids:   [capacity] int32, distinct batch ids ascending; pad slots hold
            ``vocab`` (out of range -> dropped by ``mode='drop'`` scatters).
    inv:    [batch] int32, slot of each batch element's id. On capacity
            overflow, dropped ids carry out-of-range slots that JAX's gather
            clips to the last kept slot.
    counts: [capacity] float32 batch occurrence count per slot (0 on pads).
    """

    uids: jnp.ndarray
    inv: jnp.ndarray
    counts: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.uids.shape[0]

    def n_unique(self) -> jnp.ndarray:
        """Number of real (non-pad) slots, traced."""
        return jnp.sum((self.counts > 0).astype(jnp.int32))


def sort_unique(col: jnp.ndarray, vocab: int, capacity: int):
    """``(uids, inv, counts)`` of a 1-D column, all int32, as
    ``jnp.unique(col, size=capacity, fill_value=vocab, return_inverse=True,
    return_counts=True)`` gives them, from sorts alone.

    1. Sort the ids, carrying their positions: ``s``, ``perm``.
    2. ``first`` marks each run's first slot; ``slot = cumsum(first) - 1``
       is each sorted element's slot.
    3. Sort the run starts (every other position keyed past the end) with
       their ids: the first ``capacity`` are the uids, and the gaps between
       consecutive starts, the last one closed by ``b``, the counts.
    4. Sort ``slot`` back into batch order by ``perm``: the inverse.

    A slot past ``capacity`` (overflow) keeps its rank as ``inv``, which the
    forward's gather clips to the last kept slot.
    """
    b = col.shape[0]
    iota = lax.iota(jnp.int32, b)
    s, perm = lax.sort((col, iota), num_keys=1, is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    slot = jnp.cumsum(first, dtype=jnp.int32) - 1
    starts, vals = lax.sort((jnp.where(first, iota, b), s), num_keys=1,
                            is_stable=False)
    if capacity + 1 > b:
        starts = jnp.pad(starts, (0, capacity + 1 - b), constant_values=b)
        vals = jnp.pad(vals, (0, capacity + 1 - b))
    starts = starts[:capacity + 1]
    uids = jnp.where(starts[:capacity] < b, vals[:capacity], vocab)
    _, inv = lax.sort((perm, slot), num_keys=1, is_stable=False)
    return (uids.astype(jnp.int32), inv,
            starts[1:] - starts[:capacity])


def unique_ids(ids_col: jnp.ndarray, vocab: int, capacity: int) -> UniqueField:
    """Deduplicate one field's batch column into a padded-capacity slot set."""
    uids, inv, counts = sort_unique(ids_col, vocab, capacity)
    return UniqueField(uids=uids, inv=inv, counts=counts.astype(jnp.float32))


def batch_unique(
    ids: jnp.ndarray,
    vocab_sizes: Sequence[int],
    capacity: int = 0,
) -> dict:
    """Per-field dedup of a [batch, n_fields] id matrix.

    ``capacity`` <= 0 selects the exact default ``min(batch, vocab_f)`` per
    field; a positive value caps every field at ``min(capacity, vocab_f)``.
    Returns ``{"field_i": UniqueField}``.
    """
    b = ids.shape[0]
    out = {}
    for i, v in enumerate(vocab_sizes):
        cap = min(b, v) if capacity <= 0 else min(capacity, v)
        out[f"field_{i}"] = unique_ids(ids[:, i], v, cap)
    return out


def unique_owned_ids(
    ids_col: jnp.ndarray,
    owned: jnp.ndarray,
    vocab: int,
    capacity: int,
):
    """Dedup the subset of a batch column selected by ``owned``.

    The per-shard variant of ``unique_ids``: non-owned ids are masked to the
    ``vocab`` sentinel before the dedup, so the unique set covers only the
    ids ``owned`` flags — the rows one model-shard is responsible for.
    Because the sentinel itself occupies a slot when any id is masked, the
    dedup runs at ``capacity + 1`` and the sentinel slot (always last — the
    sentinel is the largest value) is dropped.

    Returns ``(uids, counts, overflow)``:
      uids:     [capacity] int32 distinct owned ids ascending; pad slots
                hold ``vocab``.
      counts:   [capacity] float32 batch occurrence counts (0 on pads).
      overflow: bool scalar — more than ``capacity`` distinct owned ids in
                the batch (the kept slots are then the ``capacity`` smallest;
                callers must fall back to a dense update to stay exact).
    """
    masked = jnp.where(owned, ids_col, vocab)
    uids, counts = jnp.unique(masked, size=capacity + 1, fill_value=vocab,
                              return_counts=True)
    real = uids < vocab
    counts = jnp.where(real, counts, 0)
    # slot `capacity` holding a real id means at least capacity+1 distinct
    # owned ids were present — the dedup dropped some
    overflow = uids[capacity] < vocab
    return (uids[:capacity].astype(jnp.int32),
            counts[:capacity].astype(jnp.float32), overflow)


def gather_rows(tables: dict, uniq: dict) -> dict:
    """Gather each field's unique rows: ``{"field_i": [capacity_i, dim]}``.

    Pad slots (uid == vocab) clip to the last row — garbage values that are
    never read back (inv never points at a pad slot) nor scattered.
    """
    return {f: tables[f][u.uids] for f, u in uniq.items()}


def scatter_rows(tables: dict, uniq: dict, rows: dict) -> dict:
    """Write updated unique rows back; pad slots (uid out of range) drop."""
    return {
        f: tables[f].at[uniq[f].uids].set(
            rows[f].astype(tables[f].dtype), mode="drop")
        for f in tables
    }


def lookup_rows(rows: dict, uniq: dict, dtype=None) -> jnp.ndarray:
    """Forward lookup from gathered unique rows -> [batch, n_fields, dim].

    ``dtype`` casts each column like ``lookup`` does — note the cast sits
    *after* the unique-row gather, so the sparse path's row cotangents
    (what CowClip clips and Adam consumes) stay f32.
    """
    cols = [rows[f"field_{i}"][uniq[f"field_{i}"].inv]
            for i in range(len(uniq))]
    if dtype is not None:
        cols = [c.astype(dtype) for c in cols]
    return jnp.stack(cols, axis=1)


def field_counts(ids: jnp.ndarray, vocab_sizes: Sequence[int]) -> dict:
    """Per-field id occurrence counts in the batch (CowClip's ``cnt``),
    for the dense/fused paths: one ``segment_sum`` per field, fusing with
    the backward scatter-add. Returns a tree matching the tables tree with
    [vocab_f] float32 leaves. The sparse path never materializes these —
    its counts come out of the ``batch_unique`` dedup directly
    (``UniqueField.counts``).
    """
    b = ids.shape[0]
    ones = jnp.ones((b,), jnp.float32)
    return {
        f"field_{i}": jax.ops.segment_sum(
            ones, ids[:, i], num_segments=v
        )
        for i, v in enumerate(vocab_sizes)
    }


def token_counts(tokens: jnp.ndarray, vocab_size: int) -> jnp.ndarray:
    """Occurrence counts of each vocab id in an LM batch ([B, S] int32)."""
    flat = tokens.reshape(-1)
    return jax.ops.segment_sum(
        jnp.ones_like(flat, jnp.float32), flat, num_segments=vocab_size
    )
