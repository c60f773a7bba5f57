"""Row-sharded embedding placement: the per-shard math under ``shard_map``.

Tables are partitioned by *row* (id) over the mesh's ``"model"`` axis while
the batch splits over ``"data"`` — the hierarchical layout every
terabyte-scale CTR system converges on (arXiv:2201.05500, arXiv:2209.05310):
10^8 embedding params shard, the ~0.5M dense tower replicates. CowClip makes
the embedding optimizer *collective-free* under this placement: the clip
threshold, L2 decay and Adam moments are all row-local, so once the gradient
rows and batch counts are on the owning shard, the whole update runs without
communication.

Two id -> (shard, local row) mappings, both with a padded
``rows_per_shard = ceil(vocab / n_shards)``:

* ``div`` (contiguous): shard ``id // R``, local ``id % R``. Physical layout
  equals logical row order, i.e. a padded table under
  ``NamedSharding(mesh, P("model", None))`` — the production default.
* ``mod`` (round-robin): shard ``id % S``, local ``id // S``. Spreads hot
  low ids (Zipf-skewed CTR vocabularies sort by frequency) evenly across
  shards. Physical layout is a row permutation of logical order, so the
  train step converts logical -> physical -> logical around the ``shard_map``
  (one all-to-all-shaped gather each way; ``div`` skips both).

Per-device forward lookup is mask-and-psum: out-of-shard ids read local row
0 and are zeroed, then one ``psum`` over ``"model"`` assembles the full
[batch_local, dim] embedding. The backward is the transpose: per-shard
``segment_sum`` of the embedding cotangent restricted to owned ids, then a
``psum`` over ``"data"`` to accumulate every batch slice's contribution.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map  # noqa: F401  (re-exported for the step builders)
from jax.sharding import AxisType, Mesh

from ..core.cowclip import cowclip_table
from ..core.optim import decay_factor, sparse_adam_rows

SCHEMES = ("div", "mod")


@dataclasses.dataclass(frozen=True)
class RowShardPlan:
    """Static id -> (shard, local row) mapping for one field's table."""

    vocab: int
    n_shards: int
    scheme: str = "div"

    def __post_init__(self):
        if self.scheme not in SCHEMES:
            raise ValueError(f"unknown partition scheme {self.scheme!r}; "
                             f"expected one of {SCHEMES}")
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")

    @property
    def rows_per_shard(self) -> int:
        return math.ceil(self.vocab / self.n_shards)

    @property
    def padded_vocab(self) -> int:
        return self.rows_per_shard * self.n_shards

    def shard_of(self, ids: jnp.ndarray) -> jnp.ndarray:
        if self.scheme == "div":
            return ids // self.rows_per_shard
        return ids % self.n_shards

    def local_row(self, ids: jnp.ndarray) -> jnp.ndarray:
        if self.scheme == "div":
            return ids % self.rows_per_shard
        return ids // self.n_shards

    # ---- physical <-> logical layout -------------------------------------
    # Physical = concat of per-shard blocks (what P("model") sharding sees);
    # logical = row i holds id i. For "div" they coincide.

    @property
    def is_identity_layout(self) -> bool:
        return self.scheme == "div" or self.n_shards == 1

    def logical_of_physical(self) -> np.ndarray:
        """perm with physical_table = logical_padded[perm]."""
        p = np.arange(self.padded_vocab)
        if self.is_identity_layout:
            return p
        r, l = p // self.rows_per_shard, p % self.rows_per_shard
        return l * self.n_shards + r

    def physical_of_logical(self) -> np.ndarray:
        """perm with logical_padded = physical_table[perm]."""
        inv = np.empty(self.padded_vocab, dtype=np.int64)
        inv[self.logical_of_physical()] = np.arange(self.padded_vocab)
        return inv


def make_plans(vocab_sizes: Sequence[int], n_shards: int,
               scheme: str = "div") -> Dict[str, RowShardPlan]:
    return {f"field_{i}": RowShardPlan(v, n_shards, scheme)
            for i, v in enumerate(vocab_sizes)}


def pad_rows(table: jnp.ndarray, padded_vocab: int) -> jnp.ndarray:
    """Zero-pad a [vocab, dim] table to [padded_vocab, dim]. Pad rows start
    at zero and stay there: they get zero gradient and zero counts, and the
    geometric coupled-L2 decay of an exactly-zero row is zero
    (``0 * (1 - lr*l2)^k == 0``)."""
    extra = padded_vocab - table.shape[0]
    if extra == 0:
        return table
    return jnp.concatenate(
        [table, jnp.zeros((extra,) + table.shape[1:], table.dtype)], axis=0)


def unpad_rows(table: jnp.ndarray, vocab: int) -> jnp.ndarray:
    return table if table.shape[0] == vocab else table[:vocab]


def pad_embed_tree(embed: dict, plans: Dict[str, RowShardPlan]) -> dict:
    """Pad every group's tables ({"fm": {...}, "lin": {...}}) to the plan's
    padded vocab (logical row order)."""
    return {g: {f: pad_rows(w, plans[f].padded_vocab)
                for f, w in tables.items()}
            for g, tables in embed.items()}


def unpad_embed_tree(embed: dict, plans: Dict[str, RowShardPlan]) -> dict:
    return {g: {f: unpad_rows(w, plans[f].vocab) for f, w in tables.items()}
            for g, tables in embed.items()}


def to_physical(embed: dict, plans: Dict[str, RowShardPlan]) -> dict:
    """Logical (padded) row order -> per-shard physical order. Identity for
    the "div" scheme; a static row permutation (all-to-all under SPMD) for
    "mod"."""
    return {
        g: {f: (w if plans[f].is_identity_layout
                else jnp.take(w, plans[f].logical_of_physical(), axis=0))
            for f, w in tables.items()}
        for g, tables in embed.items()
    }


def to_logical(embed: dict, plans: Dict[str, RowShardPlan]) -> dict:
    return {
        g: {f: (w if plans[f].is_identity_layout
                else jnp.take(w, plans[f].physical_of_logical(), axis=0))
            for f, w in tables.items()}
        for g, tables in embed.items()
    }


# ---------------------------------------------------------------------------
# per-device (inside shard_map) building blocks
# ---------------------------------------------------------------------------


def owned_mask_and_rows(ids_col: jnp.ndarray, plan: RowShardPlan,
                        axis_name: str = "model"):
    """(mine, local) for one field's batch column on the current shard:
    ``mine`` flags ids this shard owns; ``local`` is their local row (0 for
    foreign ids — always masked by the caller)."""
    r = jax.lax.axis_index(axis_name)
    mine = plan.shard_of(ids_col) == r
    local = jnp.where(mine, plan.local_row(ids_col), 0)
    return mine, local


def lookup_partial(shard: jnp.ndarray, ids_col: jnp.ndarray,
                   plan: RowShardPlan, axis_name: str = "model") -> jnp.ndarray:
    """This shard's additive contribution to the batch lookup: owned ids'
    rows, zeros elsewhere. ``psum`` over ``axis_name`` completes the gather."""
    mine, local = owned_mask_and_rows(ids_col, plan, axis_name)
    rows = jnp.take(shard, local, axis=0)                    # [b_loc, dim]
    return jnp.where(mine[:, None], rows, jnp.zeros_like(rows))


def decayed_lookup_partial(shard: jnp.ndarray, ls_shard: jnp.ndarray,
                           ids_col: jnp.ndarray, plan: RowShardPlan,
                           step: jnp.ndarray, factor: float,
                           axis_name: str = "model") -> jnp.ndarray:
    """``lookup_partial`` with the row's pending lazy-L2 decay applied
    inline: each owned id's row is multiplied by ``factor**k`` where
    ``k = (step - 1) - last_step[row]`` pending decay-only steps — exactly
    the closed-form catch-up (``core.optim.decay_catchup_rows``), fused into
    the gather so the forward can read *raw* tables. This is what decouples
    the tower forward from the update path's dedup/collectives in the
    sharded_sparse step: nothing has to be scattered into the table before
    the lookup. ``k == 0`` multiplies by exactly 1.0, so caught-up rows pass
    through bit-identically."""
    mine, local = owned_mask_and_rows(ids_col, plan, axis_name)
    rows = jnp.take(shard, local, axis=0)                    # [b_loc, dim]
    k = ((step - 1) - jnp.take(ls_shard, local)).astype(jnp.float32)
    scale = jnp.where(k > 0, jnp.float32(factor) ** k, jnp.float32(1.0))
    rows = rows * scale[:, None]
    return jnp.where(mine[:, None], rows, jnp.zeros_like(rows))


def rowgrad_partial(g_col: jnp.ndarray, ids_col: jnp.ndarray,
                    plan: RowShardPlan, axis_name: str = "model") -> jnp.ndarray:
    """Scatter the embedding cotangent [b_loc, dim] onto this shard's rows
    ([rows_per_shard, dim]); the transpose of ``lookup_partial``. Needs a
    ``psum`` over "data" to accumulate the other batch slices."""
    mine, local = owned_mask_and_rows(ids_col, plan, axis_name)
    contrib = jnp.where(mine[:, None], g_col, jnp.zeros_like(g_col))
    return jax.ops.segment_sum(contrib, local,
                               num_segments=plan.rows_per_shard)


def counts_partial(ids_col: jnp.ndarray, plan: RowShardPlan,
                   axis_name: str = "model") -> jnp.ndarray:
    """This batch slice's occurrence count of each owned id (CowClip's
    ``cnt`` restricted to the shard); ``psum`` over "data" globalizes it."""
    mine, local = owned_mask_and_rows(ids_col, plan, axis_name)
    return jax.ops.segment_sum(mine.astype(jnp.float32), local,
                               num_segments=plan.rows_per_shard)


def shard_update(w: jnp.ndarray, g: jnp.ndarray, cnt: jnp.ndarray,
                 m: jnp.ndarray, v: jnp.ndarray, step: jnp.ndarray, *,
                 clip: bool = True, r: float = 1.0, zeta: float = 1e-5,
                 lr: float = 1e-4, l2: float = 1e-5, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8):
    """The dense embedding-optimizer chain on one table shard. Entirely
    row-local: identical math to the substrate chain restricted to this
    shard's rows, so the sharded step matches the single-device dense path
    to float32 tolerance. Count-aware like ``core.optim.lazy_coupled_adam``:
    touched rows (cnt > 0) run CowClip -> coupled L2 -> Adam; absent rows
    take one geometric decay step ``w *= 1 - lr*l2`` with the Adam moments
    held."""
    w32 = w.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    m32 = m.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    if clip:
        g32 = cowclip_table(g32, w32, cnt, r=r, zeta=zeta)
    w2, m2, v2 = sparse_adam_rows(g32, w32, m32, v32, step,
                                  lr=lr, l2=l2, b1=b1, b2=b2, eps=eps)
    touched = (cnt > 0.0)[:, None]
    w2 = jnp.where(touched, w2, w32 * jnp.float32(decay_factor(lr, l2)))
    m2 = jnp.where(touched, m2, m32)
    v2 = jnp.where(touched, v2, v32)
    return w2.astype(w.dtype), m2.astype(m.dtype), v2.astype(v.dtype)


def batch_forward_backward(cfg, plans, fwd_tables, dense_params,
                           ids, feats, labels, n_data: int, *,
                           last_steps=None, step=None, factor=None):
    """The per-device forward/backward shared by both sharded train steps.

    Masked local lookup of each field (+psum over "model" to assemble the
    full [b_loc, F, dim] embedding), tower forward on the local batch
    slice, gradients taken w.r.t. the *assembled* embeddings (no
    collectives inside the grad — the scatter back onto local rows is done
    explicitly by the caller via ``rowgrad_partial``), loss and dense-tower
    grads psum'd over "data".

    With ``last_steps``/``step``/``factor`` (the lazy-decay placements) the
    lookup applies each row's pending decay inline
    (``decayed_lookup_partial``): ``fwd_tables`` are then the *raw* shards
    and the assembled embedding is still exact — since the gradient is taken
    w.r.t. the assembled embedding, not the table, the inline multiply
    changes nothing downstream, while freeing the forward from any
    data-dependence on pre-forward catch-up scatters.

    Returns ``(loss, g_emb, g_lin, g_dense)``; ``g_lin`` is None for
    models without the first-order LR stream.
    """
    from ..models import ctr as ctr_lib

    n_fields = cfg.n_fields
    b_global = ids.shape[0] * n_data

    def partial_lookup(tables, ls_tables):
        if ls_tables is None:
            cols = [lookup_partial(tables[f"field_{i}"], ids[:, i],
                                   plans[f"field_{i}"])
                    for i in range(n_fields)]
        else:
            cols = [decayed_lookup_partial(
                        tables[f"field_{i}"], ls_tables[f"field_{i}"],
                        ids[:, i], plans[f"field_{i}"], step, factor)
                    for i in range(n_fields)]
        return jnp.stack(cols, axis=1)                   # [b_loc, F, dim]

    def ls_group(g):
        return None if last_steps is None else last_steps[g]

    with jax.named_scope("embed_lookup_psum"):
        emb = jax.lax.psum(partial_lookup(fwd_tables["fm"], ls_group("fm")),
                           "model")
        lin_emb = (jax.lax.psum(
                       partial_lookup(fwd_tables["lin"], ls_group("lin")),
                       "model")
                   if "lin" in fwd_tables else None)

    def loss_fn(emb_args, dense_p):
        e, le = emb_args
        logits = ctr_lib._forward_from_emb(dense_p, cfg, e, le, feats)
        return jnp.sum(jax.nn.softplus(logits) - labels * logits) / b_global

    with jax.named_scope("tower_fwd_bwd"):
        if lin_emb is None:
            loss_loc, ((g_emb, _), g_dense) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))((emb, None), dense_params)
            g_lin = None
        else:
            loss_loc, ((g_emb, g_lin), g_dense) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))((emb, lin_emb), dense_params)

    with jax.named_scope("loss_dense_psum"):
        loss = jax.lax.psum(loss_loc, "data")
        g_dense = jax.lax.psum(g_dense, "data")
    return loss, g_emb, g_lin, g_dense


def make_prepare_export(plans, mesh):
    """The sharded family's param layout pair: ``prepare`` zero-pads every
    table to ``rows_per_shard * n_shards`` rows (pad rows stay exactly
    zero: zero grad, zero count, zero coupled-L2 decay) and device_puts
    rows over "model" via ``sharding.specs.ctr_param_spec``; ``export``
    strips the pad rows back off, so checkpoints are
    placement-independent."""
    from ..sharding.specs import infer_ctr_param_shardings

    def prepare(params):
        params = dict(params, embed=pad_embed_tree(params["embed"], plans))
        return jax.device_put(params, infer_ctr_param_shardings(params, mesh))

    def export(params):
        return dict(params, embed=unpad_embed_tree(params["embed"], plans))

    return prepare, export


def default_mesh():
    """All local devices as ("data", "model") = (1, n): table-sharding first,
    the placement this store exists for. Pass an explicit mesh to trade
    model-axis for data-axis parallelism."""
    n = jax.device_count()
    return jax.make_mesh((1, n), ("data", "model"))


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis typed ``Auto``.

    The sharded placements leave layout outside their ``shard_map`` to the
    compiler's sharding propagation (e.g. ``export`` slices pad rows off a
    row-sharded table). ``jax.make_mesh`` types axes ``Explicit`` by
    default, under which such a slice is a type error, so the placements
    retype whatever mesh they are given."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
