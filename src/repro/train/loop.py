"""CTR training loop: jit'd step, epochs, eval — the paper's experiment
driver (single host; the distributed variant lives in repro/launch/train.py).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import GradientTransformation, apply_updates
from ..core.builders import jit_step

logger = logging.getLogger(__name__)
from ..data.synthetic import CTRDataset, iterate_batches
from ..models import ctr
from ..models import embedding as embedding_lib
from ..models.ctr import CROSS_SCOPE  # noqa: F401  (see below)
from . import metrics

# The sparse step's phases, each a ``jax.named_scope`` in the order they
# run. Every device op of the step carries the innermost of these names in
# its ``op_name`` (the compiled HLO's metadata), so a profiler trace can put
# each op's time down to its phase.
STEP_SCOPES = ("dedup", "row_gather_catchup", "tower_fwd_bwd",
               "row_update_scatter", "dense_update", "step_counters")

# ``CROSS_SCOPE``, defined in ``models/ctr.py`` and imported above, names
# the cross network of ``dcn`` and ``dcnv2`` inside ``tower_fwd_bwd``. It
# is apart from ``STEP_SCOPES`` because the other models have no cross ops.


def make_train_step(cfg: ctr.CTRConfig, tx: GradientTransformation):
    """Returns jit'd (params, opt_state, batch) -> (params, opt_state, aux).

    The task loss is plain mean BCE; L2 enters through the optimizer
    (coupled, paper-faithful), and CowClip's counts come from one unique-id
    dedup per field. With ``cfg.sparse`` the forward runs through the
    unique-id gather layer (grads w.r.t. embeddings materialize on gathered
    rows and scatter back through the gather's backward) — same update
    semantics as the dense forward, routed through the sparse layout.

    Like every step factory here, the returned callable carries its pure
    body as ``.scan_step`` for the scan engine (repro.train.engine).
    """

    def loss_fn(params, ids, dense, labels):
        if cfg.sparse:
            uniq = ctr.unique_batch(cfg, ids)
            rows = ctr.gather_embed_rows(params, uniq)
            logits = ctr.apply_rows(rows, params["dense"], cfg, uniq, dense)
        else:
            logits = ctr.apply(params, cfg, ids, dense)
        return metrics.logloss(logits, labels), logits

    def step_impl(params, opt_state, batch):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch["ids"], batch["dense"], batch["labels"]
        )
        counts = ctr.batch_counts(cfg, batch["ids"], params)
        updates, opt_state = tx.update(grads, opt_state, params, counts=counts)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    return jit_step(step_impl)


def _is_uniq(x) -> bool:
    return isinstance(x, embedding_lib.UniqueField)


def _unzip3(tree_of_triples, like):
    """Split a tree whose leaves are 3-tuples into three trees shaped
    ``like`` (jax.tree.transpose over the shared embed-tree structure)."""
    outer = jax.tree.structure(like)
    inner = jax.tree.structure((0, 0, 0))
    return jax.tree.transpose(outer, inner, tree_of_triples)


def _uniq_tree(embed_params: dict, uniq: dict) -> dict:
    """Broadcast the per-field dedup over every embedding group (fm and lin
    tables of a field share ids, hence slots and counts)."""
    return {g: {f: uniq[f] for f in tables}
            for g, tables in embed_params.items()}


def make_fused_train_step(cfg: ctr.CTRConfig, hp, *, r: float = 1.0,
                          zeta: float = 1e-5, dense_tx=None):
    """Dense train step that runs every embedding table through one fused
    CowClip+L2+Adam update per table (``kernels.cowclip.fused_cowclip_adam``)
    instead of the composable transform chain. Dense tower still goes
    through the substrate optimizer. State: {"step", "m", "v"} trees for
    embeddings + the dense transform state.

    Always the dense layout: ``embed.store.store_for`` sends a ``fused``
    request with ``cfg.sparse`` to the sparse placement. Equivalence with
    the substrate is asserted in tests/test_train_integration.py.
    """
    from ..core import optim as optim_lib
    from ..kernels.cowclip import fused_cowclip_adam

    if dense_tx is None:
        dense_tx = optim_lib.adam(hp.dense_lr, l2=hp.dense_l2)

    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(jnp.zeros_like, params["embed"]),
            "v": jax.tree.map(jnp.zeros_like, params["embed"]),
            "dense": dense_tx.init(params["dense"]),
        }

    def loss_fn(params, ids, dense, labels):
        logits = ctr.apply(params, cfg, ids, dense)
        return metrics.logloss(logits, labels)

    def step_impl(params, state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch["ids"], batch["dense"], batch["labels"])
        counts = ctr.batch_counts(cfg, batch["ids"], params)
        t = state["step"] + 1

        # 1-dim LR tables are CowClip-exempt but share the update
        # (cowclip_table skips clipping when dim < 2).
        out = jax.tree.map(
            lambda w, g, c, m, v: fused_cowclip_adam(
                w, g, c, m, v, t, r=r, zeta=zeta,
                lr=hp.emb_lr, l2=hp.emb_l2),
            params["embed"], grads["embed"], counts, state["m"], state["v"],
        )
        new_embed, new_m, new_v = _unzip3(out, params["embed"])

        d_updates, d_state = dense_tx.update(
            grads["dense"], state["dense"], params["dense"])
        new_dense = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), params["dense"], d_updates)
        new_state = {"step": t, "m": new_m, "v": new_v, "dense": d_state}
        return {"embed": new_embed, "dense": new_dense}, new_state, {
            "loss": loss}

    return jit_step(step_impl), init


def make_sparse_train_step(cfg: ctr.CTRConfig, hp, *, r: float = 1.0,
                           zeta: float = 1e-5, dense_tx=None,
                           clip: bool = True, b1: float = 0.9,
                           b2: float = 0.999, eps: float = 1e-8):
    """The sparse unique-id train step: per step, each field's batch ids are
    deduplicated once and the embedding update runs entirely on the
    ``[n_unique, dim]`` gathered rows — gather -> lazy-L2-decay catch-up ->
    forward/backward on rows -> CowClip -> Adam -> scatter. Update HBM
    traffic is O(batch), not O(vocab). The row math is
    ``kernels.cowclip``'s jnp, compiled by XLA.

    Ids absent from a batch are not touched; their coupled-L2 decay accrues
    in a per-row ``last_step`` array and is replayed on next touch (or by
    ``flush``), keeping the path exactly equivalent to the dense one.

    Each phase runs under one of ``STEP_SCOPES``. Besides ``loss`` the aux
    holds ``catchup_depth_max``, the deepest pending catch-up among the
    touched rows (int32).

    The Adam moments of a table whose rows are narrow enough
    (``kernels.cowclip.ref.packs``) are stored packed, ``k = 128 // dim``
    rows to a 128-lane row (``ref.pack_rows``; ``ref.unpack_rows`` gives
    ``[V, dim]`` back): the row gathers and scatters then touch one lane
    row per id. The weights keep their public ``[V, dim]`` form.

    Returns ``(step, init, flush)``; ``flush(params, state)`` applies all
    pending decay (needed before eval / checkpoint / comparing against the
    dense path).
    """
    from ..core import optim as optim_lib
    from ..kernels import cowclip as cc_kernels
    from ..kernels.cowclip import ref as cc_ref

    if dense_tx is None:
        dense_tx = optim_lib.adam(hp.dense_lr, l2=hp.dense_l2)
    adam_kw = dict(lr=hp.emb_lr, l2=hp.emb_l2, b1=b1, b2=b2, eps=eps)

    def zero_moment(w):
        if cc_ref.packs(w.shape[1]):
            return jnp.zeros(cc_ref.packed_shape(*w.shape), w.dtype)
        return jnp.zeros_like(w)

    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zero_moment, params["embed"]),
            "v": jax.tree.map(zero_moment, params["embed"]),
            "last_step": jax.tree.map(
                lambda t: jnp.zeros((t.shape[0],), jnp.int32),
                params["embed"]),
            "dense": dense_tx.init(params["dense"]),
        }

    def loss_fn(rows, dense_params, uniq, dense_feats, labels):
        logits = ctr.apply_rows(rows, dense_params, cfg, uniq, dense_feats)
        return metrics.logloss(logits, labels)

    def step_impl(params, state, batch):
        t = state["step"] + 1
        with jax.named_scope("dedup"):
            uniq = ctr.unique_batch(cfg, batch["ids"])
            utree = _uniq_tree(params["embed"], uniq)

        with jax.named_scope("step_counters"):
            # diagnostic: deepest pending-decay catch-up among this step's
            # touched rows (0 when every touched id was also in the last
            # batch)
            depth_tree = jax.tree.map(
                lambda u, ls: jnp.max(jnp.where(
                    u.counts > 0,
                    (t - 1) - ls[jnp.minimum(u.uids, ls.shape[0] - 1)], 0)),
                utree, state["last_step"], is_leaf=_is_uniq)
            depth = jnp.max(jnp.stack(jax.tree.leaves(depth_tree)))

        # gather + apply pending decay (closed form, O(1) in depth) so the
        # forward sees rows exactly as the dense path would at step t
        with jax.named_scope("row_gather_catchup"):
            caught = jax.tree.map(
                lambda u, w, m, v, ls: cc_kernels.sparse_gather_catchup(
                    w, m, v, ls, u.uids, t, **adam_kw),
                utree, params["embed"], state["m"], state["v"],
                state["last_step"], is_leaf=_is_uniq,
            )
        w_rows, m_rows, v_rows = _unzip3(caught, params["embed"])

        with jax.named_scope("tower_fwd_bwd"):
            loss, (g_rows, g_dense) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(
                w_rows, params["dense"], uniq, batch["dense"],
                batch["labels"])

        # CowClip -> coupled L2 -> Adam on the touched rows, scattered back;
        # untouched rows keep accruing lazy decay via last_step
        with jax.named_scope("row_update_scatter"):
            out = jax.tree.map(
                lambda u, w, m, v, ls, wr, gr, mr, vr:
                cc_kernels.sparse_update_scatter(
                    w, m, v, ls, u.uids, u.counts, wr, gr, mr, vr, t,
                    r=r, zeta=zeta, clip=clip, **adam_kw),
                utree, params["embed"], state["m"], state["v"],
                state["last_step"], w_rows, g_rows, m_rows, v_rows,
                is_leaf=_is_uniq,
            )
        outer = jax.tree.structure(params["embed"])
        inner = jax.tree.structure((0, 0, 0, 0))
        new_embed, new_m, new_v, new_ls = jax.tree.transpose(
            outer, inner, out)
        new_embed = jax.tree.map(
            lambda w, p: w.astype(p.dtype), new_embed, params["embed"])

        with jax.named_scope("dense_update"):
            d_updates, d_state = dense_tx.update(
                g_dense, state["dense"], params["dense"])
            new_dense = jax.tree.map(
                lambda p, u: p + u.astype(p.dtype), params["dense"],
                d_updates)

        new_state = {"step": t, "m": new_m, "v": new_v, "last_step": new_ls,
                     "dense": d_state}
        return {"embed": new_embed, "dense": new_dense}, new_state, {
            "loss": loss, "catchup_depth_max": depth.astype(jnp.int32)}

    return jit_step(step_impl), init, _make_lazy_flush(adam_kw)


def _make_lazy_flush(adam_kw: dict):
    """The flush shared by every lazy-decay placement (sparse and
    sharded_sparse): apply each row's pending decay-only steps through the
    current step, then stamp ``last_step = step`` everywhere. Idempotent —
    a second call replays zero iterations and rewrites identical values.

    The Adam moments pass through as they are, in whichever form the
    placement stores them (decay-only steps never move them), outside the
    jitted settle: as its outputs they would be fresh copies of both moment
    tables, doubling the state on the device."""
    from ..core import optim as optim_lib

    @jax.jit
    def settle(embed, m, v, last_step, step):
        caught = jax.tree.map(
            lambda w, m_, v_, ls: optim_lib.decay_catchup_rows(
                w, m_, v_, ls, step, **adam_kw)[0].astype(w.dtype),
            embed, m, v, last_step)
        return caught, jax.tree.map(lambda ls: jnp.full_like(ls, step),
                                    last_step)

    def flush(params, state):
        new_embed, new_ls = settle(params["embed"], state["m"], state["v"],
                                   state["last_step"], state["step"])
        return (dict(params, embed=new_embed),
                dict(state, last_step=new_ls))

    return flush


def make_sharded_train_step(cfg: ctr.CTRConfig, hp, mesh, *,
                            scheme: str = "div", r: float = 1.0,
                            zeta: float = 1e-5, dense_tx=None,
                            clip: bool = True, b1: float = 0.9,
                            b2: float = 0.999, eps: float = 1e-8):
    """The mesh-parallel train step: embedding tables row-sharded over the
    mesh's ``"model"`` axis, batch split over ``"data"``, dense tower
    replicated — one ``shard_map`` per step (repro.embed.sharded holds the
    per-shard building blocks).

    Per device: masked local lookup of owned ids (+``psum`` over "model" to
    assemble the full embedding), forward/backward of the tower on the local
    batch slice, then the embedding cotangent is scattered onto local rows
    and ``psum``'d over "data" together with CowClip's per-id counts. The
    optimizer update itself (CowClip -> coupled L2 -> Adam) is row-local and
    therefore collective-free — the paper-technique-aligned property that
    makes row sharding the right CTR placement. Dense-tower grads ``psum``
    over "data" and go through the substrate chain, replicated.

    Returns ``(step, init, flush, prepare, export)``: ``prepare`` pads each
    table to ``rows_per_shard * n_shards`` rows (zero pad rows stay exactly
    zero: zero grad, zero count, and coupled-L2 decay of a zero row is zero)
    and device_puts rows over "model" via ``sharding.specs.ctr_param_spec``;
    ``export`` strips the pad rows back off for placement-independent
    checkpoints; ``flush`` is the identity (nothing deferred — absent ids
    decay eagerly on their shard every step, exactly like the dense path).
    """
    from jax.sharding import PartitionSpec as P

    from ..core import builders as builders_lib
    from ..embed import sharded as shard_lib

    if dense_tx is None:
        dense_tx = builders_lib.dense_tower_tx(hp, b1=b1, b2=b2, eps=eps)
    n_data = mesh.shape["data"]
    n_model = mesh.shape["model"]
    plans = shard_lib.make_plans(cfg.vocab_sizes, n_model, scheme)
    upd_kw = dict(clip=clip, r=r, zeta=zeta, lr=hp.emb_lr, l2=hp.emb_l2,
                  b1=b1, b2=b2, eps=eps)
    n_fields = cfg.n_fields

    EMB = P("model", None)   # prefix spec: broadcasts over the embed tree
    REP = P()
    prepare, export = shard_lib.make_prepare_export(plans, mesh)

    def init(params):
        def zeros_like_placed(w):
            return jax.device_put(jnp.zeros(w.shape, w.dtype), w.sharding)

        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zeros_like_placed, params["embed"]),
            "v": jax.tree.map(zeros_like_placed, params["embed"]),
            "dense": dense_tx.init(params["dense"]),
        }

    def local_step(embed_sh, m_sh, v_sh, dense_params, t, ids, feats, labels):
        # ids/feats/labels are this data-slice's batch shard, replicated
        # along "model"; embed/m/v are this model-slice's table rows,
        # replicated along "data". Gradients come back w.r.t. the assembled
        # embeddings; the scatter onto local rows (the transpose of the
        # masked lookup) is explicit via rowgrad_partial below.
        #
        # Collective/compute overlap: CowClip's counts depend only on the
        # batch ids, so every per-field count psum over "data" is issued
        # *before* the tower forward; after the backward, every row-grad
        # psum launches before any shard update runs. The updates are
        # row-local and collective-free, so the scheduler can hide each
        # reduction behind the forward (counts) or behind the other
        # fields' optimizer math (row grads).
        with jax.named_scope("counts_psum"):
            cnt = {}
            for i in range(n_fields):
                f = f"field_{i}"
                cnt[f] = jax.lax.psum(
                    shard_lib.counts_partial(ids[:, i], plans[f]), "data")

        loss, g_emb, g_lin, g_dense = shard_lib.batch_forward_backward(
            cfg, plans, embed_sh, dense_params, ids, feats, labels, n_data)

        with jax.named_scope("rowgrad_psum"):
            g_rows = {g: {} for g in embed_sh}
            for i in range(n_fields):
                f = f"field_{i}"
                for group, g_batch in (("fm", g_emb), ("lin", g_lin)):
                    if group not in embed_sh:
                        continue
                    g_rows[group][f] = jax.lax.psum(
                        shard_lib.rowgrad_partial(g_batch[:, i, :],
                                                  ids[:, i], plans[f]),
                        "data")

        new_w = {g: {} for g in embed_sh}
        new_m = {g: {} for g in embed_sh}
        new_v = {g: {} for g in embed_sh}
        with jax.named_scope("shard_update"):
            for i in range(n_fields):
                f = f"field_{i}"
                for group in embed_sh:
                    new_w[group][f], new_m[group][f], new_v[group][f] = (
                        shard_lib.shard_update(
                            embed_sh[group][f], g_rows[group][f], cnt[f],
                            m_sh[group][f], v_sh[group][f], t, **upd_kw))
        return new_w, new_m, new_v, g_dense, loss

    # check_vma=False: the collectives are written out (every grad is
    # psum'd over "data" by hand). With jax's replication checker on, AD
    # would also sum the grads of the "data"-replicated inputs over "data"
    # itself, and the hand-written psum would count them n_data times.
    smapped = shard_lib.shard_map(
        local_step, mesh=mesh,
        in_specs=(EMB, EMB, EMB, REP, REP,
                  P("data", None), P("data", None), P("data")),
        out_specs=(EMB, EMB, EMB, REP, REP),
        check_vma=False,
    )

    def step_impl(params, state, batch):
        ids = batch["ids"]
        if ids.shape[0] % n_data:
            raise ValueError(
                f"batch {ids.shape[0]} not divisible by data axis {n_data}")
        t = state["step"] + 1
        # "mod" stores rows logically but shards them round-robin: convert
        # logical -> physical around the shard_map (identity under "div")
        w_p = shard_lib.to_physical(params["embed"], plans)
        m_p = shard_lib.to_physical(state["m"], plans)
        v_p = shard_lib.to_physical(state["v"], plans)
        new_w, new_m, new_v, g_dense, loss = smapped(
            w_p, m_p, v_p, params["dense"], t,
            ids, batch["dense"], batch["labels"])
        new_embed = shard_lib.to_logical(new_w, plans)
        d_updates, d_state = dense_tx.update(
            g_dense, state["dense"], params["dense"])
        new_dense = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), params["dense"], d_updates)
        new_state = {"step": t, "m": shard_lib.to_logical(new_m, plans),
                     "v": shard_lib.to_logical(new_v, plans),
                     "dense": d_state}
        return {"embed": new_embed, "dense": new_dense}, new_state, {
            "loss": loss}

    def flush(params, state):
        """Identity: the sharded path defers nothing (absent ids decay
        eagerly on their shard, exactly like the dense path)."""
        return params, state

    return jit_step(step_impl), init, flush, prepare, export


def _warn_overflow(n, t):
    """Host-side warning for sharded_sparse capacity-overflow fallbacks
    (jax.debug.callback target — fires only on overflow steps). Warnings go
    through ``logging`` (stderr by default), never stdout: benchmark and
    test drivers parse stdout."""
    logger.warning(
        "[sharded_sparse] unique capacity overflow on %d field-shard(s) at "
        "step %d; dense per-shard fallback (exact, but O(rows/shard) for "
        "those shards)", int(n), int(t))


def make_sharded_sparse_train_step(cfg: ctr.CTRConfig, hp, mesh, *,
                                   scheme: str = "div", r: float = 1.0,
                                   zeta: float = 1e-5, dense_tx=None,
                                   clip: bool = True, b1: float = 0.9,
                                   b2: float = 0.999, eps: float = 1e-8):
    """The sharded+sparse hybrid train step: tables row-sharded over
    ``"model"`` like ``make_sharded_train_step``, but each shard's optimizer
    update runs only on the batch ids it owns — per-shard unique-id dedup
    of the all-gathered batch ids (``embed.sharded_sparse.
    owned_unique_local``, capacity O(batch) per shard, inside the
    shard_map), then one post-backward ``update_phase`` per (field, group):
    gather from the raw shard + closed-form lazy-decay catch-up
    (``w *= (1 - lr*l2)**k`` via per-row ``last_step``, O(1) in pending
    depth), fused CowClip/L2/Adam on the rows, scatter back. Memory scales
    as O(vocab / n_model) per device *and* update traffic as O(batch) — the
    first placement that does both (the ROADMAP hybrid).

    Comm/compute overlap: the forward reads the *raw* tables and applies
    each row's pending decay inline during the masked lookup
    (``embed.sharded.decayed_lookup_partial``), so the dedup's "data"
    all-gathers — issued before the forward — have no consumer on the
    forward path and overlap the tower compute; after the backward, every
    row-grad psum is issued before any (collective-free) row update runs.
    A shard whose distinct
    owned ids exceed the capacity (only possible when
    ``cfg.unique_capacity`` caps it below the exact default) falls back to
    the dense per-shard update for that step — logged via ``jax.debug``
    and counted in ``aux["overflow_shards"]`` — so the hybrid matches the
    dense oracle even through overflow.

    Returns ``(step, init, flush, prepare, export)``: ``prepare``/``export``
    are the sharded placement's pad/unpad + device_put; ``flush`` forces the
    decay catch-up of every pending row (required before eval/checkpoint,
    idempotent).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core import builders as builders_lib
    from ..core import optim as optim_lib
    from ..embed import sharded as shard_lib
    from ..embed import sharded_sparse as hybrid_lib

    if dense_tx is None:
        dense_tx = builders_lib.dense_tower_tx(hp, b1=b1, b2=b2, eps=eps)
    n_data = mesh.shape["data"]
    n_model = mesh.shape["model"]
    plans = shard_lib.make_plans(cfg.vocab_sizes, n_model, scheme)
    adam_kw = dict(lr=hp.emb_lr, l2=hp.emb_l2, b1=b1, b2=b2, eps=eps)
    upd_kw = dict(clip=clip, r=r, zeta=zeta, **adam_kw)
    factor = optim_lib.decay_factor(hp.emb_lr, hp.emb_l2)
    n_fields = cfg.n_fields

    EMB = P("model", None)   # prefix spec: broadcasts over the embed tree
    LS = P("model")          # 1-D last_step leaves, rows over "model"
    REP = P()
    prepare, export = shard_lib.make_prepare_export(plans, mesh)

    def init(params):
        def zeros_like_placed(w):
            return jax.device_put(jnp.zeros(w.shape, w.dtype), w.sharding)

        last_step = jax.tree.map(
            lambda w: jax.device_put(
                jnp.zeros((w.shape[0],), jnp.int32),
                NamedSharding(mesh, LS)),
            params["embed"])
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zeros_like_placed, params["embed"]),
            "v": jax.tree.map(zeros_like_placed, params["embed"]),
            "last_step": last_step,
            "dense": dense_tx.init(params["dense"]),
        }

    def local_step(embed_sh, m_sh, v_sh, ls_sh, dense_params, t,
                   ids, feats, labels):
        # embed/m/v/ls are this model-slice's rows; ids/feats/labels this
        # data-slice's batch shard, replicated along "model".
        b_loc = ids.shape[0]
        b_global = b_loc * n_data

        # Per-shard unique-id dedup of the global batch. With a real data
        # axis the dedup is staged so the "data" collective carries unique
        # ids instead of the raw batch: (1) each data slice dedups its own
        # column per field (counts included — one sort of b_loc, identical
        # on every model replica of that slice), (2) the per-slice (uids,
        # counts) pairs are all-gathered over "data" (padded to the
        # static, still-exact cap min(b_loc, vocab) — small-vocab fields
        # gather O(vocab), not O(batch)), (3) each model shard dedups the
        # owned subset of the union, summing the gathered counts per slot
        # (same slots, counts and overflow flag as a dedup of the full
        # gathered batch — asserted in tests). With n_data == 1 the local
        # column already *is* the global batch: the all-gather would be a
        # no-op and the stage-1 sort pure overhead (measured ~25% of the
        # hybrid step on the CPU bench), so the single-stage dedup runs
        # directly — a trace-time switch, both paths bit-identical.
        # A field whose capacity equals the exact default can never
        # overflow; its fallback machinery (the full-row counts/grad
        # assembly and both cond branches) is dropped at trace time.
        staged = n_data > 1
        dedup = {}
        gathered = {}
        with jax.named_scope("dedup_allgather"):
            for i in range(n_fields):
                f = f"field_{i}"
                plan = plans[f]
                cap = hybrid_lib.shard_capacity(plan, b_global,
                                                cfg.unique_capacity)
                can_overflow = cap < min(b_global, plan.rows_per_shard)
                if staged:
                    u_slice, c_slice = hybrid_lib.slice_unique_counts(
                        ids[:, i], plan.vocab, min(b_loc, plan.vocab))
                    gids = jax.lax.all_gather(u_slice, "data", axis=0,
                                              tiled=True)
                    gcnts = jax.lax.all_gather(c_slice, "data", axis=0,
                                               tiled=True)
                    uloc, cnts, ovf = hybrid_lib.owned_unique_weighted(
                        gids, gcnts, plan, cap)
                    gathered[f] = (gids, gcnts)
                else:
                    uloc, cnts, ovf = hybrid_lib.owned_unique_local(
                        ids[:, i], plan, cap)
                    gathered[f] = None
                dedup[f] = (uloc, cnts, ovf if can_overflow else False)
        n_overflow = jax.lax.psum(
            sum(jnp.sum(jnp.asarray(d[2]).astype(jnp.int32))
                for d in dedup.values()),
            "model")

        # diagnostic: deepest pending-decay catch-up any touched slot takes
        # this step (dedup outputs are replicated over "data", so a "model"
        # max globalizes it)
        depth = jax.lax.pmax(
            jnp.max(jnp.stack([
                hybrid_lib.catchup_depth_slots(
                    ls_sh[group][f"field_{i}"], dedup[f"field_{i}"][0],
                    dedup[f"field_{i}"][1], t)
                for i in range(n_fields) for group in embed_sh])),
            "model")

        # The forward reads the *raw* tables — each looked-up row's pending
        # decay is applied inline during the gather (closed form, O(1)), so
        # the tower forward/backward has no data-dependence on the dedup
        # above: the "data" all-gathers overlap the forward compute.
        loss, g_emb, g_lin, g_dense = shard_lib.batch_forward_backward(
            cfg, plans, embed_sh, dense_params, ids, feats, labels, n_data,
            last_steps=ls_sh, step=t, factor=factor)

        # phase 2: row update on the touched slots. When overflow is
        # statically impossible (the default) the row gradient is
        # assembled directly on the [capacity] slot set — a segment_sum
        # and "data" psum of O(batch) slots instead of the
        # O(rows_per_shard) full-row materialization, which dominated the
        # hybrid's step time at production vocabs. Overflow-capable fields
        # keep the full-row grad/count assembly their dense fallback
        # branch needs. Every row-grad psum is issued before any row
        # update runs, so the "data" reductions launch back-to-back and
        # overlap the (collective-free) updates of earlier fields.
        g_psum = {g: {} for g in embed_sh}
        cnt_full = {}
        with jax.named_scope("rowgrad_psum"):
            for i in range(n_fields):
                f = f"field_{i}"
                plan = plans[f]
                uloc, cnts, ovf = dedup[f]
                cnt_full[f] = None
                if ovf is not False:
                    cnt_full[f] = (
                        hybrid_lib.full_counts_from_gathered(*gathered[f],
                                                             plan)
                        if staged else
                        jax.lax.psum(
                            shard_lib.counts_partial(ids[:, i], plan),
                            "data"))
                for group, g_batch in (("fm", g_emb), ("lin", g_lin)):
                    if group not in embed_sh:
                        continue
                    if ovf is False:
                        g_psum[group][f] = (jax.lax.psum(
                            hybrid_lib.rowgrad_slots(g_batch[:, i, :],
                                                     ids[:, i], plan, uloc),
                            "data"), None)
                    else:
                        g_psum[group][f] = (None, jax.lax.psum(
                            shard_lib.rowgrad_partial(g_batch[:, i, :],
                                                      ids[:, i], plan),
                            "data"))

        new_w = {g: {} for g in embed_sh}
        new_m = {g: {} for g in embed_sh}
        new_v = {g: {} for g in embed_sh}
        new_ls = {g: {} for g in embed_sh}
        with jax.named_scope("row_update"):
            for i in range(n_fields):
                f = f"field_{i}"
                uloc, cnts, ovf = dedup[f]
                for group in embed_sh:
                    g_slots, g_full = g_psum[group][f]
                    (new_w[group][f], new_m[group][f], new_v[group][f],
                     new_ls[group][f]) = hybrid_lib.update_phase(
                        embed_sh[group][f], m_sh[group][f], v_sh[group][f],
                        ls_sh[group][f], uloc, cnts, ovf,
                        g_slots, g_full, cnt_full[f], t, **upd_kw)
        return new_w, new_m, new_v, new_ls, g_dense, loss, n_overflow, depth

    # check_vma=False: each model shard updates its rows from the dedup of
    # the "data"-all-gathered ids and the psum'd row grads, so the new
    # tables (and the depth diagnostic) are identical on every "data"
    # slice, but jax's replication checker cannot follow that through the
    # sort-based dedup and would reject the replicated out_specs.
    smapped = shard_lib.shard_map(
        local_step, mesh=mesh,
        in_specs=(EMB, EMB, EMB, LS, REP, REP,
                  P("data", None), P("data", None), P("data")),
        out_specs=(EMB, EMB, EMB, LS, REP, REP, REP, REP),
        check_vma=False,
    )

    def step_impl(params, state, batch):
        ids = batch["ids"]
        if ids.shape[0] % n_data:
            raise ValueError(
                f"batch {ids.shape[0]} not divisible by data axis {n_data}")
        t = state["step"] + 1
        w_p = shard_lib.to_physical(params["embed"], plans)
        m_p = shard_lib.to_physical(state["m"], plans)
        v_p = shard_lib.to_physical(state["v"], plans)
        ls_p = shard_lib.to_physical(state["last_step"], plans)
        new_w, new_m, new_v, new_ls, g_dense, loss, n_overflow, depth = (
            smapped(w_p, m_p, v_p, ls_p, params["dense"], t,
                    ids, batch["dense"], batch["labels"]))
        new_embed = shard_lib.to_logical(new_w, plans)
        d_updates, d_state = dense_tx.update(
            g_dense, state["dense"], params["dense"])
        new_dense = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), params["dense"], d_updates)
        new_state = {"step": t, "m": shard_lib.to_logical(new_m, plans),
                     "v": shard_lib.to_logical(new_v, plans),
                     "last_step": shard_lib.to_logical(new_ls, plans),
                     "dense": d_state}
        return {"embed": new_embed, "dense": new_dense}, new_state, {
            "loss": loss, "overflow_shards": n_overflow,
            "catchup_depth_max": depth}

    def step_eager(params, state, batch):
        # the host-side overflow warning lives only on the eager step: a
        # scanned body cannot carry a per-step callback, so the engine's
        # chunk runner re-attaches it per chunk over the summed aux
        params, state, aux = step_impl(params, state, batch)
        jax.lax.cond(
            aux["overflow_shards"] > 0,
            lambda n, tt: jax.debug.callback(_warn_overflow, n, tt),
            lambda n, tt: None, aux["overflow_shards"], state["step"])
        return params, state, aux

    return (jit_step(step_impl, jit_target=step_eager), init,
            _make_lazy_flush(adam_kw), prepare, export)


def make_eval_fn(cfg: ctr.CTRConfig):
    """Batched, prefetch-overlapped evaluation.

    Scoring runs through the serving engine's ``padded_score_loop``: every
    dispatch is a fixed ``[batch_size]`` slice (inputs smaller than a batch
    are zero-padded *up*, never down), so ``logits_fn`` compiles once per
    ``batch_size`` regardless of how many distinct test-set sizes pass
    through — previously ``bs = min(batch_size, n)`` retraced for every
    small ``n``. Pad scores are discarded host-side; device memory is
    bounded at one batch of activations; host slicing overlaps the forward
    via the background prefetch worker. The returned metrics include
    ``eval_rows_per_sec`` (scored rows / wall-clock over the scoring loop).

    The returned ``evaluate`` exposes ``evaluate.logits_fn`` (a
    ``serve.engine.TracedFn``) so tests can assert the single-compile
    contract via ``n_traces``.
    """
    from ..serve import engine as serve_engine

    logits_fn = serve_engine.make_logits_fn(cfg)

    def evaluate(params, ds: CTRDataset, batch_size: int = 8192) -> dict:
        n = len(ds)
        t0 = time.perf_counter()
        scores = serve_engine.padded_score_loop(
            logits_fn, params, ds.ids, ds.dense, batch_size)
        seconds = time.perf_counter() - t0
        labels = ds.labels
        ll = float(np.mean(np.logaddexp(0.0, scores) - labels * scores))
        return {"auc": metrics.auc_numpy(scores, labels), "logloss": ll,
                "eval_rows_per_sec": n / max(seconds, 1e-9)}

    evaluate.logits_fn = logits_fn
    return evaluate


@dataclasses.dataclass
class TrainResult:
    history: list
    final_eval: dict
    seconds: float
    steps: int
    # final (flushed) model params and optimizer state — for checkpointing
    # and for asserting bundle contracts (e.g. flush idempotence) in tests
    params: object = None
    opt_state: object = None
    # epochs mode only: the training loss of every step, the seconds spent
    # in train steps with the device synced (eval and flush excluded), and
    # (steps, seconds) of the first dispatch, which carries the compile
    losses: list = dataclasses.field(default_factory=list)
    train_seconds: float = 0.0
    first_chunk: tuple = (0, 0.0)


def train_ctr(
    cfg: ctr.CTRConfig,
    tx: Optional[GradientTransformation],
    train_ds: CTRDataset,
    test_ds: Optional[CTRDataset],
    *,
    batch_size: int,
    epochs: int = 1,
    seed: int = 0,
    eval_every_epoch: bool = True,
    log_fn: Optional[Callable[[str], None]] = None,
    step_bundle=None,
    max_steps: Optional[int] = None,
    engine: str = "eager",
    scan_steps: int = 8,
    prefetch_buffers: int = 2,
    mode: str = "epochs",
    stream=None,
    init_state=None,
    start_step: int = 0,
    snapshot_cb=None,
) -> TrainResult:
    """Epoch driver. By default steps through the composable-optimizer path
    (``tx``); pass a ``core.builders.TrainStepBundle`` (any
    ``repro.embed.EmbeddingStore`` placement) to drive an explicit
    (step, init, flush, prepare) bundle instead — ``prepare`` lays params
    out for the placement once (the sharded store pads tables and shards
    rows over the mesh), and ``flush`` runs before every eval so
    lazily-decayed params are exact. ``max_steps`` hard-caps the total step
    count across epochs (smoke runs; the CLI's ``--steps``).

    ``engine`` selects the hot loop (repro.train.engine): ``"eager"`` — one
    jit dispatch and one blocking host->device copy per step, the
    debugging-friendly reference; ``"scan"`` — ``scan_steps`` updates fused
    into one ``lax.scan`` dispatch over prefetched, background-stacked
    batch chunks (``prefetch_buffers`` deep). Both consume the identical
    shuffle order, so results match the eager loop exactly.

    ``mode="stream"`` trains online from ``stream`` — an iterable of
    ``[k, batch, ...]`` chunks (``data.stream.stream_chunks``): no epochs,
    no fixed dataset, steps until the stream ends or ``max_steps`` is
    reached, then one flush + final eval. Both engines work; the eager
    loop unstacks each chunk, the scan engine dispatches it whole. The
    chunk geometry (batch size, scan_steps) is the stream's; this
    function's ``batch_size``/``scan_steps``/``epochs`` are ignored. The
    stream is closed on exit (also on an early ``max_steps`` cut).

    Crash-safe resume hooks (repro.train.snapshot): ``init_state`` is a
    pre-built ``(params, opt_state)`` pair (already ``prepare``d — a
    snapshot restore) that replaces the fresh init; ``start_step`` seeds
    the step counter so ``max_steps`` keeps meaning *total* steps across
    the original and resumed processes. ``snapshot_cb(params, opt_state,
    n_steps) -> (params, opt_state)`` is invoked at every chunk boundary
    in stream mode and every step boundary in eager epoch mode; the
    callback owns the cadence (and may flush — the returned pair replaces
    the live one, so a snapshot's flush stays part of the trajectory).
    """
    from . import engine as engine_lib

    if engine not in engine_lib.ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of "
                         f"{engine_lib.ENGINES}")
    if mode not in ("epochs", "stream"):
        raise ValueError(f"unknown mode {mode!r}; expected 'epochs' or "
                         "'stream'")
    if (mode == "stream") != (stream is not None):
        raise ValueError("mode='stream' requires a chunk stream (and a "
                         "stream requires mode='stream')")
    if init_state is not None:
        if step_bundle is None:
            raise ValueError("init_state (a snapshot restore) requires a "
                             "step_bundle")
        params, opt_state = init_state
        step_fn, flush = step_bundle.step, step_bundle.flush
    else:
        params = ctr.init(jax.random.key(seed), cfg)
        if step_bundle is not None:
            params = step_bundle.prepare(params)
            step_fn, opt_state, flush = (
                step_bundle.step, step_bundle.init(params),
                step_bundle.flush)
        else:
            opt_state = tx.init(params)
            step_fn = make_train_step(cfg, tx)
            flush = None
    eval_fn = make_eval_fn(cfg)
    driver = getattr(step_bundle, "stream_driver", None)
    runner = None
    if engine == "scan":
        if driver is not None and mode != "stream":
            raise ValueError(
                "this bundle drives its own host-side consume loop "
                "(stream_driver); it supports mode='stream' only")
        if driver is None:
            runner = engine_lib.make_chunk_runner(
                engine_lib.resolve_scan_step(step_bundle, step_fn))

    history = []
    n_steps = int(start_step)
    t0 = time.perf_counter()

    if mode == "stream" and driver is not None:
        try:
            params, opt_state, n_steps, sstats = driver(
                params, opt_state, stream, max_steps=max_steps)
        finally:
            close = getattr(stream, "close", None)
            if close is not None:
                close()
        seconds = time.perf_counter() - t0
        if flush is not None:
            params, opt_state = flush(params, opt_state)
        final = eval_fn(params, test_ds) if test_ds is not None else {}
        if log_fn:
            log_fn(f"stream: {n_steps} steps, migration overlap "
                   f"{sstats.get('migration_overlap_fraction', 0.0):.2f}"
                   + (f", auc={final['auc']:.4f} "
                      f"logloss={final['logloss']:.4f}" if final else ""))
        return TrainResult(history=history, final_eval=dict(final),
                           seconds=seconds, steps=n_steps, params=params,
                           opt_state=opt_state)

    if mode == "stream":
        try:
            for chunk in stream:
                k = chunk["labels"].shape[0]
                if max_steps is not None and n_steps + k > max_steps:
                    k = max_steps - n_steps
                    if k <= 0:
                        break
                    chunk = jax.tree.map(lambda x: x[:k], chunk)
                if engine == "scan":
                    params, opt_state, _ = runner(
                        params, opt_state, jax.device_put(chunk))
                    n_steps += k
                else:
                    for i in range(k):
                        batch = {kk: jnp.asarray(v[i])
                                 for kk, v in chunk.items()}
                        params, opt_state, _ = step_fn(
                            params, opt_state, batch)
                        n_steps += 1
                if snapshot_cb is not None:
                    params, opt_state = snapshot_cb(params, opt_state,
                                                    n_steps)
                if max_steps is not None and n_steps >= max_steps:
                    break
        finally:
            close = getattr(stream, "close", None)
            if close is not None:
                close()
        seconds = time.perf_counter() - t0
        if flush is not None:
            params, opt_state = flush(params, opt_state)
        final = eval_fn(params, test_ds) if test_ds is not None else {}
        if log_fn and final:
            log_fn(f"stream: {n_steps} steps, auc={final['auc']:.4f} "
                   f"logloss={final['logloss']:.4f}")
        return TrainResult(history=history, final_eval=dict(final),
                           seconds=seconds, steps=n_steps, params=params,
                           opt_state=opt_state)

    losses = []           # per-dispatch losses ([k] or scalar), fetched at the end
    train_seconds = 0.0
    first_chunk = None
    for epoch in range(epochs):
        if max_steps is not None and n_steps >= max_steps:
            break
        t_epoch = time.perf_counter()

        def on_chunk(k, aux):
            nonlocal first_chunk
            losses.append(aux["loss"])
            if first_chunk is None:
                jax.block_until_ready(aux)
                first_chunk = (k, time.perf_counter() - t_epoch)

        if engine == "scan":
            params, opt_state, ran, _ = engine_lib.run_epoch(
                runner, params, opt_state, train_ds, batch_size, scan_steps,
                seed=seed + epoch,
                max_steps=(None if max_steps is None
                           else max_steps - n_steps),
                buffer_size=prefetch_buffers, on_chunk=on_chunk)
            n_steps += ran
        else:
            for b in iterate_batches(train_ds, batch_size, seed=seed + epoch):
                batch = {k: jnp.asarray(v) for k, v in b.items()}
                params, opt_state, aux = step_fn(params, opt_state, batch)
                on_chunk(1, aux)
                n_steps += 1
                if snapshot_cb is not None:
                    params, opt_state = snapshot_cb(params, opt_state,
                                                    n_steps)
                if max_steps is not None and n_steps >= max_steps:
                    break
        jax.block_until_ready((params, opt_state))
        train_seconds += time.perf_counter() - t_epoch
        if eval_every_epoch and test_ds is not None:
            if flush is not None:
                params, opt_state = flush(params, opt_state)
            ev = eval_fn(params, test_ds)
            history.append({"epoch": epoch, **ev})
            if log_fn:
                log_fn(
                    f"epoch {epoch}: auc={ev['auc']:.4f} logloss={ev['logloss']:.4f}"
                )
    seconds = time.perf_counter() - t0
    if flush is not None:
        params, opt_state = flush(params, opt_state)
    final = (
        history[-1]
        if history
        else (eval_fn(params, test_ds) if test_ds is not None else {})
    )
    return TrainResult(
        history=history, final_eval=dict(final), seconds=seconds,
        steps=n_steps, params=params, opt_state=opt_state,
        losses=[float(x) for x in np.concatenate(
            [np.atleast_1d(x) for x in jax.device_get(losses)])]
        if losses else [],
        train_seconds=train_seconds, first_chunk=first_chunk or (0, 0.0))
