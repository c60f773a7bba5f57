"""Assemble the paper's full large-batch optimizer.

Parameter trees across the framework are split at the top level::

    params = {"embed": {<field or token tables, [vocab, dim]>},
              "dense": {<everything else>}}

The optimizer runs two groups (paper Alg. 1):

  embed : [CowClip | ablation-clip] -> count-aware coupled-L2 Adam
          (touched rows: +lambda_e * w -> Adam -> -eta_e; absent rows:
          w *= 1 - eta_e * lambda_e, Adam moments held)
  dense : Adam (+ optional L2)      -> -eta(t) with linear warmup

Order notes (faithful to the paper):
  * Clipping bounds the *task-loss* gradient; L2 is added afterwards, so ids
    absent from the batch keep decaying (the zeta lower-bound exists exactly
    because of that decay). Absent-row decay is geometric on the weight
    (not routed through Adam), which is what gives the sparse placements
    their O(1) closed-form catch-up (core/optim.py decay section).
  * On touched rows L2 flows *through* Adam (coupled, as in the paper's TF
    implementation), not decoupled AdamW-style.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax

from . import cowclip as cc
from . import optim, schedules
from .scaling import Hyperparams


def label_params(params):
    """Label each leaf 'embed' or 'dense' from the top-level split."""

    def label_subtree(name, subtree):
        return jax.tree.map(lambda _: name, subtree)

    return {k: label_subtree("embed" if k == "embed" else "dense", v)
            for k, v in params.items()}


class TwoGroupState(tuple):
    """(embed_state, dense_state) — kept a plain tuple pytree."""


def two_group(
    embed_tx: optim.GradientTransformation,
    dense_tx: optim.GradientTransformation,
) -> optim.GradientTransformation:
    """Compose embed/dense transforms over the framework's top-level split.

    Unlike the generic ``optim.partition`` this dispatches on the top-level
    dict keys directly, which lets pytree-shaped extras (CowClip's ``counts``,
    matching ``params['embed']``) flow to the embed group without masking.
    """

    def init_fn(params):
        return (embed_tx.init(params["embed"]), dense_tx.init(params["dense"]))

    def update_fn(updates, state, params=None, *, counts=None, **extras):
        e_params = None if params is None else params["embed"]
        d_params = None if params is None else params["dense"]
        e_up, e_st = embed_tx.update(
            updates["embed"], state[0], e_params, counts=counts, **extras
        )
        d_up, d_st = dense_tx.update(updates["dense"], state[1], d_params, **extras)
        return {"embed": e_up, "dense": d_up}, (e_st, d_st)

    return optim.GradientTransformation(init_fn, update_fn)


def dense_tower_tx(
    hp: Hyperparams,
    *,
    warmup_steps: int = 0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> optim.GradientTransformation:
    """The dense tower's chain (optional coupled L2 -> Adam -> linear-warmup
    LR) — identical across every embedding placement, so every bundle builds
    it here."""
    steps = []
    if hp.dense_l2:
        steps.append(optim.add_decayed_weights(hp.dense_l2))
    steps.append(optim.scale_by_adam(b1=b1, b2=b2, eps=eps))
    dense_lr = (
        schedules.linear_warmup(hp.dense_lr, warmup_steps)
        if warmup_steps
        else hp.dense_lr
    )
    steps.append(optim.scale_by_neg_lr(dense_lr))
    return optim.chain(*steps)


def build_optimizer(
    hp: Hyperparams,
    *,
    clip_kind: str = "adaptive_column",
    r: float = 1.0,
    zeta: float = 1e-5,
    clip_t: float = 1.0,
    warmup_steps: int = 0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> optim.GradientTransformation:
    """The paper's two-group optimizer as a single GradientTransformation.

    ``update`` accepts the extra kwarg ``counts``: a pytree matching
    ``params["embed"]`` where each [vocab, dim] table has a [vocab] leaf of
    per-id batch occurrence counts.
    """
    embed_steps = []
    if clip_kind != "none":
        embed_steps.append(
            cc.make_clip_transform(clip_kind, r=r, zeta=zeta, clip_t=clip_t)
        )
    # count-aware tail: coupled-L2 Adam on touched rows, one geometric
    # decay step (w *= 1 - lr*l2, moments held) on absent rows — the dense
    # counterpart of the sparse paths' O(1) closed-form lazy catch-up
    embed_steps.append(
        optim.lazy_coupled_adam(hp.emb_lr, hp.emb_l2, b1=b1, b2=b2, eps=eps)
    )
    embed_tx = optim.chain(*embed_steps)

    dense_tx = dense_tower_tx(hp, warmup_steps=warmup_steps, b1=b1, b2=b2,
                              eps=eps)
    return two_group(embed_tx, dense_tx)


class StepFn:
    """A jitted train step that also carries its un-jitted body.

    ``scan_step`` is the pure ``(params, state, batch) -> (params, state,
    aux)`` function the jit wraps, with every host-side effect (debug
    callbacks, logging) stripped — the form ``lax.scan`` can fuse K copies
    of (repro.train.engine). Calling the object runs the jitted step with
    the usual donated ``(params, state)``.
    """

    __slots__ = ("_jitted", "scan_step")

    def __init__(self, jitted, scan_step):
        self._jitted = jitted
        self.scan_step = scan_step

    def __call__(self, params, state, batch):
        return self._jitted(params, state, batch)


def jit_step(step_impl, jit_target=None) -> StepFn:
    """Standard wrapping for a pure step body: jit with donated
    ``(params, state)``, keeping the body reachable for the scan engine.
    ``jit_target`` substitutes a different function to jit (the eager
    variant with host callbacks re-attached) while ``step_impl`` stays the
    scan-safe body."""
    return StepFn(
        jax.jit(jit_target if jit_target is not None else step_impl,
                donate_argnums=(0, 1)),
        step_impl)


def nonfinite_guard(step_impl):
    """Wrap a pure step body so a poisoned batch cannot destroy the model.

    Runs the step, then selects per-leaf between the new and the old
    (params, state) on one predicate: the batch loss is finite. A NaN/Inf
    loss (upstream of every gradient) therefore skips the entire update —
    params, optimizer moments, and the step counter stay exactly as if
    the batch had never arrived, which keeps the lazy-decay placements'
    ``last_step`` bookkeeping consistent. The skip is counted in
    ``aux["skipped_steps"]`` (0 or 1 per step; sum over a scanned chunk).

    Exactness: ``jnp.where(True, new, old)`` returns ``new`` bitwise, so
    guarded and unguarded runs over clean data are identical. The guard
    composes with ``lax.scan`` (pure, no host callbacks), so every
    bundle's ``scan_step`` can be wrapped the same way.
    """
    import jax.numpy as jnp

    def guarded(params, state, batch):
        new_params, new_state, aux = step_impl(params, state, batch)
        ok = jnp.isfinite(aux["loss"])
        keep = lambda new, old: jax.tree.map(  # noqa: E731
            lambda n, o: jnp.where(ok, n, o), new, old)
        aux = dict(aux,
                   skipped_steps=(~ok).astype(jnp.int32))
        return keep(new_params, params), keep(new_state, state), aux

    return guarded


def identity_prepare(params):
    """Default param placement: leave the tree exactly as initialized."""
    return params


def identity_flush(params, state):
    """Default flush: nothing deferred, nothing to settle."""
    return params, state


class TrainStepBundle(NamedTuple):
    """A train-step bundle usable by ``train.loop.train_ctr``.

    step:    jit'd (params, state, batch) -> (params, state, aux)
    init:    params -> state (call on *prepared* params)
    flush:   (params, state) -> (params, state); applies any deferred work
             (the sparse path's pending lazy-L2 decay) — identity elsewhere,
             and idempotent everywhere.
    prepare: params -> params; placement-specific layout applied once before
             ``init`` (the sharded path pads tables and device_puts rows
             over the mesh's "model" axis) — identity elsewhere.
    export:  params -> params; inverse of ``prepare``'s layout change
             (the sharded path strips pad rows back to [vocab, dim]), so
             checkpoints are placement-independent — identity elsewhere.
             Export a *flushed* params tree.
    scan_step: the pure, host-callback-free body ``step`` jits — what the
             scan engine (repro.train.engine) fuses K copies of per
             dispatch. None falls back to scanning ``step`` itself
             (jit-under-jit inlines), minus chunk-level callback
             relocation.
    stream_transform: optional factory ``(max_steps=None) -> transform``
             for ``data.stream.ChunkStream``: runs on the stream's worker
             thread so host-side planning (the async hotcold migration
             planner) overlaps the device step; returning None from the
             transform ends the stream at the step budget.
    stream_driver: optional ``(params, state, stream, *, max_steps) ->
             (params, state, steps, stats)`` replacing the generic stream
             loop in ``train_ctr(mode="stream")`` — bundles that must
             interleave host work with each dispatch (filling eviction
             handles) own their consume loop.
    """

    step: Callable
    init: Callable
    flush: Callable
    prepare: Callable = identity_prepare
    export: Callable = identity_prepare
    scan_step: Optional[Callable] = None
    stream_transform: Optional[Callable] = None
    stream_driver: Optional[Callable] = None


TRAIN_PATHS = ("substrate", "fused", "sparse", "sharded", "sharded_sparse",
               "hotcold")


def build_train_step(
    cfg,
    hp: Hyperparams,
    *,
    path: Optional[str] = None,
    clip_kind: str = "adaptive_column",
    r: float = 1.0,
    zeta: float = 1e-5,
    clip_t: float = 1.0,
    warmup_steps: int = 0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    use_kernel: bool = False,
    mesh=None,
    partition: str = "div",
    hot_capacity: int = 4096,
    cold_store: str = "none",
    cold_dir: Optional[str] = None,
    admission: str = "cumulative",
    half_life: int = 0,
) -> TrainStepBundle:
    """Route a CTR train step through one of the six update paths, all
    served by the ``repro.embed.EmbeddingStore`` placements:

      substrate      : composable GradientTransformation chain (the oracle);
                       dense placement
      fused          : dense fused jnp CowClip+L2+Adam update per table;
                       dense placement
      sparse         : unique-id gather -> fused row update -> scatter, with
                       lazy L2 decay (O(batch) update traffic)
      sharded        : tables row-sharded over mesh axis "model", batch over
                       "data", shard_map step with a dense per-shard update
                       (``mesh``/``partition`` apply; mesh=None uses every
                       local device as (1, n))
      sharded_sparse : the hybrid — row-sharded tables with a per-shard
                       unique-id (lazy-decay) update, so memory is
                       O(vocab/n_model) and update traffic O(batch) at once
      hotcold        : two-tier streaming placement — a fixed-capacity
                       (``hot_capacity`` rows/field) frequency-ranked hot
                       working set over the full cold table, bit-identical
                       math to "sparse" via the lazy-decay catch-up.
                       ``cold_store="mem"|"mmap"`` moves the cold tier
                       out of the jitted step entirely (embed/coldstore +
                       embed/migrate): host/disk tables, host-side
                       migration planning overlapped with the step, and
                       — with "mmap" + ``cold_dir`` — vocab bounded by
                       disk instead of RAM, with bit-exact
                       flush/reopen/resume. ``admission``/``half_life``
                       select the frequency policy for either variant.

    ``path=None`` honors the config knobs: ``cfg.placement`` if set, else
    ``cfg.sparse`` selects "sparse", otherwise "substrate". The dense
    tower always runs the substrate Adam (with optional warmup).

    ``use_kernel`` accepts ``False`` only, for callers that still pass it:
    every placement runs the one jnp row update, compiled by XLA.
    """
    if use_kernel:
        raise ValueError("use_kernel=True: the Pallas CowClip row kernels "
                         "were removed; every placement runs the XLA row "
                         "update (leave use_kernel out)")
    from ..embed.store import store_for  # deferred: embed imports core

    store = store_for(cfg, path=path, mesh=mesh, partition=partition,
                      hot_capacity=hot_capacity, cold_store=cold_store,
                      cold_dir=cold_dir, admission=admission,
                      half_life=half_life)
    return store.make_bundle(
        cfg, hp, clip_kind=clip_kind, r=r, zeta=zeta, clip_t=clip_t,
        warmup_steps=warmup_steps, b1=b1, b2=b2, eps=eps)
