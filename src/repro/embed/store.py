"""EmbeddingStore: one facade over the four embedding placements.

The embedding tables are 99.9% of a CTR model's parameters (paper Table 1),
and every scaling decision in this repo is a decision about where those
rows live and how their optimizer update runs:

* ``dense``   — full [vocab, dim] tables on one device; the update streams
                the whole table every step (O(vocab)). Exactness oracle.
                ``kernel="substrate"`` runs the composable
                GradientTransformation chain, ``kernel="fused"`` the fused
                jnp CowClip+L2+Adam update per table.
* ``sparse``  — unique-id gather -> fused row update -> scatter with lazy
                L2 decay (O(batch) update traffic). One device, vocab-bound
                memory but batch-bound compute.
* ``sharded`` — tables row-sharded over the mesh's ``"model"`` axis, batch
                split over ``"data"``, via ``shard_map`` (repro.embed.sharded).
                Per-device table memory drops by the model-axis size, but
                each shard's update is still dense over its rows;
                CowClip keeps the embedding update collective-free.
* ``sharded_sparse`` — the hybrid of the two (repro.embed.sharded_sparse):
                row-sharded tables *and* per-shard unique-id dedup with lazy
                L2 decay, so per-device memory is O(vocab / n_model) and
                update traffic is O(batch) simultaneously. Capacity overflow
                on a shard falls back to that shard's dense update (exact).
* ``hotcold`` — two-tier streaming placement (repro.embed.hotcold): a
                fixed-capacity device-resident working set of hot rows
                (admission by cumulative batch frequency) over the full
                host-memory table; eviction writes back the raw row +
                ``last_step`` and the closed-form lazy-decay catch-up
                replays pending decay on re-admission, so the math is
                bit-identical to ``sparse``. Device-resident memory is
                O(capacity), update traffic O(batch).

Which to pick: dense until the table update dominates the step (vocab around
10^6 at CTR batch sizes), sparse while one device still holds the tables,
sharded/sharded_sparse when it no longer does (Criteo-scale 10^8 rows and
beyond) — sharded_sparse whenever the batch touches a small fraction of each
shard's rows, which is always true at production vocabs. See
docs/architecture.md for the full decision table.

Every placement yields the same ``TrainStepBundle`` contract consumed by
``train.loop.train_ctr``::

    bundle = store_for(cfg, path=..., mesh=...).make_bundle(cfg, hp, ...)
    params = bundle.prepare(params)        # placement-specific layout
    state  = bundle.init(params)
    params, state, aux = bundle.step(params, state, batch)
    params, state = bundle.flush(params, state)   # before eval/checkpoint
    canonical = bundle.export(params)      # placement-independent params

``prepare`` is where placement lives: identity for dense/sparse, pad-and-
device_put (rows over "model") for sharded; ``export`` is its layout
inverse, so checkpoints interchange across placements. ``flush`` settles
deferred work (the sparse path's pending lazy decay); it is idempotent
everywhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax

from ..core import builders
from ..core.builders import TRAIN_PATHS, TrainStepBundle

PLACEMENTS = ("dense", "sparse", "sharded", "sharded_sparse", "hotcold")

# core.build_train_step path name (TRAIN_PATHS) -> (placement, dense kernel)
_PATH_TO_STORE = {
    "substrate": ("dense", "substrate"),
    "fused": ("dense", "fused"),
    "sparse": ("sparse", "auto"),
    "sharded": ("sharded", "auto"),
    "sharded_sparse": ("sharded_sparse", "auto"),
    "hotcold": ("hotcold", "auto"),
}


@dataclasses.dataclass(frozen=True)
class EmbeddingStore:
    """A chosen placement plus its placement-specific knobs."""

    placement: str = "dense"
    kernel: str = "substrate"     # dense only: "substrate" | "fused"
    mesh: Any = None              # sharded only; None -> all local devices
    partition: str = "div"        # sharded only: "div" | "mod" row mapping
    hot_capacity: int = 4096      # hotcold only: hot rows per field
    cold_store: str = "none"      # hotcold only: "none" (in-step jax cold
                                  # tier) | "mem" | "mmap" (out-of-core
                                  # ColdStore + async migration planner)
    cold_dir: Optional[str] = None  # hotcold/mmap only: table directory
    admission: str = "cumulative"   # hotcold only: "cumulative" | "decayed"
    half_life: int = 0              # hotcold/decayed only: steps per halving

    def __post_init__(self):
        if self.placement not in PLACEMENTS:
            raise ValueError(f"unknown placement {self.placement!r}; "
                             f"expected one of {PLACEMENTS}")
        if self.cold_store not in ("none", "mem", "mmap"):
            raise ValueError(f"unknown cold_store {self.cold_store!r}; "
                             "expected 'none', 'mem', or 'mmap'")
        if self.cold_store != "none" and self.placement != "hotcold":
            raise ValueError("cold_store applies to the hotcold placement "
                             f"only (placement={self.placement!r})")
        if self.cold_store == "mmap" and not self.cold_dir:
            raise ValueError("cold_store='mmap' needs cold_dir "
                             "(the on-disk table directory)")

    def describe(self, cfg=None) -> str:
        """One line for logs. Given the model's ``cfg``, the sparse
        placement adds how many tables' Adam moments it stores packed
        (``kernels.cowclip.ref.packs``) and their bytes unpacked and
        packed."""
        if self.placement == "sparse" and cfg is not None:
            from ..kernels.cowclip import ref as cc_ref
            from ..models import ctr

            embed = jax.eval_shape(
                lambda: ctr.init(jax.random.key(0), cfg))["embed"]
            n, total, raw, stored = cc_ref.packed_moment_bytes(
                jax.tree.leaves(embed))
            return (f"sparse(Adam moments of {n} of {total} tables packed "
                    f"128 lanes wide: {raw} -> {stored} bytes)")
        if self.placement in ("sharded", "sharded_sparse"):
            from . import sharded as shard_lib
            mesh = self.mesh if self.mesh is not None else shard_lib.default_mesh()
            detail = ("per-shard unique-id update, "
                      if self.placement == "sharded_sparse" else "")
            return (f"{self.placement}(rows over model={mesh.shape['model']}, "
                    f"batch over data={mesh.shape['data']}, {detail}"
                    f"{self.partition} partition)")
        if self.placement == "dense":
            return f"dense({self.kernel})"
        if self.placement == "hotcold":
            adm = (f"{self.admission}(half_life={self.half_life})"
                   if self.admission == "decayed" else self.admission)
            if self.cold_store != "none":
                return (f"hotcold({self.hot_capacity} hot rows/field, "
                        f"{adm} admission, async {self.cold_store} cold "
                        f"store)")
            return (f"hotcold({self.hot_capacity} hot rows/field, "
                    f"{adm} freq-ranked admission, cold host tier)")
        return self.placement

    def make_bundle(
        self,
        cfg,
        hp,
        *,
        clip_kind: str = "adaptive_column",
        r: float = 1.0,
        zeta: float = 1e-5,
        clip_t: float = 1.0,
        warmup_steps: int = 0,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-8,
        nonfinite_guard: bool = False,
    ) -> TrainStepBundle:
        """Build this placement's (step, init, flush, prepare) bundle.

        ``nonfinite_guard`` wraps the step so a batch whose loss comes out
        NaN/Inf skips the entire update (params, moments, step counter),
        counted in ``aux["skipped_steps"]`` — value-exact on clean data.
        Not available for the async hotcold placement, whose step
        interleaves host-side eviction work that cannot be skipped.
        """
        bundle = self._build_bundle(
            cfg, hp, clip_kind=clip_kind, r=r, zeta=zeta, clip_t=clip_t,
            warmup_steps=warmup_steps, b1=b1, b2=b2, eps=eps)
        if nonfinite_guard:
            bundle = guard_bundle(bundle)
        return bundle

    def _build_bundle(
        self,
        cfg,
        hp,
        *,
        clip_kind: str = "adaptive_column",
        r: float = 1.0,
        zeta: float = 1e-5,
        clip_t: float = 1.0,
        warmup_steps: int = 0,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-8,
    ) -> TrainStepBundle:
        from ..train import loop as loop_lib  # deferred: train imports core

        if self.placement == "dense" and self.kernel != "fused":
            tx = builders.build_optimizer(
                hp, clip_kind=clip_kind, r=r, zeta=zeta, clip_t=clip_t,
                warmup_steps=warmup_steps, b1=b1, b2=b2, eps=eps)
            step = loop_lib.make_train_step(cfg, tx)
            return TrainStepBundle(step, tx.init, builders.identity_flush,
                                   scan_step=step.scan_step)

        dense_tx = builders.dense_tower_tx(
            hp, warmup_steps=warmup_steps, b1=b1, b2=b2, eps=eps)

        if self.placement == "dense":   # fused update
            step, init = loop_lib.make_fused_train_step(
                cfg, hp, r=r, zeta=zeta, dense_tx=dense_tx)
            return TrainStepBundle(step, init, builders.identity_flush,
                                   scan_step=step.scan_step)

        if clip_kind not in ("adaptive_column", "none"):
            raise ValueError(
                f"{self.placement} placement supports clip_kind "
                f"'adaptive_column' or 'none', got {clip_kind!r} "
                f"(ablation clips are substrate-only)")

        if self.placement == "sparse":
            step, init, flush = loop_lib.make_sparse_train_step(
                cfg, hp, r=r, zeta=zeta, dense_tx=dense_tx,
                clip=clip_kind == "adaptive_column", b1=b1, b2=b2, eps=eps)
            return TrainStepBundle(step, init, flush,
                                   scan_step=step.scan_step)

        if self.placement == "hotcold":
            if self.cold_store != "none":
                from . import migrate as migrate_lib

                return migrate_lib.make_async_hotcold_bundle(
                    cfg, hp, backend=self.cold_store,
                    directory=self.cold_dir, capacity=self.hot_capacity,
                    admission=self.admission, half_life=self.half_life,
                    r=r, zeta=zeta, dense_tx=dense_tx,
                    clip=clip_kind == "adaptive_column", b1=b1, b2=b2,
                    eps=eps)

            from . import hotcold as hotcold_lib

            step, init, flush = hotcold_lib.make_hotcold_train_step(
                cfg, hp, capacity=self.hot_capacity, r=r, zeta=zeta,
                dense_tx=dense_tx, clip=clip_kind == "adaptive_column",
                b1=b1, b2=b2, eps=eps, admission=self.admission,
                half_life=self.half_life)
            return TrainStepBundle(step, init, flush,
                                   scan_step=step.scan_step)

        # sharded / sharded_sparse
        from . import sharded as shard_lib

        mesh = shard_lib.auto_mesh(
            self.mesh if self.mesh is not None else shard_lib.default_mesh())
        if self.placement == "sharded_sparse":
            step, init, flush, prepare, export = (
                loop_lib.make_sharded_sparse_train_step(
                    cfg, hp, mesh, scheme=self.partition, r=r, zeta=zeta,
                    dense_tx=dense_tx, clip=clip_kind == "adaptive_column",
                    b1=b1, b2=b2, eps=eps))
        else:
            step, init, flush, prepare, export = (
                loop_lib.make_sharded_train_step(
                    cfg, hp, mesh, scheme=self.partition, r=r, zeta=zeta,
                    dense_tx=dense_tx, clip=clip_kind == "adaptive_column",
                    b1=b1, b2=b2, eps=eps))
        return TrainStepBundle(step, init, flush, prepare, export,
                               scan_step=step.scan_step)


def guard_bundle(bundle: TrainStepBundle) -> TrainStepBundle:
    """Wrap a bundle's step with the non-finite guard (core.builders).

    Re-jits the guarded pure body so both the per-step and the scanned
    engines run it; everything else in the bundle is untouched. Bundles
    with a ``stream_driver`` (async hotcold) are rejected — their step must
    run to fill eviction handles, so a skipped update would deadlock the
    migration buffer.
    """
    if bundle.stream_driver is not None:
        raise ValueError(
            "nonfinite_guard is not supported for the async hotcold "
            "placement (cold_store='mem'/'mmap'): its step fills host-side "
            "eviction handles and cannot be skipped")
    body = bundle.scan_step if bundle.scan_step is not None else bundle.step
    guarded = builders.nonfinite_guard(body)
    return bundle._replace(step=builders.jit_step(guarded),
                           scan_step=guarded)


def serving_snapshot(bundle: TrainStepBundle, params, state):
    """Canonical dense params for serving, from any placement's live state.

    ``flush`` first (settles the lazy-decay placements' pending coupled-L2
    decay via the closed-form catch-up; identity elsewhere), then ``export``
    (inverts ``prepare``'s layout — strips sharded pad rows back to
    ``[vocab, dim]``; identity elsewhere). The result is the placement-
    independent ``{"embed", "dense"}`` tree ``serve.ServingEngine`` scores
    with — so a snapshot taken from any of the four placements serves
    identically.
    """
    params, _ = bundle.flush(params, state)
    return bundle.export(params)


def max_pending_depth(state) -> int:
    """Deepest pending lazy-decay debt in an optimizer state, in steps.

    ``max(step - last_step)`` over every embedding row — 0 right after a
    ``flush`` (or for eager placements, whose state has no ``last_step``).
    Serving tests use it to prove a snapshot really exercised the catch-up
    path (depth > 0 before, exact scores after).
    """
    if not isinstance(state, dict) or "last_step" not in state:
        return 0
    step = jax.numpy.asarray(state["step"], jax.numpy.int32)
    depths = [
        int(jax.numpy.max(step - ls.astype(jax.numpy.int32)))
        for ls in jax.tree.leaves(state["last_step"])
    ]
    return max([0] + depths)


def resolve_path(cfg, path: Optional[str] = None) -> str:
    """Resolution order: explicit path > cfg.placement > cfg.sparse knob."""
    if path is None:
        path = getattr(cfg, "placement", None)
    if path is None:
        path = "sparse" if getattr(cfg, "sparse", False) else "substrate"
    if path not in TRAIN_PATHS:
        raise ValueError(
            f"unknown path {path!r}; expected one of {TRAIN_PATHS}")
    return path


def store_for(
    cfg,
    *,
    path: Optional[str] = None,
    mesh: Any = None,
    partition: str = "div",
    hot_capacity: int = 4096,
    cold_store: str = "none",
    cold_dir: Optional[str] = None,
    admission: str = "cumulative",
    half_life: int = 0,
) -> EmbeddingStore:
    """The store for a config: routes legacy path names and the config's
    ``placement``/``sparse`` knobs onto one of the placements."""
    path = resolve_path(cfg, path)
    placement, kernel = _PATH_TO_STORE[path]
    if placement == "dense" and kernel == "fused" and getattr(cfg, "sparse", False):
        # the one router: the fused step is dense only, and the sparse
        # placement's bundle carries its flush
        placement, kernel = "sparse", "auto"
    return EmbeddingStore(placement=placement, kernel=kernel, mesh=mesh,
                          partition=partition, hot_capacity=hot_capacity,
                          cold_store=cold_store, cold_dir=cold_dir,
                          admission=admission, half_life=half_life)
