"""Host-side migration planning for the out-of-core hot/cold placement.

The synchronous ``hotcold`` step resolves residency *inside* the jit: the
O(vocab) ``slot_of``/``freq`` maps ride in the device carry, admission is
ranked on device, and every cold gather/scatter sits on the step's
critical path. The observation that unlocks the split: **residency,
admission, and eviction depend only on the id stream — never on row
values.** So a host-side ``MigrationPlanner`` can replay the exact same
decision procedure in numpy, one step ahead of the device, on the
``ChunkStream`` worker thread that is already queueing batches ahead of
the consumer:

    worker thread:   batch t+1 -> plan residency -> gather miss rows
                     (store-buffer first, then cold store)      | overlapped
    consumer:        dispatch device step t  <- plan t's arrays | in time

The device step (``hotcold.make_migrate_device_step``) then takes
fixed-shape inputs — ``hit``/``src``/``ls`` assembly vectors, pre-gathered
miss rows, bank-gather indices — and keeps only the math whose *values*
matter, in the same op order as the synchronous step. Because numpy and
XLA CPU agree bitwise on the f32 frequency arithmetic and the selection
is pure integer/compare logic, async runs export params bitwise-identical
to the synchronous placement (tests/test_coldstore.py).

Eviction values flow the other way with the same one-step slack: the
planner registers each write-back in the ``StoreBuffer`` *at plan time*
(value not yet computed), the consumer fills the step's
``EvictionHandle`` right after dispatching it, and any later miss-gather
of that id blocks on the handle — read-your-writes without ever stalling
the planner on the common path. The planner drains ready entries to the
cold store opportunistically; pending entries are bounded by how far the
stream's queue lets the planner run ahead.

Deadlock freedom: a plan may block only on handles of *already emitted*
steps (the transform emits one planned batch per stream item, so the
consumer can always dispatch everything a later plan waits on). That is
why ``make_transform`` requires chunk size 1 — a multi-batch chunk could
make plan ``t+1`` wait on a handle trapped in the same unqueued chunk.
Lookahead depth is the stream's ``buffer_size``, not the chunk size.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import optim as optim_lib
from ..core.builders import TrainStepBundle
from . import hotcold as hotcold_lib
from .coldstore import ColdStore, EvictionHandle, StoreBuffer

__all__ = ["MigrationPlanner", "StepPlan", "PlannedChunk",
           "make_async_hotcold_bundle", "AsyncHotCold"]


class StepPlan:
    """One planned step: the device-step input tree plus the eviction
    handle the consumer must ``fill`` right after dispatching."""

    __slots__ = ("t", "device", "handle", "hit_rows", "lookup_rows",
                 "evictions", "depth")

    def __init__(self, t, device, handle, hit_rows, lookup_rows, evictions,
                 depth):
        self.t = t
        self.device = device
        self.handle = handle
        self.hit_rows = hit_rows
        self.lookup_rows = lookup_rows
        self.evictions = evictions
        self.depth = depth

    def fill(self, evict):
        """Hand the step's (possibly still-lazy) eviction banks to every
        store-buffer entry registered for this step."""
        self.handle.fill(evict)


class PlannedChunk(NamedTuple):
    """A stream item that already carries its migration plans."""

    chunk: dict
    plans: list


class MigrationPlanner:
    """Host replica of the synchronous step's residency/admission logic.

    State per field (all numpy, all host): ``slot_ids [C]`` (id per slot,
    vocab = empty), ``slot_of [V]`` (id -> slot, -1 = cold), ``slot_ls
    [C]`` (resident rows' last-touched step — the device hot tier carries
    no ``ls`` anymore), and ``freq [V]`` f32 under either admission
    policy. ``plan_batch`` advances this state exactly as the device step
    would and emits the step's fixed-shape input tree.
    """

    def __init__(self, cfg, store: ColdStore, *, capacity: int = 4096,
                 admission: str = "cumulative", half_life: int = 0):
        self.cfg = cfg
        self.store = store
        self.buffer = StoreBuffer(store)
        self.caps = hotcold_lib._field_caps(cfg.vocab_sizes, capacity)
        self.vocab = {f"field_{i}": int(v)
                      for i, v in enumerate(cfg.vocab_sizes)}
        self.fields = list(self.vocab)
        self.alpha = hotcold_lib.admission_alpha(admission, half_life)
        self.slot_ids = {f: np.full((self.caps[f],), self.vocab[f], np.int32)
                         for f in self.fields}
        self.slot_of = {f: np.full((self.vocab[f],), -1, np.int32)
                        for f in self.fields}
        self.slot_ls = {f: np.zeros((self.caps[f],), np.int32)
                        for f in self.fields}
        self.freq = {f: np.zeros((self.vocab[f],), np.float32)
                     for f in self.fields}
        self.t = 0                    # steps planned so far
        self.plan_seconds = 0.0       # planner busy time (overlap metric)
        self.hit_rows = 0.0
        self.lookup_rows = 0.0
        self.evictions = 0

    def _unique_cap(self, f: str, batch: int) -> int:
        """Replicates models.embedding.batch_unique's capacity rule."""
        ucap = getattr(self.cfg, "unique_capacity", 0)
        v = self.vocab[f]
        return min(batch, v) if ucap <= 0 else min(int(ucap), v)

    def plan_batch(self, ids: np.ndarray) -> StepPlan:
        """Plan one step from its ``[batch, n_fields]`` id matrix."""
        t0 = time.perf_counter()
        ids = np.asarray(ids)
        t = self.t + 1
        handle = EvictionHandle()
        dev = {k: {} for k in ("hit", "src", "ls", "sel", "wb")}
        for g in self.store.groups:
            dev.setdefault("miss_w", {})[g] = {}
            dev.setdefault("miss_m", {})[g] = {}
            dev.setdefault("miss_v", {})[g] = {}
        hit_rows = lookup_rows = 0.0
        evictions = depth = 0
        for i, f in enumerate(self.fields):
            h, l, e, d = self._plan_field(f, np.asarray(ids[:, i]), t,
                                          handle, dev)
            hit_rows += h
            lookup_rows += l
            evictions += e
            depth = max(depth, d)
        self.t = t
        self.hit_rows += hit_rows
        self.lookup_rows += lookup_rows
        self.evictions += evictions
        # opportunistically settle evictions whose step has completed
        self.buffer.drain(ready_only=True)
        self.plan_seconds += time.perf_counter() - t0
        return StepPlan(t, dev, handle, hit_rows, lookup_rows, evictions,
                        depth)

    def _plan_field(self, f, col, t, handle, dev):
        V, C = self.vocab[f], self.caps[f]
        U = self._unique_cap(f, col.shape[0])

        # dedup — np.unique and the device's embedding.sort_unique (U
        # slots, fill V) agree: np.unique's sorted ascending uids, pads
        # hold V with count 0
        uids_r, counts_r = np.unique(col, return_counts=True)
        if uids_r.shape[0] > U:
            raise ValueError(
                f"{f}: {uids_r.shape[0]} distinct ids exceed the unique "
                f"capacity {U}; the async hotcold path needs "
                "cfg.unique_capacity <= 0 (per-batch dedup)")
        n = uids_r.shape[0]
        uids = np.full((U,), V, np.int32)
        counts = np.zeros((U,), np.float32)
        uids[:n] = uids_r
        counts[:n] = counts_r
        touched = counts > 0

        # residency lookup against the host maps
        slot = self.slot_of[f][np.minimum(uids, V - 1)]
        hit = touched & (slot >= 0)
        src = np.maximum(slot, 0).astype(np.int32)

        # frequency update — f32 in-place so it bit-matches the device
        # policy (XLA CPU and numpy agree on f32 multiply/add)
        freq = self.freq[f]
        if self.alpha is not None:
            np.multiply(freq, self.alpha, out=freq)
        freq[uids[:n]] += counts[:n]

        # assembly ls (rows caught up through t-1): hits from the live
        # slot_ls, misses filled below from the gather
        ls_rows = np.zeros((U,), np.int32)
        ls_rows[hit] = self.slot_ls[f][src[hit]]

        # candidate ranking — the exact _top_c_mask selection: top-C valid
        # candidates under (freq desc, id asc); valid ids are unique so
        # the order is strict. lexsort's secondary key breaks f32-equal
        # priorities by ascending id, matching the device's bitcast
        # tie-break (non-negative f32: value order == bit order).
        tslot = np.zeros((C,), bool)
        tslot[src[hit]] = True
        res_cand = np.where(tslot, V, self.slot_ids[f]).astype(np.int32)
        fresh = np.where(touched, uids, V).astype(np.int32)
        cand = np.concatenate([res_cand, fresh])
        valid = cand < V
        prio = np.where(valid, freq[np.minimum(cand, V - 1)],
                        np.float32(0.0)).astype(np.float32)
        n_cand = cand.shape[0]
        order = np.lexsort((cand, -prio))
        order = order[valid[order]]
        take = min(C, int(valid.sum()))
        kept = np.zeros((n_cand,), bool)
        kept[order[:take]] = True

        sel = np.flatnonzero(kept).astype(np.int32)
        sel_c = np.full((C,), n_cand - 1, np.int32)
        sel_c[:sel.shape[0]] = sel
        slot_new = np.full((C,), V, np.int32)
        slot_new[:sel.shape[0]] = cand[sel]

        wb = valid & ~kept
        wb_pos = np.flatnonzero(wb).astype(np.int32)
        # every write-back is a dropped candidate: <= U of them (if all C
        # survivors are residents, the dropped set is exactly the touched
        # misses) — the same bound that sizes the sync step's compaction
        assert wb_pos.shape[0] <= U, (f, wb_pos.shape[0], U)
        wb_c = np.full((U,), n_cand - 1, np.int32)
        wb_c[:wb_pos.shape[0]] = wb_pos
        wb_ids = cand[wb_pos]
        evics = int(wb[:C].sum()) + int((wb[C:] & hit).sum())

        # eviction last-steps come off the same host bank the device
        # gathers rows from: old resident ls first, then t for fresh rows
        bank_ls = np.concatenate(
            [self.slot_ls[f], np.full((U,), t, np.int32)])
        wb_ls = bank_ls[wb_pos]
        new_slot_ls = np.zeros((C,), np.int32)
        new_slot_ls[:sel.shape[0]] = bank_ls[sel]
        new_slot_ls[slot_new >= V] = 0

        # miss rows: store-buffer first (read-your-writes), then store.
        # Read *before* registering this step's write-backs — a row both
        # missed and rejected this step must gather its pre-step value.
        miss_pos = np.flatnonzero(touched & ~hit)
        rows = self.buffer.read(f, uids[miss_pos])
        ls_rows[miss_pos] = rows["ls"]
        for g in self.store.groups:
            dtype = self.store.w[g][f].dtype
            dim = self.store.w[g][f].shape[1]
            mw = np.zeros((U, dim), dtype)
            mm = np.zeros((U, dim), np.float32)
            mv = np.zeros((U, dim), np.float32)
            mw[miss_pos] = rows["w"][g]
            mm[miss_pos] = rows["m"][g]
            mv[miss_pos] = rows["v"][g]
            dev["miss_w"][g][f] = mw
            dev["miss_m"][g][f] = mm
            dev["miss_v"][g][f] = mv

        self.buffer.register(f, wb_ids, wb_ls,
                             np.arange(wb_pos.shape[0], dtype=np.int32),
                             t, handle)

        # advance the residency maps exactly as the device step would
        so = self.slot_of[f]
        old = self.slot_ids[f]
        so[old[old < V]] = -1
        so[cand[sel]] = np.arange(sel.shape[0], dtype=np.int32)
        self.slot_ids[f] = slot_new
        self.slot_ls[f] = new_slot_ls

        dev["hit"][f] = hit
        dev["src"][f] = src
        dev["ls"][f] = ls_rows
        dev["sel"][f] = sel_c
        dev["wb"][f] = wb_c

        d = int(np.max(np.where(touched, (t - 1) - ls_rows, 0), initial=0))
        return (float(counts[hit].sum()), float(counts.sum()), evics, d)


class AsyncHotCold:
    """Controller behind the async hotcold ``TrainStepBundle``.

    Owns the cold store, the planner, and the split device step; the
    bundle's step/init/flush/prepare/export plus the stream transform
    factory and the stream driver are its bound methods (so benchmarks
    and tests reach the store and planner through
    ``bundle.stream_driver.__self__`` — or just keep the controller).
    """

    def __init__(self, cfg, hp, *, backend: str = "mem",
                 directory: Optional[str] = None, store: Optional[ColdStore]
                 = None, capacity: int = 4096,
                 admission: str = "cumulative", half_life: int = 0,
                 r: float = 1.0, zeta: float = 1e-5, dense_tx=None,
                 clip: bool = True, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8):
        if callable(hp.emb_lr) or callable(hp.emb_l2):
            raise ValueError(
                "async hotcold migration requires constant embedding "
                "lr/l2 (the flush settle uses the closed-form decay)")
        self.cfg = cfg
        self.hp = hp
        self.backend = backend
        self.directory = directory
        self.capacity = capacity
        self.admission = admission
        self.half_life = half_life
        if dense_tx is None:
            dense_tx = optim_lib.adam(hp.dense_lr, l2=hp.dense_l2)
        self.dense_tx = dense_tx
        self.adam_kw = dict(lr=hp.emb_lr, l2=hp.emb_l2, b1=b1, b2=b2,
                            eps=eps)
        self.device_step = hotcold_lib.make_migrate_device_step(
            cfg, hp, r=r, zeta=zeta, dense_tx=dense_tx, clip=clip,
            b1=b1, b2=b2, eps=eps)
        self.store = store
        self.planner: Optional[MigrationPlanner] = None
        self._prepared = threading.Event()
        self._sidecar = None
        self.last_stream_stats: Optional[dict] = None
        # whole-table settle keeps flush bit-identical to the synchronous
        # flush at test sizes; chunked settle bounds RSS for >RAM tables
        self.settle_chunk_rows = 1 << 21

    # -- bundle hooks -------------------------------------------------------

    def bundle(self) -> TrainStepBundle:
        return TrainStepBundle(
            step=self.step, init=self.init, flush=self.flush,
            prepare=self.prepare, export=self.export,
            stream_transform=self.make_transform,
            stream_driver=self.drive)

    def prepare(self, params):
        """Attach (or create) the cold store and the planner; return the
        params tree with the embed leaves replaced by store views."""
        import os

        if self.store is None:
            meta = (os.path.join(self.directory, "meta.json")
                    if self.directory else None)
            if (self.backend == "mmap" and meta is not None
                    and os.path.exists(meta)):
                self.store = ColdStore.open(self.directory)
            else:
                self.store = ColdStore.from_params(
                    params["embed"], backend=self.backend,
                    directory=self.directory)
        elif not self.store.populated:
            for g, tables in params["embed"].items():
                for f, tbl in tables.items():
                    self.store.w[g][f][...] = np.asarray(tbl)
            self.store.populated = True
        self.planner = MigrationPlanner(
            self.cfg, self.store, capacity=self.capacity,
            admission=self.admission, half_life=self.half_life)
        dense = params["dense"]
        if self.store.resumed:
            self._sidecar = self.store.load_sidecar()
            if self._sidecar is not None:
                pl = self.planner
                pl.t = int(self._sidecar["t"])
                for f in pl.fields:
                    pl.slot_ids[f][...] = self._sidecar[f"slot_ids/{f}"]
                    pl.slot_of[f][...] = self._sidecar[f"slot_of/{f}"]
                    pl.slot_ls[f][...] = self._sidecar[f"slot_ls/{f}"]
                    pl.freq[f][...] = self._sidecar[f"freq/{f}"]
                leaves, treedef = jax.tree.flatten(dense)
                dense = jax.tree.unflatten(treedef, [
                    jnp.asarray(self._sidecar[f"dense_param/{i}"])
                    for i in range(len(leaves))])
        self._prepared.set()
        return {"embed": self.store.param_views(), "dense": dense}

    def init(self, params):
        dense_opt = self.dense_tx.init(params["dense"])
        if self._sidecar is not None:
            leaves, treedef = jax.tree.flatten(dense_opt)
            dense_opt = jax.tree.unflatten(treedef, [
                jnp.asarray(self._sidecar[f"dense_opt/{i}"])
                for i in range(len(leaves))])
        pl = self.planner
        hot = {k: {g: {} for g in self.store.groups}
               for k in ("w", "m", "v")}
        for g in self.store.groups:
            for f in self.store.fields:
                C = pl.caps[f]
                dim = self.store.w[g][f].shape[1]
                if self.store.resumed:
                    sid_c = np.minimum(pl.slot_ids[f],
                                       pl.vocab[f] - 1)
                    hot["w"][g][f] = jnp.asarray(
                        np.asarray(self.store.w[g][f][sid_c]))
                    hot["m"][g][f] = jnp.asarray(
                        np.asarray(self.store.m[g][f][sid_c]))
                    hot["v"][g][f] = jnp.asarray(
                        np.asarray(self.store.v[g][f][sid_c]))
                else:
                    hot["w"][g][f] = jnp.zeros(
                        (C, dim), self.store.w[g][f].dtype)
                    hot["m"][g][f] = jnp.zeros((C, dim), jnp.float32)
                    hot["v"][g][f] = jnp.zeros((C, dim), jnp.float32)
        return {"step": pl.t, "hot": hot, "dense": dense_opt}

    def step(self, params, state, batch):
        """Inline (plan-then-dispatch) step — the overlap-off path, and
        what the epoch driver calls. Bitwise identical to the overlapped
        driver: planning order is the same, only the timing differs."""
        plan = self.planner.plan_batch(np.asarray(batch["ids"]))
        dense, dense_opt, hot, evict, aux = self.device_step(
            params["dense"], state["dense"], state["hot"],
            jnp.int32(plan.t), batch, plan.device)
        plan.fill(evict)
        aux = dict(aux,
                   catchup_depth_max=np.int32(plan.depth),
                   hot_hit_rows=np.float32(plan.hit_rows),
                   hot_lookup_rows=np.float32(plan.lookup_rows),
                   evictions=np.int32(plan.evictions))
        return ({"embed": params["embed"], "dense": dense},
                {"step": plan.t, "hot": hot, "dense": dense_opt}, aux)

    def make_transform(self, max_steps: Optional[int] = None) -> Callable:
        """The ChunkStream worker-thread hook: plan each chunk's batch
        before it is queued (that *is* the lookahead), and enforce the
        step budget at the source — returning None ends the stream, so
        every planned step is consumed and every registered write-back
        gets its handle filled."""

        def transform(chunk):
            # the stream worker may reach the first chunk before the
            # consumer has called bundle.prepare(); wait for it (the
            # worker is a daemon thread, so an abandoned stream cannot
            # hang interpreter shutdown)
            self._prepared.wait()
            if self.planner is None:
                raise RuntimeError("bundle.prepare() must run before the "
                                   "stream transform plans batches")
            k = chunk["labels"].shape[0]
            if max_steps is not None:
                rem = max_steps - self.planner.t
                if rem <= 0:
                    return None
                if k > rem:
                    k = rem
                    chunk = {kk: v[:k] for kk, v in chunk.items()}
            if k != 1:
                raise ValueError(
                    "the async hotcold stream plans one batch per chunk "
                    f"(got a {k}-batch chunk): build the stream with "
                    "scan_steps=1; lookahead depth is buffer_size")
            plans = [self.planner.plan_batch(np.asarray(chunk["ids"][0]))]
            return PlannedChunk(chunk, plans)

        return transform

    def drive(self, params, state, stream, *, max_steps=None):
        """Consume a (planned or raw) chunk stream: dispatch each step,
        fill its eviction handle, thread the device carry. Returns
        ``(params, state, steps, stats)`` with the migration stats the
        bench records."""
        from ..train import engine as engine_lib

        pl = self.planner
        carry = [params["dense"], state["dense"], state["hot"]]
        last_t = [int(state.get("step", pl.t))]
        base = (pl.plan_seconds, self.store.gather_bytes, pl.hit_rows,
                pl.lookup_rows, pl.evictions)

        def plan(batch):
            return pl.plan_batch(np.asarray(batch["ids"]))

        def dispatch(p, batch):
            d_p, d_o, hot = carry
            d_p, d_o, hot, evict, _ = self.device_step(
                d_p, d_o, hot, jnp.int32(p.t), batch, p.device)
            p.fill(evict)
            carry[:] = [d_p, d_o, hot]
            last_t[0] = p.t

        res = engine_lib.drive_planned_stream(
            stream, plan=plan, dispatch=dispatch, max_steps=max_steps)
        jax.block_until_ready(carry)
        plan_s = pl.plan_seconds - base[0]
        overlap = 0.0
        if res.planned_ahead and plan_s > 0:
            overlap = max(0.0, min(1.0, 1.0 - res.stall_seconds / plan_s))
        stats = {
            "steps": res.steps,
            "stall_seconds": res.stall_seconds,
            "plan_seconds": plan_s,
            "migration_overlap_fraction": overlap,
            "cold_gather_bytes": self.store.gather_bytes - base[1],
            "hot_hit_rows": pl.hit_rows - base[2],
            "hot_lookup_rows": pl.lookup_rows - base[3],
            "evictions": pl.evictions - base[4],
            "store_buffer_pending": self.buffer_pending(),
        }
        self.last_stream_stats = stats
        return ({"embed": params["embed"], "dense": carry[0]},
                {"step": last_t[0], "hot": carry[2], "dense": carry[1]},
                res.steps, stats)

    def flush(self, params, state):
        """Reconcile every tier and settle all pending decay — the async
        counterpart of the synchronous flush, bitwise identical to it:
        drain the store-buffer, scatter the hot tier home, run the
        closed-form decay over the full tables through ``t``, re-gather
        the hot tier from the settled tables, persist the resume sidecar
        (mmap). Idempotent."""
        pl = self.planner
        store = self.store
        t = pl.t
        self.buffer.drain_all()
        for f in pl.fields:
            sid = pl.slot_ids[f]
            valid = sid < pl.vocab[f]
            ids = sid[valid]
            rows = {"w": {}, "m": {}, "v": {},
                    "ls": pl.slot_ls[f][valid]}
            for g in store.groups:
                rows["w"][g] = np.asarray(state["hot"]["w"][g][f])[valid]
                rows["m"][g] = np.asarray(state["hot"]["m"][g][f])[valid]
                rows["v"][g] = np.asarray(state["hot"]["v"][g][f])[valid]
            store.scatter(f, ids, rows)
        self._settle_decay(t)
        for f in pl.fields:
            store.ls[f][...] = t
            pl.slot_ls[f][...] = t
        hot = {k: {g: {} for g in store.groups} for k in ("w", "m", "v")}
        for g in store.groups:
            for f in store.fields:
                sid_c = np.minimum(pl.slot_ids[f], pl.vocab[f] - 1)
                hot["w"][g][f] = jnp.asarray(
                    np.asarray(store.w[g][f][sid_c]))
                hot["m"][g][f] = jnp.asarray(
                    np.asarray(store.m[g][f][sid_c]))
                hot["v"][g][f] = jnp.asarray(
                    np.asarray(store.v[g][f][sid_c]))
        self._save_sidecar(params["dense"], state["dense"])
        store.flush_files()
        return ({"embed": store.param_views(), "dense": params["dense"]},
                {"step": t, "hot": hot, "dense": state["dense"]})

    def export(self, params):
        """Canonical (placement-independent) checkpoint tree: materialized
        copies of the settled cold tables. Export a *flushed* tree."""
        return {"embed": {g: {f: np.array(self.store.w[g][f])
                              for f in self.store.fields}
                          for g in self.store.groups},
                "dense": params["dense"]}

    # -- crash-safe snapshots ------------------------------------------------

    def export_snapshot(self, params, state) -> dict:
        """Flat numpy leaves capturing the complete *flushed* controller
        state for the ``mem`` backend — settled cold tables (w/m/v), the
        per-field ``ls`` vector, the planner's residency/frequency maps,
        ``t``, and the dense tower's params + optimizer moments. Call only
        right after ``flush`` (buffer drained, hot tier scattered home,
        ``ls`` uniform at ``t``), which makes the hot tier redundant: a
        resume regathers it from the tables, exactly as ``flush`` did.

        The ``mmap`` backend needs none of this — its snapshot is a copy
        of the store directory itself, whose resume sidecar ``flush``
        already persisted (``prepare``/``init`` replay it on open).
        """
        pl = self.planner
        store = self.store
        leaves = {"t": np.int64(pl.t)}
        for f in pl.fields:
            leaves[f"slot_ids/{f}"] = np.array(pl.slot_ids[f])
            leaves[f"slot_of/{f}"] = np.array(pl.slot_of[f])
            leaves[f"slot_ls/{f}"] = np.array(pl.slot_ls[f])
            leaves[f"freq/{f}"] = np.array(pl.freq[f])
            leaves[f"ls/{f}"] = np.array(store.ls[f])
        for g in store.groups:
            for f in store.fields:
                leaves[f"cold_w/{g}/{f}"] = np.array(store.w[g][f])
                leaves[f"cold_m/{g}/{f}"] = np.array(store.m[g][f])
                leaves[f"cold_v/{g}/{f}"] = np.array(store.v[g][f])
        for i, leaf in enumerate(jax.tree.leaves(params["dense"])):
            leaves[f"dense_param/{i}"] = np.asarray(leaf)
        for i, leaf in enumerate(jax.tree.leaves(state["dense"])):
            leaves[f"dense_opt/{i}"] = np.asarray(leaf)
        return leaves

    def import_snapshot(self, leaves, params):
        """Rebuild (params, state) from ``export_snapshot`` leaves.
        ``params`` is the freshly *prepared* tree (it supplies the dense
        treedef; its embed views point at this controller's store, whose
        tables are overwritten here). Returns the (params, state) pair the
        trainer resumes from — bitwise the post-flush state the snapshot
        captured."""
        pl = self.planner
        store = self.store
        t = int(leaves["t"])
        pl.t = t
        for f in pl.fields:
            pl.slot_ids[f][...] = leaves[f"slot_ids/{f}"]
            pl.slot_of[f][...] = leaves[f"slot_of/{f}"]
            pl.slot_ls[f][...] = leaves[f"slot_ls/{f}"]
            pl.freq[f][...] = leaves[f"freq/{f}"]
            store.ls[f][...] = leaves[f"ls/{f}"]
        for g in store.groups:
            for f in store.fields:
                store.w[g][f][...] = leaves[f"cold_w/{g}/{f}"]
                store.m[g][f][...] = leaves[f"cold_m/{g}/{f}"]
                store.v[g][f][...] = leaves[f"cold_v/{g}/{f}"]
        hot = {k: {g: {} for g in store.groups} for k in ("w", "m", "v")}
        for g in store.groups:
            for f in store.fields:
                sid_c = np.minimum(pl.slot_ids[f], pl.vocab[f] - 1)
                hot["w"][g][f] = jnp.asarray(
                    np.asarray(store.w[g][f][sid_c]))
                hot["m"][g][f] = jnp.asarray(
                    np.asarray(store.m[g][f][sid_c]))
                hot["v"][g][f] = jnp.asarray(
                    np.asarray(store.v[g][f][sid_c]))
        leaves_p, treedef = jax.tree.flatten(params["dense"])
        dense = jax.tree.unflatten(treedef, [
            jnp.asarray(leaves[f"dense_param/{i}"])
            for i in range(len(leaves_p))])
        opt_template = self.dense_tx.init(dense)
        leaves_o, treedef_o = jax.tree.flatten(opt_template)
        dense_opt = jax.tree.unflatten(treedef_o, [
            jnp.asarray(leaves[f"dense_opt/{i}"])
            for i in range(len(leaves_o))])
        return ({"embed": store.param_views(), "dense": dense},
                {"step": t, "hot": hot, "dense": dense_opt})

    # -- internals ----------------------------------------------------------

    @property
    def buffer(self) -> StoreBuffer:
        return self.planner.buffer

    def buffer_pending(self) -> int:
        return self.planner.buffer.pending() if self.planner else 0

    def _settle_decay(self, t: int):
        """``w *= (1 - lr*l2)^k`` over the full tables — the exact
        expression ``decay_catchup_rows`` evaluates in the synchronous
        flush, chunked by rows so a >RAM mmap table settles under a
        bounded footprint."""
        lr, l2 = self.adam_kw["lr"], self.adam_kw["l2"]

        @jax.jit
        def settle(w, ls):
            k = jnp.maximum(jnp.int32(t) - ls, 0)
            factor = jnp.float32(optim_lib.decay_factor(lr, l2))
            scale = jnp.where(k > 0, factor ** k.astype(jnp.float32),
                              jnp.float32(1.0))
            return (w.astype(jnp.float32) * scale[:, None]).astype(w.dtype)

        R = self.settle_chunk_rows
        for g in self.store.groups:
            for f in self.store.fields:
                tbl = self.store.w[g][f]
                ls = self.store.ls[f]
                for lo in range(0, tbl.shape[0], R):
                    hi = min(lo + R, tbl.shape[0])
                    tbl[lo:hi] = np.asarray(
                        settle(np.asarray(tbl[lo:hi]),
                               np.asarray(ls[lo:hi])))
        self.store.flush_files()

    def _save_sidecar(self, dense_params, dense_opt):
        if self.store.backend != "mmap":
            return
        pl = self.planner
        leaves = {"t": np.int64(pl.t)}
        for f in pl.fields:
            leaves[f"slot_ids/{f}"] = pl.slot_ids[f]
            leaves[f"slot_of/{f}"] = pl.slot_of[f]
            leaves[f"slot_ls/{f}"] = pl.slot_ls[f]
            leaves[f"freq/{f}"] = pl.freq[f]
        for i, leaf in enumerate(jax.tree.leaves(dense_params)):
            leaves[f"dense_param/{i}"] = np.asarray(leaf)
        for i, leaf in enumerate(jax.tree.leaves(dense_opt)):
            leaves[f"dense_opt/{i}"] = np.asarray(leaf)
        self.store.save_sidecar(leaves)


def make_async_hotcold_bundle(cfg, hp, **kwargs) -> TrainStepBundle:
    """The async hotcold placement as a ``TrainStepBundle`` (see
    ``AsyncHotCold`` for the knobs: backend/directory/store, capacity,
    admission/half_life, and the shared clip/optimizer hypers)."""
    return AsyncHotCold(cfg, hp, **kwargs).bundle()
