"""Out-of-core cold tier + async migration (embed/coldstore, embed/migrate).

The load-bearing guarantee: moving the cold tier out of the jitted step —
host numpy tables ("mem") or np.memmap files ("mmap"), residency planned
host-side one step ahead, eviction values flowing through the store-buffer
— changes *nothing* about the math. Async runs export params **bitwise
identical** to the synchronous hotcold placement (capacity >= 2, the same
taxonomy as tests/test_hotcold.py), under both admission policies, whether
steps are planned inline or overlapped on the stream worker thread, and
across an mmap flush -> process "exit" -> reopen -> resume boundary.

The store-buffer's read-your-writes protocol (newest pending entry per
(field, id), reads consult the buffer before the store, drain writes
before popping) is pinned by a property test driving random
miss/evict/drain interleavings against a dict oracle.

Property tests run through tests/hypcompat.py: real hypothesis when
installed, a deterministic seeded sweep otherwise.
"""

import functools
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    from hypcompat import hypothesis, st

from repro.core import build_train_step, scale_hyperparams
from repro.data import stream as stream_lib
from repro.data.synthetic import make_ctr_dataset, iterate_batches
from repro.embed.coldstore import ColdStore, EvictionHandle, StoreBuffer
from repro.models import ctr
from repro.train import train_ctr

VOCABS = (60, 13, 5)
BATCH = 32
STEPS = 8


def _cfg(**kw):
    return ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                         emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                         **kw)


def _hp():
    return scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                             base_batch=BATCH, batch_size=BATCH,
                             base_dense_lr=2e-3)


@functools.lru_cache(maxsize=None)
def _batches(seed=1):
    ds = make_ctr_dataset(512, VOCABS, n_dense=3, zipf_a=1.2, seed=3)
    out = []
    for b in iterate_batches(ds, BATCH, seed=seed):
        out.append(b)
        if len(out) >= STEPS:
            break
    return out


def _bundle(capacity, **kw):
    return build_train_step(_cfg(), _hp(), path="hotcold",
                            hot_capacity=capacity, **kw)


def _steps(bundle, params, state, batches):
    auxes = []
    for b in batches:
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, state, aux = bundle.step(params, state, batch)
        auxes.append({k: float(v) for k, v in aux.items()})
    return params, state, auxes


def _export(bundle, params):
    return {jax.tree_util.keystr(k): np.asarray(v).copy() for k, v in
            jax.tree_util.tree_leaves_with_path(bundle.export(params))}


def _run_inline(capacity, **kw):
    bundle = _bundle(capacity, **kw)
    params = bundle.prepare(ctr.init(jax.random.key(0), _cfg()))
    state = bundle.init(params)
    params, state, auxes = _steps(bundle, params, state, _batches())
    params, state = bundle.flush(params, state)
    return _export(bundle, params), auxes


@functools.lru_cache(maxsize=None)
def _run_cached(capacity, cold_store="none", admission="cumulative",
                half_life=0):
    """Memoised non-mmap runs (each capacity compiles its own shapes)."""
    kw = {}
    if cold_store != "none":
        kw["cold_store"] = cold_store
    return _run_inline(capacity, admission=admission, half_life=half_life,
                       **kw)


def _assert_bitwise(a, b, msg=""):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{msg}{k}")


# ---------------------------------------------------------------------------
# exactness: async == sync, mem == mmap, capacity-independent
# ---------------------------------------------------------------------------


@hypothesis.given(capacity=st.sampled_from([2, 4, 8]))
@hypothesis.settings(max_examples=6, deadline=None)
def test_async_mem_bitwise_matches_sync(capacity):
    """The tentpole claim: host-side planning + store-buffered evictions
    reproduce the synchronous in-step cold tier bit for bit — same losses,
    same hit/eviction counts, same exported params."""
    sync, sync_aux = _run_cached(capacity)
    am, am_aux = _run_cached(capacity, cold_store="mem")
    _assert_bitwise(sync, am)
    for sa, aa in zip(sync_aux, am_aux):
        for k in ("loss", "hot_hit_rows", "hot_lookup_rows", "evictions"):
            assert sa[k] == aa[k], (k, sa, aa)


def test_async_mmap_bitwise_matches_mem():
    """The on-disk backend is a storage choice, not a math change."""
    am, _ = _run_cached(4, cold_store="mem")
    with tempfile.TemporaryDirectory() as d:
        mm, _ = _run_inline(4, cold_store="mmap", cold_dir=d)
    _assert_bitwise(am, mm)


def test_async_capacity_runs_bitwise_identical():
    """PR 8's capacity-independence survives the out-of-core split: a
    capacity-starved async run equals the no-eviction run bit for bit."""
    small, _ = _run_cached(2, cold_store="mem")
    big, _ = _run_cached(100, cold_store="mem")
    _assert_bitwise(small, big)


def test_decayed_admission_async_matches_sync():
    """The decayed admission policy's f32 frequency arithmetic agrees
    bitwise between the host planner (numpy) and the device step (XLA).
    (Exported params can never distinguish the policies — residency does
    not change the math; tests/test_hotcold.py pins their divergence on
    the frequency state instead.)"""
    sync, _ = _run_cached(4, admission="decayed", half_life=3)
    am, _ = _run_cached(4, cold_store="mem", admission="decayed",
                        half_life=3)
    _assert_bitwise(sync, am)


# ---------------------------------------------------------------------------
# the overlapped path: stream transform + driver
# ---------------------------------------------------------------------------


def _run_driver(capacity, **kw):
    bundle = _bundle(capacity, **kw)

    def events():
        yield from _batches()

    stream = stream_lib.stream_chunks(
        events(), BATCH, 1, buffer_size=4,
        transform=bundle.stream_transform(max_steps=STEPS))
    res = train_ctr(_cfg(), None, None, None, batch_size=BATCH,
                    step_bundle=bundle, max_steps=STEPS, engine="scan",
                    mode="stream", stream=stream)
    ctrl = bundle.stream_driver.__self__
    return _export(bundle, res.params), res, ctrl


def test_overlapped_driver_bitwise_matches_inline():
    """Planning on the stream worker thread (lookahead = buffer_size)
    reorders nothing: the overlapped drive bit-matches the inline step
    loop and the synchronous placement."""
    sync, _ = _run_cached(4)
    drv, res, ctrl = _run_driver(4, cold_store="mem")
    _assert_bitwise(sync, drv)
    assert res.steps == STEPS
    stats = ctrl.last_stream_stats
    assert stats["steps"] == STEPS
    assert 0.0 <= stats["migration_overlap_fraction"] <= 1.0
    assert stats["cold_gather_bytes"] > 0
    # the drive-end snapshot may hold in-flight write-backs from the last
    # steps; train_ctr's flush drains every one of them
    assert stats["store_buffer_pending"] >= 0
    assert ctrl.buffer_pending() == 0


def test_transform_rejects_multi_batch_chunks():
    bundle = _bundle(4, cold_store="mem")
    bundle.prepare(ctr.init(jax.random.key(0), _cfg()))
    transform = bundle.stream_transform(max_steps=STEPS)
    b = _batches()[0]
    chunk = {k: np.stack([v, v]) for k, v in b.items()}
    with pytest.raises(ValueError, match="scan_steps=1"):
        transform(chunk)


def test_transform_enforces_step_budget():
    """The budget lives at the source: the transform ends the stream, so
    no planned step (with registered write-backs) is ever dropped.
    (Capacity >= vocab: every id stays resident, so planning registers no
    write-backs — calling the transform without a consumer dispatching
    steps would otherwise block on its own planned evictions' handles.)"""
    bundle = _bundle(100, cold_store="mem")
    bundle.prepare(ctr.init(jax.random.key(0), _cfg()))
    transform = bundle.stream_transform(max_steps=2)
    b = _batches()[0]
    chunk = {k: v[None] for k, v in b.items()}
    assert transform(dict(chunk)) is not None
    assert transform(dict(chunk)) is not None
    assert transform(dict(chunk)) is None


# ---------------------------------------------------------------------------
# mmap persistence: flush -> reopen -> resume
# ---------------------------------------------------------------------------


def test_mmap_flush_reopen_resume_bitexact():
    """Flush at step 4, drop the store, reopen the directory in a *fresh*
    bundle (params deliberately re-initialized with a different seed — the
    directory plus sidecar must fully define the model) and run steps 5-8:
    bit-identical to one uninterrupted run flushed at the same step."""
    bs = _batches()
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        b1 = _bundle(4, cold_store="mmap", cold_dir=d1)
        p = b1.prepare(ctr.init(jax.random.key(0), _cfg()))
        s = b1.init(p)
        p, s, _ = _steps(b1, p, s, bs[:4])
        p, s = b1.flush(p, s)
        p, s, _ = _steps(b1, p, s, bs[4:])
        p, s = b1.flush(p, s)
        ref = _export(b1, p)

        b2 = _bundle(4, cold_store="mmap", cold_dir=d2)
        p = b2.prepare(ctr.init(jax.random.key(0), _cfg()))
        s = b2.init(p)
        p, s, _ = _steps(b2, p, s, bs[:4])
        p, s = b2.flush(p, s)
        b2.stream_driver.__self__.store.close()

        b3 = _bundle(4, cold_store="mmap", cold_dir=d2)
        p = b3.prepare(ctr.init(jax.random.key(1), _cfg()))
        ctrl3 = b3.stream_driver.__self__
        assert ctrl3.store.resumed
        assert ctrl3.planner.t == 4
        s = b3.init(p)
        p, s, _ = _steps(b3, p, s, bs[4:])
        p, s = b3.flush(p, s)
        res = _export(b3, p)
    _assert_bitwise(ref, res)


def test_flush_is_bitwise_idempotent():
    bundle = _bundle(4, cold_store="mem")
    p = bundle.prepare(ctr.init(jax.random.key(0), _cfg()))
    s = bundle.init(p)
    p, s, _ = _steps(bundle, p, s, _batches())
    p, s = bundle.flush(p, s)
    once = _export(bundle, p)
    p, s = bundle.flush(p, s)
    _assert_bitwise(once, _export(bundle, p))


# ---------------------------------------------------------------------------
# ColdStore basics
# ---------------------------------------------------------------------------


def test_store_mem_mmap_gather_scatter_agree():
    spec = {"fm": {"field_0": (20, 4, "float32")},
            "lin": {"field_0": (20, 1, "float32")}}
    rng = np.random.default_rng(0)
    mem = ColdStore.create(spec, backend="mem")
    with tempfile.TemporaryDirectory() as d:
        mm = ColdStore.create(spec, backend="mmap", directory=d)
        for store in (mem, mm):
            store.w["fm"]["field_0"][...] = rng.normal(size=(20, 4))
            rng = np.random.default_rng(0)  # same draws for both stores
        rows = {"w": {"fm": np.ones((2, 4), np.float32),
                      "lin": np.ones((2, 1), np.float32)},
                "m": {"fm": np.full((2, 4), 2, np.float32),
                      "lin": np.full((2, 1), 2, np.float32)},
                "v": {"fm": np.full((2, 4), 3, np.float32),
                      "lin": np.full((2, 1), 3, np.float32)},
                "ls": np.asarray([7, 9], np.int32)}
        ids = np.asarray([3, 11])
        for store in (mem, mm):
            store.scatter("field_0", ids, rows)
        g_mem = mem.gather("field_0", ids)
        g_mm = mm.gather("field_0", ids)
        for key in ("w", "m", "v"):
            for g in ("fm", "lin"):
                np.testing.assert_array_equal(g_mem[key][g], g_mm[key][g])
                np.testing.assert_array_equal(g_mem[key][g], rows[key][g])
        np.testing.assert_array_equal(g_mem["ls"], rows["ls"])
        assert mem.gather_bytes == mm.gather_bytes > 0
        assert mem.table_bytes() == mm.table_bytes()
        mm.close()


def test_store_rejects_bad_backend():
    with pytest.raises(ValueError, match="backend"):
        ColdStore("ssd")
    with pytest.raises(ValueError, match="directory"):
        ColdStore("mmap")


# ---------------------------------------------------------------------------
# store-buffer read-your-writes under random interleavings
# ---------------------------------------------------------------------------


def _fresh_buffer(vocab=12, dim=3):
    spec = {"fm": {"field_0": (vocab, dim, "float32")}}
    store = ColdStore.create(spec, backend="mem")
    store.w["fm"]["field_0"][...] = np.arange(
        vocab * dim, dtype=np.float32).reshape(vocab, dim)
    return store, StoreBuffer(store)


@hypothesis.given(seed=st.integers(0, 63))
@hypothesis.settings(max_examples=24, deadline=None)
def test_store_buffer_read_your_writes(seed):
    """Random interleavings of register / late handle fill / read / drain
    against a dict oracle: a read always observes the newest registered
    write for an id (even while its handle is unfilled and nothing has
    reached the store), drains never lose or reorder writes, and a final
    drain_all leaves the store itself equal to the oracle."""
    rng = np.random.default_rng(seed)
    vocab, dim = 12, 3
    store, buf = _fresh_buffer(vocab, dim)
    oracle = {i: store.w["fm"]["field_0"][i].copy() for i in range(vocab)}
    unfilled = []   # (handle, bank, ids, rows) waiting for a late fill
    step = 0
    for _ in range(30):
        op = rng.integers(0, 4)
        if op == 0:                                # evict: register a step
            step += 1
            n = int(rng.integers(1, 4))
            ids = rng.choice(vocab, size=n, replace=False)
            bank = rng.normal(size=(n, dim)).astype(np.float32)
            handle = EvictionHandle()
            buf.register("field_0", ids, np.full(n, step, np.int32),
                         np.arange(n), step, handle)
            for k, i in enumerate(ids):
                oracle[int(i)] = bank[k].copy()
            unfilled.append((handle, bank))
            if rng.integers(0, 2):                 # sometimes fill late
                continue
            op = 1
        if op == 1 and unfilled:                   # fill oldest handle
            handle, bank = unfilled.pop(0)
            handle.fill({k: {"fm": {"field_0": bank * s}}
                         for k, s in (("w", 1), ("m", 0), ("v", 0))})
        elif op == 2:                              # read-your-writes
            n = int(rng.integers(1, 5))
            ids = rng.choice(vocab, size=n, replace=True)
            # fill everything pending first: an unfilled handle blocks a
            # read, which single-threaded would deadlock (in training the
            # consumer thread fills while the planner reads)
            for handle, bank in unfilled:
                handle.fill({k: {"fm": {"field_0": bank * s}}
                             for k, s in (("w", 1), ("m", 0), ("v", 0))})
            unfilled.clear()
            out = buf.read("field_0", ids)
            for k, i in enumerate(ids):
                np.testing.assert_array_equal(
                    out["w"]["fm"][k], oracle[int(i)],
                    err_msg=f"id {i} at seed {seed}")
        elif op == 3:                              # opportunistic drain
            buf.drain(ready_only=True)
    for handle, bank in unfilled:
        handle.fill({k: {"fm": {"field_0": bank * s}}
                     for k, s in (("w", 1), ("m", 0), ("v", 0))})
    buf.drain_all()
    assert buf.pending() == 0
    for i in range(vocab):
        np.testing.assert_array_equal(store.w["fm"]["field_0"][i],
                                      oracle[i], err_msg=f"store id {i}")


def test_store_buffer_newest_entry_wins():
    """Two evictions of the same id: the read returns the newer bank even
    though the older entry was registered (and could still drain) first."""
    store, buf = _fresh_buffer()
    h1, h2 = EvictionHandle(), EvictionHandle()
    old = np.full((1, 3), 5.0, np.float32)
    new = np.full((1, 3), 9.0, np.float32)
    buf.register("field_0", np.asarray([4]), np.asarray([1], np.int32),
                 np.arange(1), 1, h1)
    buf.register("field_0", np.asarray([4]), np.asarray([2], np.int32),
                 np.arange(1), 2, h2)
    h1.fill({k: {"fm": {"field_0": old}} for k in ("w", "m", "v")})
    h2.fill({k: {"fm": {"field_0": new}} for k in ("w", "m", "v")})
    out = buf.read("field_0", np.asarray([4]))
    np.testing.assert_array_equal(out["w"]["fm"][0], new[0])
    buf.drain_all()
    assert buf.pending() == 0
    np.testing.assert_array_equal(store.w["fm"]["field_0"][4], new[0])


def test_store_buffer_drain_ready_only_skips_inflight():
    store, buf = _fresh_buffer()
    h = EvictionHandle()
    buf.register("field_0", np.asarray([2]), np.asarray([1], np.int32),
                 np.arange(1), 1, h)
    assert buf.drain(ready_only=True) == 0
    assert buf.pending() == 1
    h.fill({k: {"fm": {"field_0": np.ones((1, 3), np.float32)}}
            for k in ("w", "m", "v")})
    assert buf.drain(ready_only=True) == 1
    assert buf.pending() == 0
