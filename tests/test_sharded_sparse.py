"""Sharded+sparse hybrid placement: per-shard unique-id dedup math,
single-device (1x1 mesh) equivalence with lazy decay, the capacity-overflow
dense fallback (including mid-run overflow), shard-offset-aware kernels vs
their oracles, store/CLI routing — and the full multi-device exactness
matrix (2x4 / 8x1 / mod / overflow) in an 8-virtual-device subprocess, and
a float64 run on a 2x2 mesh of four against the sparse placement.

The contract under test: the hybrid step — per-shard dedup of the global
batch, gather + lazy-L2-decay catch-up via per-row ``last_step``, fused
CowClip/L2/Adam on the touched rows, scatter back (dense per-shard fallback
on capacity overflow) — followed by a ``flush`` matches the single-device
dense substrate optimizer to f32 tolerance, params and AUC alike.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_optimizer, build_train_step, scale_hyperparams
from repro.embed import EmbeddingStore, store_for
from repro.embed.sharded import RowShardPlan
from repro.embed.sharded_sparse import shard_capacity, shard_unique_sets
from repro.kernels.cowclip import ops as cc_ops
from repro.launch.train import resolve_placement
from repro.models import ctr
from repro.train.loop import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCABS = (57, 13, 5)


def _cfg(**kw):
    return ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                         emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                         **kw)


def _hp():
    return scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                             base_batch=64, batch_size=64,
                             base_dense_lr=2e-3)


def _batches(n_steps, batch=32, seed=1, widen_after=0):
    """Duplicate-heavy batches; with ``widen_after=k`` field 0 starts on a
    2-id pool and widens to 5 ids from step k (overflow trigger)."""
    rng = np.random.default_rng(seed)
    for i in range(n_steps):
        pool0 = ([1, 50] if widen_after and i < widen_after
                 else [1, 2, 3, 50, 51])
        ids = np.stack([
            rng.choice(pool0, size=batch),
            rng.integers(0, 13, size=batch),
            rng.choice([0, 4], size=batch),
        ], axis=1).astype(np.int32)
        yield {
            "ids": jnp.asarray(ids),
            "dense": jnp.asarray(rng.normal(size=(batch, 3)).astype(np.float32)),
            "labels": jnp.asarray((rng.random(batch) < 0.3).astype(np.float32)),
        }


def _max_err(a_tree, b_tree):
    return max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree))
    )


def _dense_oracle(cfg, hp):
    params = ctr.init(jax.random.key(0), cfg)
    tx = build_optimizer(hp, warmup_steps=0)
    return (make_train_step(cfg, tx), jax.tree.map(jnp.copy, params),
            tx.init(params), params)


# ---------------------------------------------------------------------------
# per-shard dedup (pure, no mesh)
# ---------------------------------------------------------------------------


def test_shard_capacity_defaults_and_caps():
    plan = RowShardPlan(57, 4)                      # rows_per_shard = 15
    assert shard_capacity(plan, batch=32) == 15     # min(batch, rows)
    assert shard_capacity(plan, batch=8) == 8
    assert shard_capacity(plan, batch=32, unique_capacity=3) == 3
    # the cap never exceeds the exact default (overflow would be pointless)
    assert shard_capacity(plan, batch=8, unique_capacity=100) == 8
    assert shard_capacity(plan, batch=0, unique_capacity=0) == 1


@pytest.mark.parametrize("scheme", ["div", "mod"])
def test_shard_unique_sets_slots_counts_owners(scheme):
    plan = RowShardPlan(13, 4, scheme)
    ids = jnp.array([0, 1, 5, 5, 9, 12, 12, 12, 1, 0], jnp.int32)
    us = shard_unique_sets(ids, plan, capacity=4)
    assert us.local_rows.shape == (4, 4)
    assert not bool(us.overflow.any())
    ids_np = np.asarray(ids)
    for s in range(4):
        owned = sorted(set(i for i in ids_np
                           if int(plan.shard_of(jnp.asarray([i]))[0]) == s))
        loc = np.asarray(us.local_rows[s])
        cnt = np.asarray(us.counts[s])
        exp_loc = [int(plan.local_row(jnp.asarray([i]))[0]) for i in owned]
        np.testing.assert_array_equal(loc[:len(owned)], exp_loc)
        # pads out of local range with zero counts
        assert (loc[len(owned):] == plan.rows_per_shard).all()
        assert (cnt[len(owned):] == 0).all()
        np.testing.assert_array_equal(
            cnt[:len(owned)], [int((ids_np == i).sum()) for i in owned])


def test_shard_unique_sets_overflow_flag_per_shard():
    plan = RowShardPlan(57, 4)      # div: shard 0 owns 0..14
    ids = jnp.array([1, 2, 3, 50, 51], jnp.int32)
    us = shard_unique_sets(ids, plan, capacity=2)
    # shard 0 sees 3 distinct owned ids > capacity 2 -> overflow; shard 3
    # sees exactly 2 -> fine; shards 1, 2 see none
    np.testing.assert_array_equal(np.asarray(us.overflow),
                                  [True, False, False, False])
    # kept slots are the capacity smallest owned ids
    np.testing.assert_array_equal(np.asarray(us.local_rows[0]), [1, 2])


def test_shard_unique_sets_full_shard_no_false_overflow():
    """A batch covering every row a shard owns, at exactly that capacity,
    must not flag overflow (the sentinel needs its own internal slot)."""
    plan = RowShardPlan(8, 2)       # shard 0 owns 0..3
    ids = jnp.array([0, 1, 2, 3, 0, 1, 7], jnp.int32)
    us = shard_unique_sets(ids, plan, capacity=4)
    assert not bool(us.overflow[0])
    np.testing.assert_array_equal(np.asarray(us.local_rows[0]), [0, 1, 2, 3])


# ---------------------------------------------------------------------------
# single-device (1x1 mesh) equivalence — in-process
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["div", "mod"])
def test_hybrid_step_matches_dense_on_1x1_mesh(scheme):
    cfg = _cfg()
    hp = _hp()
    dstep, dparams, dstate, params0 = _dense_oracle(cfg, hp)

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, hp, path="sharded_sparse", mesh=mesh,
                              partition=scheme, warmup_steps=0)
    sparams = bundle.prepare(jax.tree.map(jnp.copy, params0))
    sstate = bundle.init(sparams)

    for b in _batches(6):
        dparams, dstate, da = dstep(dparams, dstate, dict(b))
        sparams, sstate, sa = bundle.step(sparams, sstate, dict(b))
        assert float(da["loss"]) == pytest.approx(float(sa["loss"]), rel=1e-5)
        assert int(sa["overflow_shards"]) == 0

    sparams, sstate = bundle.flush(sparams, sstate)
    assert _max_err(dparams, bundle.export(sparams)) <= 1e-5


def test_hybrid_defers_untouched_rows_until_flush():
    """Before flush, ids absent from every batch keep their original rows
    byte-identical (decay pending in last_step); flush settles them to the
    dense path's values and is idempotent."""
    cfg = _cfg()
    hp = _hp()
    dstep, dparams, dstate, params0 = _dense_oracle(cfg, hp)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, hp, path="sharded_sparse", mesh=mesh,
                              warmup_steps=0)
    sparams = bundle.prepare(jax.tree.map(jnp.copy, params0))
    sstate = bundle.init(sparams)
    before = np.asarray(params0["embed"]["fm"]["field_0"]).copy()

    batches = list(_batches(3, seed=2))
    for b in batches:
        dparams, dstate, _ = dstep(dparams, dstate, dict(b))
        sparams, sstate, _ = bundle.step(sparams, sstate, dict(b))

    touched = np.unique(np.concatenate(
        [np.asarray(b["ids"])[:, 0] for b in batches]))
    untouched = np.setdiff1d(np.arange(VOCABS[0]), touched)
    after = np.asarray(sparams["embed"]["fm"]["field_0"])
    ls = np.asarray(sstate["last_step"]["fm"]["field_0"])
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert (ls[untouched] == 0).all()
    assert (ls[touched] > 0).all()

    f_params, f_state = bundle.flush(sparams, sstate)
    assert _max_err(dparams, bundle.export(f_params)) <= 1e-5
    p2, s2 = bundle.flush(f_params, f_state)
    assert _max_err(f_params, p2) == 0.0
    for a, b in zip(jax.tree.leaves(f_state), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# capacity-overflow dense fallback (acceptance criterion)
# ---------------------------------------------------------------------------


def test_overflow_mid_run_falls_back_dense_and_stays_exact():
    """unique_capacity=3 while field 0's pool widens from 2 to 5 distinct
    ids at step 2: the (only) shard overflows mid-run, takes the dense
    fallback, and the final params still match the dense oracle at <=1e-5
    after the next flush — unlike the single-device sparse placement, the
    hybrid's overflow trades speed, never exactness."""
    cfg = _cfg(unique_capacity=3)
    hp = _hp()
    dstep, dparams, dstate, params0 = _dense_oracle(
        dataclasses.replace(cfg, unique_capacity=0), hp)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, hp, path="sharded_sparse", mesh=mesh,
                              warmup_steps=0)
    sparams = bundle.prepare(jax.tree.map(jnp.copy, params0))
    sstate = bundle.init(sparams)

    def narrow_batches(n_steps, widen_after, batch=32, seed=3):
        # every field stays within capacity 3 until field 0 widens to 5 ids
        rng = np.random.default_rng(seed)
        for i in range(n_steps):
            pool0 = [1, 50] if i < widen_after else [1, 2, 3, 50, 51]
            ids = np.stack([
                rng.choice(pool0, size=batch),
                rng.integers(0, 3, size=batch),
                rng.choice([0, 4], size=batch),
            ], axis=1).astype(np.int32)
            yield {
                "ids": jnp.asarray(ids),
                "dense": jnp.asarray(
                    rng.normal(size=(batch, 3)).astype(np.float32)),
                "labels": jnp.asarray(
                    (rng.random(batch) < 0.3).astype(np.float32)),
            }

    overflow_steps = []
    for i, b in enumerate(narrow_batches(6, widen_after=2)):
        dparams, dstate, da = dstep(dparams, dstate, dict(b))
        sparams, sstate, sa = bundle.step(sparams, sstate, dict(b))
        assert float(da["loss"]) == pytest.approx(float(sa["loss"]), rel=1e-5)
        if int(sa["overflow_shards"]):
            overflow_steps.append(i)

    # steps 0-1 fit in capacity (2 distinct ids), the widened steps overflow
    assert overflow_steps and min(overflow_steps) >= 2

    sparams, sstate = bundle.flush(sparams, sstate)
    assert _max_err(dparams, bundle.export(sparams)) <= 1e-5


# ---------------------------------------------------------------------------
# shard-offset row functions vs the local-id form
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dim", [8, 1])
def test_sparse_kernels_row_offset_match_oracle(dim):
    """The row_offset form: global uids against a mid-table row-shard
    window give the rows and tables of the local-id form, whose pads fall
    out of range (dim=1 exercises the CowClip-exempt LR stream)."""
    vocab, cap = 50, 6
    rows, off = 15, 15          # shard window: global rows 15..29
    ks = jax.random.split(jax.random.key(0), 6)
    w = 0.01 * jax.random.normal(ks[0], (vocab, dim))
    m = 0.001 * jax.random.normal(ks[1], (vocab, dim))
    v = 0.0001 * jnp.abs(jax.random.normal(ks[2], (vocab, dim)))
    ls = jax.random.randint(ks[3], (vocab,), 0, 5)
    t = jnp.asarray(7, jnp.int32)
    ids = jnp.array([17, 22, 17, 29, 15, 22])       # global, inside window
    uids, cnt = jnp.unique(ids, size=cap, fill_value=vocab,
                           return_counts=True)
    uids, cnt = uids.astype(jnp.int32), cnt.astype(jnp.float32)
    g_rows = 0.1 * jax.random.normal(ks[4], (cap, dim))
    kw = dict(lr=1e-3, l2=1e-4)
    n_real = int((cnt > 0).sum())

    shard = [a[off:off + rows] for a in (w, m, v, ls)]
    off_rows = cc_ops.sparse_gather_catchup(*shard, uids, t, row_offset=off,
                                            **kw)
    # pre-localized ids: the pads (vocab - off = 35) are out of range
    loc_rows = cc_ops.sparse_gather_catchup(*shard, uids - off, t, **kw)
    for a, b in zip(off_rows, loc_rows):
        np.testing.assert_array_equal(np.asarray(a)[:n_real],
                                      np.asarray(b)[:n_real])

    def update(ids, **offset):
        return cc_ops.sparse_update_scatter(
            *(jnp.copy(a) for a in shard), ids, cnt, off_rows[0], g_rows,
            off_rows[1], off_rows[2], t, **offset, **kw)

    off_out = update(uids, row_offset=off)
    for a, b in zip(off_out, update(uids - off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # rows outside the unique set are untouched on the shard
    unset = np.setdiff1d(np.arange(rows), np.asarray(uids[:n_real]) - off)
    for after, before in zip(off_out, shard):
        np.testing.assert_array_equal(np.asarray(after)[unset],
                                      np.asarray(before)[unset])


# ---------------------------------------------------------------------------
# store / bundle / CLI routing
# ---------------------------------------------------------------------------


def test_store_routes_sharded_sparse():
    from repro.core.builders import TRAIN_PATHS

    assert "sharded_sparse" in TRAIN_PATHS
    store = store_for(_cfg(placement="sharded_sparse"))
    assert store.placement == "sharded_sparse"
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    d = EmbeddingStore(placement="sharded_sparse", mesh=mesh,
                       partition="mod").describe()
    assert "sharded_sparse" in d and "unique-id" in d and "mod" in d


def test_hybrid_bundle_prepare_export_round_trip():
    """prepare pads (57 -> 60 under model=4 when available) and export
    strips back to canonical tables; init carries row-sharded last_step."""
    n_model = 4 if jax.device_count() >= 4 else 1
    mesh = jax.make_mesh((1, n_model), ("data", "model"))
    cfg = _cfg()
    bundle = build_train_step(cfg, _hp(), path="sharded_sparse", mesh=mesh)
    params0 = ctr.init(jax.random.key(0), cfg)
    prepared = bundle.prepare(jax.tree.map(jnp.copy, params0))
    plan = RowShardPlan(57, n_model)
    assert prepared["embed"]["fm"]["field_0"].shape == (plan.padded_vocab, 8)
    state = bundle.init(prepared)
    assert state["last_step"]["fm"]["field_0"].shape == (plan.padded_vocab,)
    assert state["last_step"]["fm"]["field_0"].dtype == jnp.int32
    for a, b in zip(jax.tree.leaves(bundle.export(prepared)),
                    jax.tree.leaves(params0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ctr_param_spec_shards_1d_field_state():
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.sharding.specs import ctr_param_spec

    try:
        mesh = AbstractMesh((2, 4), ("data", "model"))
    except TypeError:
        mesh = AbstractMesh((("data", 2), ("model", 4)))
    assert ctr_param_spec("last_step/fm/field_0", (60,), mesh) == P("model")
    # indivisible rows fall back to replicated, like the 2-D rule
    assert ctr_param_spec("last_step/fm/field_0", (57,), mesh) == P(None)


def test_cli_sparse_alias_and_conflict():
    warnings = []
    assert resolve_placement(None, True, warn=warnings.append) == "sparse"
    assert any("deprecated" in w for w in warnings)
    assert resolve_placement("sparse", True, warn=warnings.append) == "sparse"
    assert resolve_placement("sharded_sparse", False) == "sharded_sparse"
    assert resolve_placement(None, False) is None
    with pytest.raises(SystemExit, match="deprecated alias"):
        resolve_placement("sharded", True)


# ---------------------------------------------------------------------------
# multi-device exactness matrix (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------


CASES = ["hybrid_2x4_div", "hybrid_8x1_div", "hybrid_2x4_mod",
         "hybrid_2x4_one_shard", "hybrid_2x4_overflow"]


@pytest.fixture(scope="module")
def hybrid_records():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)   # the driver sets its own 8-device flag
    script = os.path.join(REPO, "tests", "sharded_exactness_main.py")
    proc = subprocess.run([sys.executable, script] + CASES, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = [json.loads(line) for line in proc.stdout.strip().splitlines()
            if line.startswith("{")]
    return {r["name"]: r for r in recs}


@pytest.mark.parametrize("case", CASES)
def test_hybrid_matches_dense_multi_device(hybrid_records, case):
    """Acceptance criterion: sharded_sparse on an 8-virtual-device mesh
    matches the single-device dense path (params and AUC) to f32 tolerance,
    covering 2x4 and 8x1 meshes, uneven vocab-per-shard remainders (57 over
    4), mod round-robin partitioning, one-shard batches, and a mid-run
    capacity-overflow step taking the dense fallback."""
    rec = hybrid_records[case]
    assert rec["embed_err"] <= 1e-5, rec
    assert rec["dense_err"] <= 1e-5, rec
    assert rec["loss_err"] <= 1e-5, rec
    assert abs(rec["auc_dense"] - rec["auc_sharded"]) <= 1e-3, rec
    if case == "hybrid_2x4_overflow":
        assert rec["overflow_steps"] >= 1, rec
    else:
        assert rec["overflow_steps"] == 0, rec


def test_hybrid_2x2_matches_sparse_in_float64():
    """sharded_sparse on a (2, 2) mesh and sparse on one device, at
    deepfm-criteo widths (fields cut to 20,000 ids, field 20 at an odd
    100,003 so one shard carries a pad row), run in float64 through
    ``run_ctr``: after 8 steps every param agrees to float64 rounding. In
    float32 the "data" axis's other summation order is a last-bit
    difference that Adam can grow over the steps; at float64 the same order
    leaves nothing of the size of an Adam step, so a gap here is a fault in
    the mesh path, not rounding."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)   # the driver sets its own 4-device flag
    script = os.path.join(REPO, "tests", "mesh_f64_main.py")
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads([line for line in proc.stdout.splitlines()
                      if line.startswith("{")][-1])
    assert rec["param_dtypes"] == ["float64"], rec
    assert rec["max_abs_err"] <= 1e-12, rec
    assert rec["max_loss_gap"] <= 1e-12, rec
