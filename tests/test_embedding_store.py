"""EmbeddingStore facade: placement routing, the TrainStepBundle contract
(prepare/init/step/flush), and flush idempotence — ``train_ctr`` calls
``flush`` both before the last eval and again after the loop, so the second
call must be a bitwise no-op on params and optimizer state."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TRAIN_PATHS, build_train_step, scale_hyperparams
from repro.core.builders import TrainStepBundle, identity_prepare
from repro.data.synthetic import make_ctr_dataset
from repro.embed import EmbeddingStore, store_for
from repro.models import ctr
from repro.train import train_ctr

VOCABS = (60, 13, 5)


def _cfg(**kw):
    return ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                         emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                         **kw)


def _hp():
    return scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                             base_batch=64, batch_size=64,
                             base_dense_lr=2e-3)


def _assert_trees_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def test_store_resolution_order():
    assert store_for(_cfg()).placement == "dense"
    assert store_for(_cfg(sparse=True)).placement == "sparse"
    assert store_for(_cfg(placement="sharded")).placement == "sharded"
    # explicit path beats the config knobs
    assert store_for(_cfg(sparse=True), path="substrate").placement == "dense"
    # fused entry point with the sparse knob set carries the sparse flush
    assert store_for(_cfg(sparse=True), path="fused").placement == "sparse"
    assert store_for(_cfg(), path="fused").kernel == "fused"


def test_unknown_path_and_placement_rejected():
    with pytest.raises(ValueError, match="unknown path"):
        store_for(_cfg(), path="magnetic_tape")
    with pytest.raises(ValueError, match="unknown path"):
        build_train_step(_cfg(placement="nope"), _hp())
    with pytest.raises(ValueError, match="unknown placement"):
        EmbeddingStore(placement="magnetic_tape")
    assert "sharded" in TRAIN_PATHS


def test_use_kernel_accepts_false_only():
    """``build_train_step`` keeps ``use_kernel`` for callers that still pass
    it: ``True`` is refused, and ``False`` builds the same step as leaving
    it out."""
    cfg = _cfg(sparse=True)
    with pytest.raises(ValueError, match="Pallas CowClip row kernels"):
        build_train_step(cfg, _hp(), use_kernel=True)
    params = jax.eval_shape(lambda: ctr.init(jax.random.key(0), cfg))
    batch = {"ids": jax.ShapeDtypeStruct((32, len(VOCABS)), jnp.int32),
             "dense": jax.ShapeDtypeStruct((32, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((32,), jnp.float32)}
    texts = []
    for kw in ({}, {"use_kernel": False}):
        bundle = build_train_step(cfg, _hp(), **kw)
        state = jax.eval_shape(bundle.init, params)
        texts.append(jax.jit(bundle.scan_step).lower(
            params, state, batch).as_text())
    assert texts[0] == texts[1]


def test_sparse_placement_rejects_ablation_clips():
    with pytest.raises(ValueError, match="substrate-only"):
        build_train_step(_cfg(sparse=True), _hp(), clip_kind="global")
    with pytest.raises(ValueError, match="substrate-only"):
        build_train_step(_cfg(), _hp(), path="sharded",
                         mesh=jax.make_mesh((1, 1), ("data", "model")),
                         clip_kind="global")


def test_describe_names_the_placement():
    assert EmbeddingStore().describe() == "dense(substrate)"
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    d = EmbeddingStore(placement="sharded", mesh=mesh).describe()
    assert "model=1" in d and "div" in d


# ---------------------------------------------------------------------------
# bundle contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["substrate", "fused", "sparse"])
def test_non_sharded_bundles_prepare_is_identity(path):
    bundle = build_train_step(_cfg(sparse=path == "sparse"), _hp(), path=path)
    assert isinstance(bundle, TrainStepBundle)
    assert bundle.prepare is identity_prepare
    params = ctr.init(jax.random.key(0), _cfg())
    assert bundle.prepare(params) is params


def test_flush_idempotent_after_train_ctr_sparse():
    """train_ctr flushes before the last eval and again after the loop; the
    second flush must be a no-op. Assert it on the returned final state: one
    more flush leaves params and opt state bitwise unchanged."""
    cfg = _cfg(sparse=True)
    ds = make_ctr_dataset(2000, VOCABS, n_dense=3, zipf_a=1.2, seed=0)
    tr, te = ds.split(0.9)
    bundle = build_train_step(cfg, _hp())
    res = train_ctr(cfg, None, tr, te, batch_size=128, epochs=1, seed=0,
                    step_bundle=bundle)
    assert res.params is not None and res.opt_state is not None
    p2, s2 = bundle.flush(res.params, res.opt_state)
    _assert_trees_identical(res.params, p2)
    _assert_trees_identical(res.opt_state, s2)
    # the deferral bookkeeping agrees: every row is caught up to the final
    # step, so there is nothing left to replay
    for ls in jax.tree.leaves(res.opt_state["last_step"]):
        assert (np.asarray(ls) == int(res.opt_state["step"])).all()


@pytest.mark.parametrize("path", ["substrate", "sharded"])
def test_flush_identity_for_eager_paths(path):
    cfg = _cfg()
    mesh = (jax.make_mesh((1, 1), ("data", "model"))
            if path == "sharded" else None)
    bundle = build_train_step(cfg, _hp(), path=path, mesh=mesh)
    params = bundle.prepare(ctr.init(jax.random.key(0), cfg))
    state = bundle.init(params)
    p2, s2 = bundle.flush(params, state)
    _assert_trees_identical(params, p2)
    _assert_trees_identical(state, s2)


def test_train_ctr_returns_final_params():
    cfg = _cfg()
    ds = make_ctr_dataset(1500, VOCABS, n_dense=3, zipf_a=1.2, seed=3)
    tr, te = ds.split(0.9)
    bundle = build_train_step(cfg, _hp(), path="substrate")
    res = train_ctr(cfg, None, tr, te, batch_size=128, epochs=1, seed=1,
                    step_bundle=bundle)
    assert res.params is not None
    # the returned params are the trained ones, not the init
    init_params = ctr.init(jax.random.key(1), cfg)
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(res.params), jax.tree.leaves(init_params))]
    assert max(diffs) > 0


def test_sharded_export_strips_padding_and_restores(tmp_path):
    """export is prepare's layout inverse: padded sharded params come back
    as canonical [vocab, dim] tables that checkpoint.restore accepts
    against a fresh ctr.init template (vocab 57 does not divide model=4,
    so prepare padded to 60)."""
    from repro.train import checkpoint

    if jax.device_count() >= 4:
        mesh = jax.make_mesh((1, 4), ("data", "model"))
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = dataclasses.replace(_cfg(), vocab_sizes=(57, 13, 5))
    bundle = build_train_step(cfg, _hp(), path="sharded", mesh=mesh)
    params0 = ctr.init(jax.random.key(0), cfg)
    prepared = bundle.prepare(jax.tree.map(jnp.copy, params0))
    if mesh.shape["model"] == 4:
        assert prepared["embed"]["fm"]["field_0"].shape == (60, 8)
    exported = bundle.export(prepared)
    _assert_trees_identical(exported, params0)

    path = str(tmp_path / "ck.npz")
    checkpoint.save(path, exported)
    restored = checkpoint.restore(path, ctr.init(jax.random.key(1), cfg))
    _assert_trees_identical(restored, params0)

    # non-sharded bundles export as identity
    dense_bundle = build_train_step(cfg, _hp(), path="substrate")
    assert dense_bundle.export(params0) is params0


def test_train_ctr_through_sharded_sparse_bundle_1x1():
    """End-to-end epoch driver through the hybrid placement on the host
    mesh: train_ctr's pre-eval flush settles the lazy decay, the returned
    state is fully caught up, and one more flush is a bitwise no-op."""
    cfg = _cfg(placement="sharded_sparse")
    ds = make_ctr_dataset(1500, VOCABS, n_dense=3, zipf_a=1.2, seed=6)
    tr, te = ds.split(0.9)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, _hp(), mesh=mesh)
    res = train_ctr(cfg, None, tr, te, batch_size=128, epochs=1, seed=2,
                    step_bundle=bundle)
    assert np.isfinite(res.final_eval["logloss"])
    assert 0.0 <= res.final_eval["auc"] <= 1.0
    p2, s2 = bundle.flush(res.params, res.opt_state)
    _assert_trees_identical(res.params, p2)
    _assert_trees_identical(res.opt_state, s2)
    for ls in jax.tree.leaves(res.opt_state["last_step"]):
        assert (np.asarray(ls) == int(res.opt_state["step"])).all()


def test_train_ctr_through_sharded_bundle_1x1():
    """End-to-end epoch driver through the sharded placement on the host
    mesh: prepare runs once, eval sees padded tables, metrics are sane."""
    cfg = _cfg(placement="sharded")
    ds = make_ctr_dataset(1500, VOCABS, n_dense=3, zipf_a=1.2, seed=5)
    tr, te = ds.split(0.9)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, _hp(), mesh=mesh)
    res = train_ctr(cfg, None, tr, te, batch_size=128, epochs=1, seed=2,
                    step_bundle=bundle)
    assert np.isfinite(res.final_eval["logloss"])
    assert 0.0 <= res.final_eval["auc"] <= 1.0
    assert res.steps == len(tr) // 128
