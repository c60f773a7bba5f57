"""Checkpoint interchange across every embedding placement pair.

A checkpoint written from any placement must be loadable by any other:
``flush`` settles pending lazy decay, ``export`` inverts the placement's
layout (shard padding, device sharding) back to canonical ``[vocab, dim]``
tables, and ``prepare`` lays the canonical tree out for the next
placement. This suite trains a few steps under each *source* placement —
far enough that the lazy placements carry non-zero pending-decay depth
before their flush — round-trips the export through an actual ``.npz``
checkpoint file, continues training under each *target* placement, and
asserts that every target agrees with the dense-substrate continuation of
the same checkpoint to 1e-5 in both params and held-out AUC.

The full matrix is PATHS x PATHS = 36 pairs; source trainings, bundles,
and continuations are memoised so each placement trains once per role.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_train_step, scale_hyperparams
from repro.data.synthetic import make_ctr_dataset, iterate_batches
from repro.embed.store import max_pending_depth
from repro.models import ctr
from repro.train import checkpoint, make_eval_fn

PATHS = ["substrate", "fused", "sparse", "sharded", "sharded_sparse",
         "hotcold"]
LAZY = {"sparse", "sharded_sparse", "hotcold"}
SHARDED = {"sharded", "sharded_sparse"}
BATCH = 32
STEPS = 3


def _cfg():
    return ctr.CTRConfig(name="deepfm", vocab_sizes=(60, 13, 5), n_dense=3,
                         emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2)


def _hp():
    return scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                             base_batch=BATCH, batch_size=BATCH,
                             base_dense_lr=2e-3)


@functools.lru_cache(maxsize=1)
def _data():
    ds = make_ctr_dataset(512, (60, 13, 5), n_dense=3, zipf_a=1.2, seed=4)
    tr, te = ds.split(0.8)
    batches = []
    for b in iterate_batches(tr, BATCH, seed=2):
        batches.append({k: jnp.asarray(v) for k, v in b.items()})
        if len(batches) >= 2 * STEPS:
            break
    return batches[:STEPS], batches[STEPS:], te


@functools.lru_cache(maxsize=1)
def _eval_fn():
    return make_eval_fn(_cfg())


@functools.lru_cache(maxsize=None)
def _bundle(path):
    mesh = (jax.make_mesh((1, 1), ("data", "model"))
            if path in SHARDED else None)
    return build_train_step(_cfg(), _hp(), path=path, mesh=mesh,
                            hot_capacity=8)


@functools.lru_cache(maxsize=None)
def _source_checkpoint(path, tmp_dir):
    """Train STEPS steps under ``path``, flush, export, and round-trip the
    canonical params through an .npz checkpoint file."""
    bundle = _bundle(path)
    source_batches, _, _ = _data()
    params = bundle.prepare(ctr.init(jax.random.key(0), _cfg()))
    state = bundle.init(params)
    for b in source_batches:
        params, state, _ = bundle.step(params, state, b)
    if path in LAZY:
        # the checkpoint must settle real pending decay, not a no-op
        assert max_pending_depth(state) > 0, path
    params, state = bundle.flush(params, state)
    canonical = bundle.export(params)

    ck = f"{tmp_dir}/{path}.npz"
    checkpoint.save(ck, canonical)
    restored = checkpoint.restore(ck, ctr.init(jax.random.key(9), _cfg()))
    for a, b in zip(jax.tree.leaves(canonical), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return restored


@functools.lru_cache(maxsize=None)
def _continue_from(source, target, tmp_dir):
    """Load the source checkpoint under ``target``, train STEPS more steps,
    flush + export, and evaluate held-out AUC."""
    bundle = _bundle(target)
    _, cont_batches, te = _data()
    restored = _source_checkpoint(source, tmp_dir)
    params = bundle.prepare(jax.tree.map(jnp.copy, restored))
    state = bundle.init(params)
    for b in cont_batches:
        params, state, _ = bundle.step(params, state, b)
    params, state = bundle.flush(params, state)
    exported = bundle.export(params)
    auc = _eval_fn()(exported, te)["auc"]
    leaves = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
              jax.tree_util.tree_leaves_with_path(exported)}
    return leaves, float(auc)


@pytest.fixture(scope="module")
def ck_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("interchange"))


@pytest.mark.parametrize("target", PATHS)
@pytest.mark.parametrize("source", PATHS)
def test_interchange(source, target, ck_dir):
    """Checkpoint from ``source``, continue under ``target``: params and
    subsequent AUC match the dense-substrate continuation of the same
    checkpoint to 1e-5."""
    leaves, auc = _continue_from(source, target, ck_dir)
    ref_leaves, ref_auc = _continue_from(source, "substrate", ck_dir)
    assert leaves.keys() == ref_leaves.keys()
    for k in leaves:
        np.testing.assert_allclose(leaves[k], ref_leaves[k],
                                   atol=1e-5, rtol=0, err_msg=k)
    assert abs(auc - ref_auc) <= 1e-5, (source, target, auc, ref_auc)


def test_source_checkpoints_agree_across_placements(ck_dir):
    """Before any continuation: the flushed + exported checkpoints of all
    six placements describe the same model to 1e-5."""
    ref = _source_checkpoint("substrate", ck_dir)
    ref_leaves = jax.tree.leaves(ref)
    for path in PATHS[1:]:
        got = jax.tree.leaves(_source_checkpoint(path, ck_dir))
        for a, b in zip(got, ref_leaves):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=0, err_msg=path)
