"""End-to-end integration: the system trains, CowClip behaves as the paper
describes, and the fused CowClip+L2+Adam update is interchangeable with the
optimizer substrate inside a real train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    apply_updates,
    build_optimizer,
    scale_hyperparams,
)
from repro.core.optim import ScaleByAdamState
from repro.data import make_ctr_dataset
from repro.kernels.cowclip import fused_cowclip_adam
from repro.models import ctr
from repro.train import train_ctr
from repro.train.loop import make_train_step

VOCABS = (300, 1000, 50)


@pytest.fixture(scope="module")
def dataset():
    return make_ctr_dataset(24_000, VOCABS, n_dense=4, zipf_a=1.15, seed=0)


def _cfg(name="deepfm"):
    return ctr.CTRConfig(name=name, vocab_sizes=VOCABS, n_dense=4, emb_dim=8,
                         mlp_dims=(32, 32, 32), emb_sigma=1e-2)


def test_training_learns_above_chance(dataset):
    tr, te = dataset.split(0.9)
    cfg = _cfg()
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-5,
                           base_batch=512, batch_size=512,
                           base_dense_lr=2e-3)
    tx = build_optimizer(hp, warmup_steps=10)
    res = train_ctr(cfg, tx, tr, te, batch_size=512, epochs=4, seed=0)
    assert res.final_eval["auc"] > 0.62, res.final_eval
    assert res.steps == 4 * (len(tr) // 512)


def test_cowclip_stabilizes_large_batch_high_lr(dataset):
    """At an aggressive LR, unclipped training diverges or stalls while
    CowClip keeps it finite and learning — Alg. 1's purpose."""
    tr, te = dataset.split(0.9)
    cfg = _cfg()

    def run(clip_kind):
        hp = scale_hyperparams("linear", base_lr=2e-2, base_l2=1e-5,
                               base_batch=4096, batch_size=4096)
        if clip_kind == "adaptive_column":
            hp = hp.replace(emb_lr=2e-2)
        tx = build_optimizer(hp, clip_kind=clip_kind)
        return train_ctr(cfg, tx, tr, te, batch_size=4096, epochs=3, seed=1)

    clipped = run("adaptive_column")
    unclipped = run("none")
    assert clipped.final_eval["auc"] >= unclipped.final_eval["auc"] - 0.005
    assert np.isfinite(clipped.final_eval["logloss"])


def test_train_step_jit_donation(dataset):
    cfg = _cfg("dcn")
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-5,
                           base_batch=512, batch_size=512)
    tx = build_optimizer(hp)
    params = ctr.init(jax.random.key(0), cfg)
    state = tx.init(params)
    step = make_train_step(cfg, tx)
    from repro.data import iterate_batches

    b = next(iterate_batches(dataset, 512))
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    params, state, aux = step(params, state, batch)
    assert np.isfinite(float(aux["loss"]))


def test_fused_update_equals_substrate_step():
    """One optimizer step on an embedding table via (a) the composable
    transform chain and (b) the fused update must agree."""
    vocab, dim, batch = 200, 8, 64
    key = jax.random.key(0)
    table = 0.01 * jax.random.normal(key, (vocab, dim))
    params = {"embed": {"t": table}, "dense": {"w": jnp.ones((2, 2))}}

    hp = scale_hyperparams("cowclip", base_lr=1e-4, base_l2=1e-4,
                           base_batch=1024, batch_size=1024)
    tx = build_optimizer(hp, zeta=1e-5, warmup_steps=0)
    state = tx.init(params)

    ids = jax.random.randint(jax.random.key(1), (batch,), 0, vocab)
    g_table = jnp.zeros((vocab, dim)).at[ids].add(
        0.1 * jax.random.normal(jax.random.key(2), (batch, dim)))
    counts = {"t": jnp.zeros(vocab).at[ids].add(1.0)}
    grads = {"embed": {"t": g_table}, "dense": {"w": jnp.zeros((2, 2))}}

    updates, _ = tx.update(grads, state, params, counts=counts)
    via_substrate = apply_updates(params, updates)["embed"]["t"]

    w_new, m_new, v_new = fused_cowclip_adam(
        table, g_table, counts["t"], jnp.zeros_like(table),
        jnp.zeros_like(table), jnp.asarray(1, jnp.int32),
        r=1.0, zeta=1e-5, lr=hp.emb_lr, l2=hp.emb_l2,
    )
    np.testing.assert_allclose(np.asarray(w_new), np.asarray(via_substrate),
                               rtol=1e-5, atol=1e-8)

    # and the fused update's moments match the substrate's Adam state
    emb_state = updates  # recompute state from tx for comparison
    _, new_state = tx.update(grads, state, params, counts=counts)
    adam_state = [s for s in jax.tree.leaves(new_state[0],
                                             is_leaf=lambda x: isinstance(x, ScaleByAdamState))]
    # structural check only: moments finite and nonzero where ids hit
    hit = np.unique(np.asarray(ids))
    assert np.abs(np.asarray(m_new)[hit]).max() > 0
    assert np.isfinite(np.asarray(v_new)).all()


def test_fused_train_step_matches_substrate(dataset):
    """A full DeepFM train step through make_fused_train_step matches the
    composable-optimizer step."""
    from repro.data import iterate_batches
    from repro.train.loop import make_fused_train_step

    cfg = _cfg()
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-4,
                           base_batch=512, batch_size=512)
    params = ctr.init(jax.random.key(5), cfg)

    # substrate path (no dense warmup so the dense chains match exactly)
    tx = build_optimizer(hp, clip_kind="adaptive_column", zeta=1e-5,
                         warmup_steps=0)
    state = tx.init(params)
    sub_step = make_train_step(cfg, tx)

    fused_step, fused_init = make_fused_train_step(cfg, hp, zeta=1e-5)
    fstate = fused_init(params)

    b = next(iterate_batches(dataset, 512, seed=9))
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    import copy
    p_sub, state, aux1 = sub_step(jax.tree.map(jnp.copy, params), state,
                                  dict(batch))
    p_fused, fstate, aux2 = fused_step(jax.tree.map(jnp.copy, params), fstate,
                                       dict(batch))
    assert float(aux1["loss"]) == pytest.approx(float(aux2["loss"]), rel=1e-6)
    for (path, a), (_, bb) in zip(
        jax.tree_util.tree_flatten_with_path(p_sub["embed"])[0],
        jax.tree_util.tree_flatten_with_path(p_fused["embed"])[0],
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-5,
                                   atol=1e-8, err_msg=str(path))


def test_scaling_rule_failure_direction(dataset):
    """Directional mini-repro of paper Tables 2/4 at 16x batch from a
    converged base LR: linear scaling (16x the LR) destabilizes training
    (much worse logloss) while the CowClip rule stays close to the
    small-batch baseline. Full-scale repro lives in benchmarks + EXPERIMENTS
    §Repro (measured there: linear diverges to logloss 3.78 at 64x while
    CowClip holds AUC above the baseline)."""
    tr, te = dataset.split(0.9)
    cfg = _cfg()

    def run(rule, clip_kind, batch, epochs=4):
        hp = scale_hyperparams(rule, base_lr=2e-2, base_l2=1e-5,
                               base_batch=512, batch_size=batch,
                               base_dense_lr=4e-2)
        tx = build_optimizer(hp, clip_kind=clip_kind,
                             warmup_steps=max(1, len(tr) // batch))
        return train_ctr(cfg, tx, tr, te, batch_size=batch, epochs=epochs,
                         seed=2).final_eval

    small = run("no_scale", "none", 512)
    big_linear = run("linear", "none", 8192)       # LR 0.32: unstable
    big_cowclip = run("cowclip", "adaptive_column", 8192)
    assert big_cowclip["logloss"] < big_linear["logloss"], (
        small, big_linear, big_cowclip)
    assert big_cowclip["auc"] > big_linear["auc"] - 0.01
