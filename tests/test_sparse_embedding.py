"""Sparse unique-id embedding update path: dense-vs-sparse exactness,
lazy-L2-decay catch-up, capacity overflow, and the row functions' pad
slots.

The contract under test: a sparse train step (unique -> gather -> lazy-decay
catch-up -> forward on rows -> CowClip -> L2 -> Adam -> scatter) followed by
a ``flush`` of all pending decay must land bitwise-close (f32) to the dense
substrate optimizer chain, for batches with heavy duplicate ids and for ids
absent over many consecutive steps.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_optimizer, build_train_step, scale_hyperparams
from repro.core import optim as optim_lib
from repro.kernels.cowclip import (
    ref as cc_ref,
    sparse_gather_catchup,
    sparse_update_scatter,
)
from repro.models import ctr, embedding
from repro.train.loop import make_sparse_train_step, make_train_step

VOCABS = (60, 13, 5)


def _cfg(**kw):
    return ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                         emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                         **kw)


def _hp(l2=1e-3):
    return scale_hyperparams("cowclip", base_lr=1e-3, base_l2=l2,
                             base_batch=64, batch_size=64,
                             base_dense_lr=2e-3)


def _dup_heavy_batches(n_steps, batch=32, seed=0):
    """Batches where field 0 cycles a handful of ids (most of its vocab-60
    absent for many steps) and field 2 repeats 2 of 5 ids heavily."""
    rng = np.random.default_rng(seed)
    for _ in range(n_steps):
        ids = np.stack([
            rng.choice([1, 2, 3, 50, 51], size=batch),
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


# ---------------------------------------------------------------------------
# unique-id layer
# ---------------------------------------------------------------------------


def test_unique_ids_slots_counts_and_pads():
    ids = jnp.array([7, 3, 7, 7, 1, 3])
    u = embedding.unique_ids(ids, vocab=10, capacity=6)
    np.testing.assert_array_equal(np.asarray(u.uids), [1, 3, 7, 10, 10, 10])
    np.testing.assert_array_equal(np.asarray(u.counts), [1, 2, 3, 0, 0, 0])
    assert int(u.n_unique()) == 3
    # inverse reconstructs the batch
    np.testing.assert_array_equal(np.asarray(u.uids)[np.asarray(u.inv)],
                                  np.asarray(ids))


def _zipf_col(rng, batch, vocab):
    return np.minimum(rng.zipf(1.1, size=batch) - 1, vocab - 1)


# (column kind, batch, vocab, capacity)
DEDUP_CASES = {
    "zipf_heavy_dups": ("zipf", 4096, 100_000, 4096),
    "all_distinct": ("distinct", 512, 100_000, 512),
    "all_equal": ("equal", 512, 100_000, 512),
    "overflow": ("zipf", 4096, 100_000, 37),
    "overflow_all_distinct": ("distinct", 512, 100_000, 100),
    "capacity_is_vocab_below_batch": ("zipf", 2048, 300, 300),
    "capacity_above_batch": ("zipf", 64, 100_000, 200),
    "batch_of_one": ("zipf", 1, 10, 1),
    "batch_of_one_wide": ("zipf", 1, 10, 4),
}


@pytest.mark.parametrize("case", sorted(DEDUP_CASES))
def test_unique_ids_equals_jnp_and_np_unique(case):
    """The sorts-only dedup gives ``jnp.unique(size=, fill_value=,
    return_inverse=True, return_counts=True)``'s uids, inverse and counts
    bit for bit, and ``np.unique``'s: sorted ids, ``vocab`` pads, each
    element's rank among the distinct ids (``>= capacity`` when its id was
    dropped on overflow) and the kept ids' occurrence counts."""
    kind, batch, vocab, cap = DEDUP_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    col = {"zipf": lambda: _zipf_col(rng, batch, vocab),
           "distinct": lambda: rng.permutation(vocab)[:batch],
           "equal": lambda: np.full(batch, rng.integers(vocab))}[kind]()
    col = col.astype(np.int32)
    u = jax.jit(embedding.unique_ids, static_argnums=(1, 2))(
        jnp.asarray(col), vocab, cap)

    ju, jinv, jcnt = jnp.unique(jnp.asarray(col), size=cap, fill_value=vocab,
                                return_inverse=True, return_counts=True)
    np.testing.assert_array_equal(np.asarray(u.uids), np.asarray(ju))
    np.testing.assert_array_equal(np.asarray(u.inv),
                                  np.asarray(jinv).reshape(-1))
    np.testing.assert_array_equal(np.asarray(u.counts),
                                  np.asarray(jcnt).astype(np.float32))

    nu, ninv, ncnt = np.unique(col, return_inverse=True, return_counts=True)
    keep = min(cap, len(nu))
    want_uids = np.full(cap, vocab, np.int32)
    want_uids[:keep] = nu[:keep]
    want_counts = np.zeros(cap, np.float32)
    want_counts[:keep] = ncnt[:keep]
    np.testing.assert_array_equal(np.asarray(u.uids), want_uids)
    np.testing.assert_array_equal(np.asarray(u.inv), ninv.reshape(-1))
    np.testing.assert_array_equal(np.asarray(u.counts), want_counts)
    assert u.uids.dtype == u.inv.dtype == jnp.int32
    assert u.counts.dtype == jnp.float32
    assert u.uids.shape == (cap,) and u.inv.shape == (batch,)
    if len(nu) > cap:        # overflow: the smallest ids kept, the rest out
        dropped = col > nu[cap - 1]
        assert dropped.any()
        assert (np.asarray(u.inv)[dropped] >= cap).all()
        assert (np.asarray(u.inv)[~dropped] < cap).all()


def test_field_counts_match_dense_segment_sum():
    rng = np.random.default_rng(3)
    ids = np.stack([rng.integers(0, v, size=128) for v in VOCABS], axis=1)
    counts = embedding.field_counts(jnp.asarray(ids), VOCABS)
    for i, v in enumerate(VOCABS):
        dense = np.bincount(ids[:, i], minlength=v).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(counts[f"field_{i}"]), dense)


def test_sparse_forward_equals_dense_forward():
    cfg = _cfg()
    params = ctr.init(jax.random.key(0), cfg)
    batch = next(_dup_heavy_batches(1))
    dense_logits = ctr.apply(params, cfg, batch["ids"], batch["dense"])
    uniq = ctr.unique_batch(cfg, batch["ids"])
    rows = ctr.gather_embed_rows(params, uniq)
    sparse_logits = ctr.apply_rows(rows, params["dense"], cfg, uniq,
                                   batch["dense"])
    np.testing.assert_allclose(np.asarray(sparse_logits),
                               np.asarray(dense_logits), atol=1e-6)


# ---------------------------------------------------------------------------
# dense-vs-sparse train step equivalence (acceptance criterion)
# ---------------------------------------------------------------------------


def test_sparse_step_matches_dense_substrate_10_steps():
    """>= 10 steps with duplicate-heavy batches and long-absent ids: flushed
    sparse params must be bitwise-close (atol 1e-5 f32) to the dense path."""
    n_steps, batch = 12, 32
    cfg_d = _cfg()
    cfg_s = dataclasses.replace(cfg_d, sparse=True)
    hp = _hp()

    params = ctr.init(jax.random.key(0), cfg_d)
    tx = build_optimizer(hp, warmup_steps=0)
    dstate = tx.init(params)
    dstep = make_train_step(cfg_d, tx)
    sstep, sinit, sflush = make_sparse_train_step(cfg_s, hp)
    dparams = jax.tree.map(jnp.copy, params)
    sparams = jax.tree.map(jnp.copy, params)
    sstate = sinit(sparams)

    for b in _dup_heavy_batches(n_steps, batch=batch, seed=1):
        dparams, dstate, da = dstep(dparams, dstate, dict(b))
        sparams, sstate, sa = sstep(sparams, sstate, dict(b))
        assert float(da["loss"]) == pytest.approx(float(sa["loss"]), rel=1e-5)

    sparams, sstate = sflush(sparams, sstate)
    assert _max_err(dparams, sparams) <= 1e-5


def test_sparse_forward_substrate_step_matches_dense():
    """cfg.sparse routes make_train_step's forward through the gather layer;
    the composable-optimizer update must be unaffected by the rerouting."""
    cfg_d = _cfg()
    cfg_s = dataclasses.replace(cfg_d, sparse=True)
    hp = _hp()
    params = ctr.init(jax.random.key(2), cfg_d)
    tx = build_optimizer(hp, warmup_steps=0)

    d_params = jax.tree.map(jnp.copy, params)
    s_params = jax.tree.map(jnp.copy, params)
    d_state, s_state = tx.init(params), tx.init(params)
    d_step, s_step = make_train_step(cfg_d, tx), make_train_step(cfg_s, tx)
    for b in _dup_heavy_batches(3, seed=5):
        d_params, d_state, _ = d_step(d_params, d_state, dict(b))
        s_params, s_state, _ = s_step(s_params, s_state, dict(b))
    assert _max_err(d_params, s_params) <= 1e-5


# ---------------------------------------------------------------------------
# lazy L2 decay
# ---------------------------------------------------------------------------


def test_absent_id_lazy_decay_exact_after_k_skipped_steps():
    """An id absent for k steps must, on its next touch, catch up exactly
    the k decay-only Adam iterations the dense path applied one-by-one."""
    vocab, dim, k = 12, 8, 7
    key = jax.random.key(0)
    w = 0.05 * jax.random.normal(key, (vocab, dim))
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    kw = dict(r=1.0, zeta=1e-5, lr=1e-3, l2=1e-2)

    # dense: id 5 gets a gradient at step 1, then zero gradient for k steps
    g1 = jnp.zeros((vocab, dim)).at[5].set(0.3)
    cnt1 = jnp.zeros(vocab).at[5].set(2.0)
    dw, dm, dv = cc_ref.cowclip_adam_reference(
        w, g1, cnt1, m, v, jnp.asarray(1, jnp.int32), **kw)
    for t in range(2, 2 + k):
        dw, dm, dv = cc_ref.cowclip_adam_reference(
            dw, jnp.zeros_like(w), jnp.zeros(vocab), dm, dv,
            jnp.asarray(t, jnp.int32), **kw)

    # sparse: same step 1, then nothing — id 5 never touched again
    ls = jnp.zeros(vocab, jnp.int32)
    cap = 4
    uids, cnt = jnp.unique(jnp.array([5, 5]), size=cap, fill_value=vocab,
                           return_counts=True)
    uids = uids.astype(jnp.int32)
    cnt = cnt.astype(jnp.float32)
    wr, mr, vr = cc_ref.sparse_gather_catchup_reference(
        w, m, v, ls, uids, jnp.asarray(1, jnp.int32),
        lr=kw["lr"], l2=kw["l2"])
    g_rows = jnp.zeros((cap, dim)).at[0].set(0.3)
    sw, sm, sv, sls = cc_ref.sparse_update_scatter_reference(
        w, m, v, ls, uids, cnt, wr, g_rows, mr, vr,
        jnp.asarray(1, jnp.int32), **kw)
    # flush pending decay through step 1 + k for every row
    fw, fm, fv = optim_lib.decay_catchup_rows(
        sw, sm, sv, sls, jnp.asarray(1 + k, jnp.int32),
        lr=kw["lr"], l2=kw["l2"])

    np.testing.assert_allclose(np.asarray(fw), np.asarray(dw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(fm), np.asarray(dm), atol=1e-6)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(dv), atol=1e-6)


def test_lazy_path_exact_at_zero_l2():
    """At l2=0 the absent-row decay factor is exactly 1.0 — a once-touched
    row holds still (moments too) until its next gradient, so the lazy path
    must match the dense oracle with zero pending work to collapse."""
    cfg_d = _cfg()
    cfg_s = dataclasses.replace(cfg_d, sparse=True)
    hp = _hp(l2=0.0)
    assert hp.emb_l2 == 0.0

    params = ctr.init(jax.random.key(6), cfg_d)
    tx = build_optimizer(hp, warmup_steps=0)
    dstate = tx.init(params)
    dstep = make_train_step(cfg_d, tx)
    sstep, sinit, sflush = make_sparse_train_step(cfg_s, hp)
    dparams = jax.tree.map(jnp.copy, params)
    sparams = jax.tree.map(jnp.copy, params)
    sstate = sinit(sparams)

    for b in _dup_heavy_batches(8, seed=9):
        dparams, dstate, _ = dstep(dparams, dstate, dict(b))
        sparams, sstate, _ = sstep(sparams, sstate, dict(b))
    sparams, sstate = sflush(sparams, sstate)
    assert _max_err(dparams, sparams) <= 1e-5


def test_untouched_rows_not_written_until_flush():
    """The sparse step must leave absent ids' rows byte-identical (decay is
    deferred, not applied) and record the deferral in last_step."""
    cfg = _cfg(sparse=True)
    hp = _hp()
    params = ctr.init(jax.random.key(1), cfg)
    step, init, _ = make_sparse_train_step(cfg, hp)
    state = init(params)
    before = np.asarray(params["embed"]["fm"]["field_0"]).copy()

    b = next(_dup_heavy_batches(1, seed=2))   # field 0 only touches 5 ids
    params, state, _ = step(params, state, b)

    after = np.asarray(params["embed"]["fm"]["field_0"])
    ls = np.asarray(state["last_step"]["fm"]["field_0"])
    touched = np.unique(np.asarray(b["ids"])[:, 0])
    untouched = np.setdiff1d(np.arange(VOCABS[0]), touched)
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert (ls[touched] == 1).all()
    assert (ls[untouched] == 0).all()


def test_flush_passes_moments_through():
    """flush settles the tables and last_step and hands back the Adam
    moments as the same arrays: a copy of each moment table would double
    the embedding state on the device at Criteo widths."""
    cfg = _cfg(sparse=True)
    params = ctr.init(jax.random.key(1), cfg)
    step, init, flush = make_sparse_train_step(cfg, _hp())
    params, state, _ = step(params, init(params),
                            next(_dup_heavy_batches(1, seed=2)))
    _, flushed = flush(params, state)
    for key in ("m", "v"):
        for a, b in zip(jax.tree.leaves(flushed[key]),
                        jax.tree.leaves(state[key])):
            assert a is b
    for ls in jax.tree.leaves(flushed["last_step"]):
        assert (np.asarray(ls) == 1).all()


# ---------------------------------------------------------------------------
# capacity overflow
# ---------------------------------------------------------------------------


def test_unique_capacity_overflow_documented_behavior():
    """capacity < n_unique: the capacity smallest ids are kept; dropped ids
    alias the last kept slot in the forward and receive no update; training
    stays finite."""
    cfg = _cfg(sparse=True, unique_capacity=3)  # field 0 sees 5 unique ids
    hp = _hp()
    params = ctr.init(jax.random.key(4), cfg)
    step, init, flush = make_sparse_train_step(cfg, hp)
    state = init(params)
    before = np.asarray(params["embed"]["fm"]["field_0"]).copy()

    b = next(_dup_heavy_batches(1, seed=3))   # field 0 ids: {1,2,3,50,51}
    params, state, aux = step(params, state, b)
    assert np.isfinite(float(aux["loss"]))
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(params))

    after = np.asarray(params["embed"]["fm"]["field_0"])
    ls = np.asarray(state["last_step"]["fm"]["field_0"])
    kept = [1, 2, 3]          # 3 smallest of the 5 unique ids
    dropped = [50, 51]
    assert (ls[kept] == 1).all()
    # dropped ids: no update, no last_step advance — decay stays pending
    np.testing.assert_array_equal(after[dropped], before[dropped])
    assert (ls[dropped] == 0).all()

    # overflow is detectable: kept occurrences < batch size
    uniq = ctr.unique_batch(cfg, b["ids"])
    assert float(uniq["field_0"].counts.sum()) < b["ids"].shape[0]

    params, state = flush(params, state)
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# row functions: pad slots
# ---------------------------------------------------------------------------


def test_sparse_kernel_pad_slots_write_nothing():
    """Pad slots (count 0) are dropped by the row update's scatter even when
    a pad uid minus the shard's row offset lands inside the shard."""
    rows, dim, off = 40, 8, 20          # shard of global rows 20..59
    ks = jax.random.split(jax.random.key(3), 5)
    w = 0.01 * jax.random.normal(ks[0], (rows, dim))
    m = 0.001 * jax.random.normal(ks[1], (rows, dim))
    v = 0.0001 * jnp.abs(jax.random.normal(ks[2], (rows, dim)))
    # two real slots, then pads carrying the global vocab sentinel 50,
    # which is local row 30 of this shard
    uids = jnp.array([22, 41, 50, 50], jnp.int32)
    cnt = jnp.array([2.0, 1.0, 0.0, 0.0])
    t = jnp.asarray(4, jnp.int32)
    kw = dict(lr=1e-3, l2=1e-4)
    ls = jnp.zeros((rows,), jnp.int32)
    w_rows, m_rows, v_rows = sparse_gather_catchup(
        w, m, v, ls, uids, t, row_offset=off, **kw)
    g_rows = 0.1 * jax.random.normal(ks[3], (4, dim))
    *new, new_ls = sparse_update_scatter(
        jnp.copy(w), jnp.copy(m), jnp.copy(v), jnp.copy(ls), uids, cnt,
        w_rows, g_rows, m_rows, v_rows, t, row_offset=off, **kw)
    written = np.array([2, 21])
    kept = np.setdiff1d(np.arange(rows), written)
    assert (np.asarray(new_ls)[written] == 4).all()
    assert (np.asarray(new_ls)[kept] == 0).all()
    for before, after in zip((w, m, v), new):
        np.testing.assert_array_equal(np.asarray(after)[kept],
                                      np.asarray(before)[kept])
        assert not np.array_equal(np.asarray(after)[written],
                                  np.asarray(before)[written])
