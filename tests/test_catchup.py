"""Closed-form lazy-decay catch-up: property tests against the iterative
replay oracle, the schedule fallback, the row gather with a shard row
offset, and the depth-10_000 first-touch regression.

The contract: ``core.optim.decay_catchup_rows`` collapses k pending
decay-only steps into one multiply ``w *= (1 - lr*l2)**k`` (O(1) in k), and
must match the one-multiply-per-step replay (``decay_replay_reference``)
within f32 tolerance at any depth — including depth 10_000, where the old
``fori_loop`` replay this replaced would run 10_000 iterations. Weights are
drawn at the framework's embedding init scale (``emb_sigma = 1e-2``): the
replay oracle itself accumulates ~1 ulp of rounding bias per multiply, so
the absolute gap at depth 10_000 is only meaningful at realistic
magnitudes.
"""

import jax
import jax.numpy as jnp
import numpy as np

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    from hypcompat import hypothesis, st

from repro.core import optim as optim_lib
from repro.kernels.cowclip import ref as cc_ref
from repro.kernels.cowclip import sparse_gather_catchup


def _rows(rng, n, dim, scale=1e-2):
    """Embedding-scale rows, bounded so the replay oracle's per-multiply
    rounding drift (~depth * ulp/2, relative) stays under the 1e-5
    absolute tolerance at depth 10_000."""
    return jnp.asarray(
        rng.uniform(-1.5 * scale, 1.5 * scale, size=(n, dim))
        .astype(np.float32))


# ---------------------------------------------------------------------------
# closed form vs iterative replay
# ---------------------------------------------------------------------------


@hypothesis.settings(max_examples=30, deadline=None)
@hypothesis.given(
    depth=st.integers(0, 10_000),
    lr=st.floats(1e-5, 1e-1),
    l2=st.floats(0.0, 1e-1),
    dim=st.sampled_from([1, 4, 10]),
    seed=st.integers(0, 2**16),
)
def test_closed_form_matches_replay(depth, lr, l2, dim, seed):
    rng = np.random.default_rng(seed)
    n = 12
    w = _rows(rng, n, dim)
    m = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    v = jnp.asarray(np.abs(rng.normal(size=(n, dim))).astype(np.float32))
    # mixed pending depths per row, max == depth
    ls = jnp.asarray(
        rng.integers(0, depth + 1, size=n).astype(np.int32)).at[0].set(0)
    step = jnp.asarray(depth, jnp.int32)

    w_cf, m_cf, v_cf = optim_lib.decay_catchup_rows(
        w, m, v, ls, step, lr=lr, l2=l2)
    w_rp = optim_lib.decay_replay_reference(w, ls, step, lr=lr, l2=l2)

    np.testing.assert_allclose(np.asarray(w_cf), np.asarray(w_rp),
                               atol=1e-5, rtol=0)
    # decay-only steps never move the Adam moments
    np.testing.assert_array_equal(np.asarray(m_cf), np.asarray(m))
    np.testing.assert_array_equal(np.asarray(v_cf), np.asarray(v))


def test_closed_form_matches_float64_geometric_at_depth_10000():
    """Against a float64 ground truth (same f32-rounded factor, exact pow)
    the closed form is tighter than the replay it replaced — the replay
    accumulates one rounding per multiply, pow does not."""
    rng = np.random.default_rng(3)
    lr, l2 = 1e-3, 1e-4
    w = _rows(rng, 16, 8)
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    ls = jnp.zeros((16,), jnp.int32)
    step = jnp.asarray(10_000, jnp.int32)

    w_cf, _, _ = optim_lib.decay_catchup_rows(w, m, v, ls, step, lr=lr, l2=l2)
    factor64 = float(optim_lib.decay_factor(lr, l2))
    truth = np.asarray(w, np.float64) * factor64**10_000
    np.testing.assert_allclose(np.asarray(w_cf), truth, atol=1e-7, rtol=1e-5)


def test_zero_depth_and_zero_l2_are_exact_noops():
    rng = np.random.default_rng(7)
    w = _rows(rng, 8, 4)
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    step = jnp.asarray(5000, jnp.int32)
    # k == 0: multiply by exactly 1.0 — bit-identical passthrough
    caught, _, _ = optim_lib.decay_catchup_rows(
        w, m, v, jnp.full((8,), 5000, jnp.int32), step, lr=1e-3, l2=1e-4)
    np.testing.assert_array_equal(np.asarray(caught), np.asarray(w))
    # l2 == 0: factor is exactly 1.0 at any depth
    caught, _, _ = optim_lib.decay_catchup_rows(
        w, m, v, jnp.zeros((8,), jnp.int32), step, lr=1e-3, l2=0.0)
    np.testing.assert_array_equal(np.asarray(caught), np.asarray(w))


# ---------------------------------------------------------------------------
# scheduled (callable) lr/l2: the capped-replay fallback
# ---------------------------------------------------------------------------


def test_catchup_mode_detection():
    assert optim_lib.catchup_mode(1e-3, 1e-4) == "closed_form"
    assert optim_lib.catchup_mode(lambda s: 1e-3, 1e-4) == "replay_window"
    assert optim_lib.catchup_mode(1e-3, lambda s: 1e-4) == "replay_window"


@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(depth=st.integers(0, 60), seed=st.integers(0, 2**16))
def test_varying_schedule_exact_within_window(depth, seed):
    """A genuinely varying lr schedule: the fallback replays pending steps
    exactly as long as depth <= replay_window."""
    rng = np.random.default_rng(seed)
    lr = lambda s: 1e-3 * (1.0 + 0.5 * jnp.sin(0.1 * s))   # noqa: E731
    l2 = 1e-2
    w = _rows(rng, 10, 6)
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    ls = jnp.asarray(rng.integers(0, depth + 1, size=10).astype(np.int32))
    step = jnp.asarray(depth, jnp.int32)
    w_cf, _, _ = optim_lib.decay_catchup_rows(
        w, m, v, ls, step, lr=lr, l2=l2, replay_window=64)
    w_rp = optim_lib.decay_replay_reference(w, ls, step, lr=lr, l2=l2)
    np.testing.assert_allclose(np.asarray(w_cf), np.asarray(w_rp),
                               atol=1e-6, rtol=1e-5)


def test_constant_valued_schedule_exact_at_any_depth():
    """A callable that returns a constant takes the fallback path but its
    geometric tail is exact, so depth 10_000 still matches the replay."""
    rng = np.random.default_rng(11)
    lr = lambda s: jnp.full(jnp.shape(s), 1e-3, jnp.float32)  # noqa: E731
    w = _rows(rng, 12, 8)
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    ls = jnp.zeros((12,), jnp.int32)
    step = jnp.asarray(10_000, jnp.int32)
    w_cf, _, _ = optim_lib.decay_catchup_rows(
        w, m, v, ls, step, lr=lr, l2=1e-4, replay_window=64)
    w_rp = optim_lib.decay_replay_reference(w, ls, step, lr=lr, l2=1e-4)
    np.testing.assert_allclose(np.asarray(w_cf), np.asarray(w_rp),
                               atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# the row gather with a shard row offset
# ---------------------------------------------------------------------------


@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(
    depth=st.integers(1, 10_000),
    row_offset=st.sampled_from([0, 16, 48]),
    seed=st.integers(0, 2**16),
)
def test_kernel_catchup_matches_replay_with_row_offset(depth, row_offset,
                                                       seed):
    """``sparse_gather_catchup`` fed global uids against one row shard (the
    sharded_sparse calling convention) matches the iterative replay of the
    gathered rows at any pending depth."""
    rng = np.random.default_rng(seed)
    rows, dim, cap = 16, 8, 6
    lr, l2 = 1e-3, 1e-2
    w = _rows(rng, rows, dim)
    m = jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))
    v = jnp.asarray(np.abs(rng.normal(size=(rows, dim))).astype(np.float32))
    ls = jnp.asarray(rng.integers(0, depth, size=rows).astype(np.int32))
    # distinct owned ids, global (shard-offset) numbering
    local = rng.choice(rows, size=cap, replace=False).astype(np.int32)
    uids = jnp.asarray(np.sort(local) + row_offset)
    step = jnp.asarray(depth, jnp.int32)

    w_k, m_k, v_k = sparse_gather_catchup(
        w, m, v, ls, uids, step, lr=lr, l2=l2, row_offset=row_offset)

    loc = np.asarray(uids) - row_offset
    w_rp = optim_lib.decay_replay_reference(w[loc], ls[loc], step - 1,
                                            lr=lr, l2=l2)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_rp),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m)[loc])
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v)[loc])


# ---------------------------------------------------------------------------
# regression: first touch at step 10_000 == fresh dense run
# ---------------------------------------------------------------------------


def test_first_touch_at_step_10000_matches_dense_run():
    """An id absent for 10_000 steps and then gathered must come out as if
    a dense run had decayed it every step: the caught-up row equals 10_000
    applications of the dense oracle's absent-row branch, and the ``aux``
    depth diagnostic would read 10_000 for it."""
    rng = np.random.default_rng(42)
    vocab, dim = 24, 8
    lr, l2 = 1e-3, 1e-3
    w = _rows(rng, vocab, dim)
    m = jnp.asarray(rng.normal(size=(vocab, dim)).astype(np.float32))
    v = jnp.asarray(np.abs(rng.normal(size=(vocab, dim))).astype(np.float32))
    ls = jnp.zeros((vocab,), jnp.int32)
    t = jnp.asarray(10_001, jnp.int32)     # catch up through step 10_000

    # dense run: 10_000 steps of the dense oracle with the id absent
    # (cnt = 0) — exactly the absent-row branch per step
    cnt = jnp.zeros((vocab,), jnp.float32)

    def body(i, wmv):
        wd, md, vd = wmv
        return cc_ref.cowclip_adam_reference(
            wd, jnp.zeros_like(wd), cnt, md, vd, i + 1, lr=lr, l2=l2)

    w_dense, m_dense, v_dense = jax.lax.fori_loop(0, 10_000, body, (w, m, v))

    # sparse placement: one closed-form catch-up at first touch
    uids = jnp.arange(vocab, dtype=jnp.int32)[:8]
    w_rows, m_rows, v_rows = sparse_gather_catchup(
        w, m, v, ls, uids, t, lr=lr, l2=l2)

    np.testing.assert_allclose(np.asarray(w_rows), np.asarray(w_dense)[:8],
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(m_rows),
                                  np.asarray(m_dense)[:8])
    np.testing.assert_array_equal(np.asarray(v_rows),
                                  np.asarray(v_dense)[:8])


# ---------------------------------------------------------------------------
# aux diagnostic: catchup_depth_max
# ---------------------------------------------------------------------------


def _tiny_batches(n_steps, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    for s in range(n_steps):
        # step 0 touches only low ids; later steps bring in high ids whose
        # pending depth then shows up in the diagnostic
        hi = 4 if s == 0 else 40
        ids = np.stack([
            rng.integers(0, hi, size=batch),
            rng.integers(0, 13, size=batch),
            rng.integers(0, 5, size=batch),
        ], axis=1).astype(np.int32)
        yield {
            "ids": jnp.asarray(ids),
            "dense": jnp.asarray(
                rng.normal(size=(batch, 3)).astype(np.float32)),
            "labels": jnp.asarray(
                (rng.random(batch) < 0.3).astype(np.float32)),
        }


def test_sparse_aux_reports_catchup_depth():
    from repro.core import build_train_step, scale_hyperparams
    from repro.models import ctr

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=(60, 13, 5), n_dense=3,
                        emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                        sparse=True)
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=16, batch_size=16, base_dense_lr=2e-3)
    bundle = build_train_step(cfg, hp, path="sparse")
    params = bundle.prepare(ctr.init(jax.random.key(0), cfg))
    state = bundle.init(params)
    depths = []
    for b in _tiny_batches(3):
        params, state, aux = bundle.step(params, state, b)
        depths.append(int(aux["catchup_depth_max"]))
    # step 1: nothing pending (fresh state). Step 2 first-touches ids that
    # missed step 1 -> depth 1. Depth never exceeds t - 1.
    assert depths[0] == 0
    assert depths[1] == 1
    assert 0 <= depths[2] <= 2


def test_sharded_sparse_aux_reports_catchup_depth():
    from repro.core import build_train_step, scale_hyperparams
    from repro.models import ctr

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=(60, 13, 5), n_dense=3,
                        emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                        placement="sharded_sparse")
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=16, batch_size=16, base_dense_lr=2e-3)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, hp, path="sharded_sparse", mesh=mesh)
    params = bundle.prepare(ctr.init(jax.random.key(1), cfg))
    state = bundle.init(params)
    depths = []
    for b in _tiny_batches(3, seed=1):
        params, state, aux = bundle.step(params, state, b)
        depths.append(int(aux["catchup_depth_max"]))
    assert depths[0] == 0
    assert depths[1] == 1
    assert 0 <= depths[2] <= 2
