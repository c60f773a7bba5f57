"""The sparse placement's packed Adam moments: ``k = 128 // dim`` rows to a
128-lane row (repro.kernels.cowclip.ref).

The contract: the packed form holds exactly the ``[V, dim]`` form's values,
its row gather and scatter move them bit for bit (ids that share a lane
row, pad slots, a vocab that ``k`` does not divide), pad lanes stay 0, and
a whole sparse step, scan chunk and flush with packed moments lands bitwise
on the same step with ``[V, dim]`` moments. The other placements keep
``[V, dim]`` moments.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scale_hyperparams
from repro.kernels.cowclip import ref as cc_ref
from repro.kernels.cowclip import sparse_gather_catchup, sparse_update_scatter
from repro.models import ctr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIMS = (1, 8, 10, 16)


def _table(vocab, dim, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(vocab, dim)).astype(np.float32))


def _slots(vocab, dim, cap, seed=0):
    """Sorted unique ids, several per lane row and the vocab's last one,
    then pad slots holding ``vocab`` (count 0)."""
    k = cc_ref.LANES // dim
    rng = np.random.default_rng(seed)
    near = np.arange(k + 2)                   # the first lane rows, crowded
    ids = np.unique(np.concatenate([near, [vocab - 1, vocab // 2],
                                    rng.integers(0, vocab, 5)]))
    ids = ids[ids < vocab][:cap - 2]
    uids = np.full(cap, vocab, np.int32)
    uids[:len(ids)] = ids
    counts = np.zeros(cap, np.float32)
    counts[:len(ids)] = rng.integers(1, 4, len(ids))
    return jnp.asarray(uids), jnp.asarray(counts)


@pytest.mark.parametrize("dim", DIMS)
@pytest.mark.parametrize("vocab", (1, 61, 300))
def test_pack_round_trip_and_zero_pads(dim, vocab):
    """pack -> unpack is the identity; the packed form is ``[ceil(V / k),
    128]`` with zeros in every lane no row owns."""
    t = _table(vocab, dim)
    p = cc_ref.pack_rows(t)
    k = cc_ref.LANES // dim
    assert p.shape == cc_ref.packed_shape(vocab, dim) == (-(-vocab // k), 128)
    np.testing.assert_array_equal(cc_ref.unpack_rows(p, vocab, dim), t)
    flat = np.asarray(p)[:, :k * dim].reshape(-1, dim)
    assert (np.asarray(p)[:, k * dim:] == 0).all()
    assert (flat[vocab:] == 0).all()
    np.testing.assert_allclose(float(jnp.linalg.norm(p.ravel())),
                               float(jnp.linalg.norm(t.ravel())), rtol=1e-6)


@pytest.mark.parametrize("dim", DIMS)
def test_gather_packed_is_exact(dim):
    """Real slots read their rows bit for bit, -0.0, inf and NaN included."""
    vocab = 61
    t = np.asarray(_table(vocab, dim, seed=1)).copy()
    t[3, 0], t[4, -1], t[5, 0] = -0.0, np.inf, np.nan
    t = jnp.asarray(t)
    uids, counts = _slots(vocab, dim, 24, seed=1)
    real = np.asarray(counts) > 0
    got = jax.jit(cc_ref.gather_packed, static_argnums=2)(
        cc_ref.pack_rows(t), uids, dim)
    want = np.asarray(t)[np.asarray(uids)[real]]
    assert np.asarray(got)[real].tobytes() == want.tobytes()


@pytest.mark.parametrize("dim", DIMS)
def test_scatter_packed_sets_rows_exactly(dim):
    """Set semantics on real slots only, with neighbours in one lane row;
    pad slots (uid == vocab, whose lane row may be the last, part-pad one)
    write nothing; pad lanes stay 0; the packed norm is the ``[V, dim]``
    norm to float32 rounding."""
    for vocab in (61, 60):
        t = _table(vocab, dim, seed=2)
        uids, counts = _slots(vocab, dim, 24, seed=2)
        rows = _table(24, dim, seed=3)
        keep = counts > 0
        loc = jnp.where(keep, uids, vocab)
        want = t.at[loc].set(rows, mode="drop")
        got = jax.jit(cc_ref.scatter_packed, donate_argnums=0)(
            cc_ref.pack_rows(t), loc, rows, keep)
        assert (np.asarray(cc_ref.unpack_rows(got, vocab, dim)).tobytes()
                == np.asarray(want).tobytes())
        k = cc_ref.LANES // dim
        flat = np.asarray(got)[:, :k * dim].reshape(-1, dim)
        assert (np.asarray(got)[:, k * dim:] == 0).all()
        assert (flat[vocab:] == 0).all()
        np.testing.assert_allclose(float(jnp.linalg.norm(got.ravel())),
                                   float(jnp.linalg.norm(want.ravel())),
                                   rtol=1e-6)


def test_row_functions_read_either_form():
    """``sparse_gather_catchup`` / ``sparse_update_scatter`` give the same
    rows and tables, bit for bit, whether the moments come packed or
    ``[V, dim]``."""
    vocab, dim = 61, 10
    w, m, v = (_table(vocab, dim, seed=s) for s in (4, 5, 6))
    v = jnp.abs(v)
    ls = jnp.asarray(np.random.default_rng(7).integers(0, 3, vocab),
                     jnp.int32)
    uids, counts = _slots(vocab, dim, 24, seed=4)
    g = _table(24, dim, seed=8)
    t = jnp.int32(4)
    kw = dict(lr=1e-2, l2=1e-3)
    outs = []
    for form in (jnp.copy, cc_ref.pack_rows):
        rows = sparse_gather_catchup(w, form(m), form(v), ls, uids, t, **kw)
        new = sparse_update_scatter(
            jnp.copy(w), form(m), form(v), jnp.copy(ls), uids, counts,
            rows[0], g, rows[1], rows[2], t, **kw)
        new = (new[0], *(a if a.shape == w.shape
                         else cc_ref.unpack_rows(a, vocab, dim)
                         for a in new[1:3]), new[3])
        outs.append((rows, new))
    real = np.asarray(counts) > 0
    for a, b in zip(outs[0][0], outs[1][0]):
        np.testing.assert_array_equal(np.asarray(a)[real],
                                      np.asarray(b)[real])
    for a, b in zip(outs[0][1], outs[1][1]):
        np.testing.assert_array_equal(a, b)


def test_sparse_init_packs_by_shape():
    """``init`` builds each moment table in the form its shape picks:
    packed where two or more rows of two or more values fit a lane row,
    ``[V, 1]`` for the first-order tables; no ``[V, dim]`` copy."""
    from repro.train.loop import make_sparse_train_step

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=(61, 13, 5), n_dense=3,
                        emb_dim=10, mlp_dims=(16,), sparse=True)
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=64, batch_size=64, base_dense_lr=2e-3)
    _, init, _ = make_sparse_train_step(cfg, hp)
    state = jax.eval_shape(init, jax.eval_shape(
        lambda: ctr.init(jax.random.key(0), cfg)))
    for key in ("m", "v"):
        assert [a.shape for a in state[key]["fm"].values()] == [
            (6, 128), (2, 128), (1, 128)]
        assert [a.shape for a in state[key]["lin"].values()] == [
            (61, 1), (13, 1), (5, 1)]
    assert not cc_ref.packs(1) and not cc_ref.packs(65)
    assert cc_ref.packs(2) and cc_ref.packs(64)


def test_store_describes_packed_moments():
    """The sparse store's description counts the packed moment tables and
    their bytes in both forms; other placements say nothing of it."""
    from repro.embed import EmbeddingStore

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=(61, 13, 5), n_dense=3,
                        emb_dim=10, mlp_dims=(16,), sparse=True)
    lin = 2 * 4 * 1 * (61 + 13 + 5)
    raw = 2 * 4 * 10 * (61 + 13 + 5) + lin
    packed = 2 * 4 * 128 * (6 + 2 + 1) + lin
    assert EmbeddingStore(placement="sparse").describe(cfg) == (
        f"sparse(Adam moments of 3 of 6 tables packed 128 lanes wide: "
        f"{raw} -> {packed} bytes)")
    assert EmbeddingStore(placement="sparse").describe() == "sparse"
    assert EmbeddingStore().describe(cfg) == "dense(substrate)"


@pytest.mark.parametrize("path", ["fused", "sharded", "sharded_sparse",
                                  "hotcold"])
def test_other_placements_keep_unpacked_moments(path):
    """Every placement but sparse keeps its own ``[rows, dim]`` moments:
    each moment leaf has its table's shape (a row shard's, for the mesh
    placements; the hot tier's rows, for hotcold)."""
    from repro.core import build_train_step

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=(61, 13, 5), n_dense=3,
                        emb_dim=10, mlp_dims=(16,))
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=64, batch_size=64, base_dense_lr=2e-3)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bundle = build_train_step(cfg, hp, path=path, mesh=mesh, hot_capacity=4)
    params = bundle.prepare(ctr.init(jax.random.key(0), cfg))
    state = bundle.init(params)
    tables = [state] + ([state["hot"]] if "hot" in state else [])
    for st in tables:
        for key in ("m", "v"):
            for a, w in zip(jax.tree.leaves(st[key]),
                            jax.tree.leaves(params["embed"])):
                assert a.shape[1] == w.shape[1], (path, key, a.shape)
                if st is state:
                    assert a.shape == w.shape, (path, key, a.shape)


CASES = ["dim8", "dim10", "dim10_divides", "dim10_overflow", "dim16"]


@pytest.fixture(scope="module")
def packed_records():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = os.path.join(REPO, "tests", "packed_moments_main.py")
    proc = subprocess.run([sys.executable, script] + CASES, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = [json.loads(line) for line in proc.stdout.strip().splitlines()
            if line.startswith("{")]
    return {r["name"]: r for r in recs}


@pytest.mark.parametrize("case", CASES)
def test_packed_step_bitwise_equals_unpacked(packed_records, case):
    """Sparse steps with packed moments against ``[V, dim]`` moments over
    the same batches: w, m, v and last_step bitwise equal after every eager
    step, after a scanned chunk of several steps, and after each flush; pad
    lanes exactly 0; the moments' norms (what the benchmark's check reads)
    equal to float32 rounding."""
    rec = packed_records[case]
    assert rec["n_packed"] == 3, rec
    assert all(rec["eager_steps_bitwise_equal"]), rec
    assert rec["eager_flush_bitwise_equal"], rec
    assert rec["scan_bitwise_equal"], rec
    assert rec["scan_flush_bitwise_equal"], rec
    assert rec["pad_lanes_zero"], rec
    assert rec["norm_rel_gap"] < 1e-6, rec
