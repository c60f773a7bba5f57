"""Hot/cold two-tier placement properties (embed/hotcold.py).

Three guarantees, each load-bearing for streaming training:

* **Residency never changes the math** — runs of the same batch stream at
  different hot capacities export *bitwise identical* params (so an
  evicted-then-readmitted row bit-matches one that stayed hot), and the
  placement agrees with the sparse/dense references within the framework's
  1e-5 exactness budget.
* **No row is lost or double-resident** — ``slot_ids``/``slot_of`` stay a
  bijection between occupied slots and resident ids, bounded by capacity.
* **Hit rate is monotone in capacity** on a fixed Zipf stream: the hot set
  is the global top-C of all ids touched so far under (freq desc, id asc),
  and frequencies are residency-independent, so the hit sets nest.

Property tests run through tests/hypcompat.py: real hypothesis when
installed, a deterministic seeded sweep otherwise. Capacities are drawn
from a small pool and runs are memoised — each distinct capacity compiles
its own step shapes, so the pool keeps the sweep cheap.
"""

import functools

import jax
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    from hypcompat import hypothesis, st

from repro.core import build_train_step, scale_hyperparams
from repro.data.synthetic import make_ctr_dataset, iterate_batches
from repro.embed.hotcold import (hot_tier_bytes, resident_ids,
                                 residency_map_bytes)
from repro.embed.store import max_pending_depth
from repro.models import ctr

VOCABS = (60, 13, 5)
BATCH = 32
STEPS = 8
CAP_POOL = [1, 2, 4, 8, 16, 100]      # 100 >= max(VOCABS): nothing evicts


def _cfg(**kw):
    return ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                         emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                         **kw)


def _hp():
    return scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                             base_batch=BATCH, batch_size=BATCH,
                             base_dense_lr=2e-3)


def _batches(seed):
    ds = make_ctr_dataset(512, VOCABS, n_dense=3, zipf_a=1.2, seed=3)
    out = []
    for b in iterate_batches(ds, BATCH, seed=seed):
        out.append(b)
        if len(out) >= STEPS:
            break
    return out


@functools.lru_cache(maxsize=None)
def _run(path, capacity=0, seed=1, admission="cumulative", half_life=0):
    """Train STEPS steps; returns (exported params leaves as a dict keyed
    by path string, final state, per-step aux dicts)."""
    import jax.numpy as jnp

    kw = ({"hot_capacity": capacity, "admission": admission,
           "half_life": half_life} if path == "hotcold" else {})
    bundle = build_train_step(_cfg(), _hp(), path=path, **kw)
    params = bundle.prepare(ctr.init(jax.random.key(0), _cfg()))
    state = bundle.init(params)
    auxes = []
    for b in _batches(seed):
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, state, aux = bundle.step(params, state, batch)
        auxes.append({k: float(v) for k, v in aux.items()})
    depth = max_pending_depth(state)
    params, state = bundle.flush(params, state)
    leaves = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
              jax.tree_util.tree_leaves_with_path(bundle.export(params))}
    return leaves, state, auxes, depth


# ---------------------------------------------------------------------------
# residency invariants
# ---------------------------------------------------------------------------


@hypothesis.given(capacity=st.sampled_from(CAP_POOL),
                  seed=st.sampled_from([1, 2]))
@hypothesis.settings(max_examples=12, deadline=None)
def test_no_row_lost_or_double_resident(capacity, seed):
    """slot_ids/slot_of stay a bijection: every resident id occupies
    exactly one slot, every occupied slot maps back to its id, and no id
    is resident twice (which would fork the row's update history)."""
    _, state, _, _ = _run("hotcold", capacity, seed)
    hot = state["hot"]
    for f, vocab in (("field_0", 60), ("field_1", 13), ("field_2", 5)):
        sid = np.asarray(hot["slot_ids"][f])
        so = np.asarray(hot["slot_of"][f])
        res = sid[sid < vocab]
        assert len(res) == len(np.unique(res)), f       # no double residency
        assert len(res) <= min(capacity, vocab)
        # bijection both ways
        for s, i in enumerate(sid):
            if i < vocab:
                assert so[i] == s
        cold = np.setdiff1d(np.arange(vocab), res)
        assert (so[cold] == -1).all()
        # resident_ids agrees with the raw maps
        np.testing.assert_array_equal(np.sort(resident_ids(state)[f]),
                                      np.sort(res))


@pytest.mark.parametrize("admission,half_life",
                         [("cumulative", 0), ("decayed", 3)])
def test_frequencies_are_capacity_independent(admission, half_life):
    """Id frequencies depend only on the batches seen — the residency-
    independence that makes the admission ranking a global total order.
    Holds for both policies: the decayed score's per-step multiply touches
    every id identically, so it never couples frequency to residency."""
    _, st_small, _, _ = _run("hotcold", 2, admission=admission,
                             half_life=half_life)
    _, st_big, _, _ = _run("hotcold", 100, admission=admission,
                           half_life=half_life)
    for f in ("field_0", "field_1", "field_2"):
        np.testing.assert_array_equal(
            np.asarray(st_small["hot"]["freq"][f]),
            np.asarray(st_big["hot"]["freq"][f]))


# ---------------------------------------------------------------------------
# exactness: capacity independence (bitwise) and the reference placements
# ---------------------------------------------------------------------------


@hypothesis.given(capacity=st.sampled_from([2, 4, 8, 16]))
@hypothesis.settings(max_examples=10, deadline=None)
def test_capacity_runs_bitwise_identical(capacity):
    """The heart of the placement: an evicted-then-readmitted row
    bit-matches one that stayed hot, so the exported params of a
    capacity-starved run equal the no-eviction (capacity >= vocab) run
    bit for bit."""
    leaves_small, _, _, _ = _run("hotcold", capacity)
    leaves_big, _, _, _ = _run("hotcold", 100)
    assert leaves_small.keys() == leaves_big.keys()
    for k in leaves_small:
        np.testing.assert_array_equal(leaves_small[k], leaves_big[k],
                                      err_msg=k)


def test_decayed_admission_capacity_runs_bitwise_identical():
    """Capacity independence is a property of the *policy shape* (rank a
    residency-independent score), not of the cumulative policy: the
    decayed score inherits it unchanged."""
    leaves_small, _, _, _ = _run("hotcold", 2, admission="decayed",
                                 half_life=3)
    leaves_big, _, _, _ = _run("hotcold", 100, admission="decayed",
                               half_life=3)
    for k in leaves_small:
        np.testing.assert_array_equal(leaves_small[k], leaves_big[k],
                                      err_msg=k)
    # the policy is real: it admits a different working set than
    # cumulative on the same stream (frequencies diverge)
    _, st_cum, _, _ = _run("hotcold", 2)
    _, st_dec, _, _ = _run("hotcold", 2, admission="decayed", half_life=3)
    assert any(
        not np.array_equal(np.asarray(st_cum["hot"]["freq"][f]),
                           np.asarray(st_dec["hot"]["freq"][f]))
        for f in ("field_0", "field_1", "field_2"))


def test_capacity_one_within_rounding():
    """The degenerate single-row hot tier compiles to different XLA
    specializations (single-row gathers fold to broadcasts), so capacity 1
    agrees to f32 rounding rather than bit for bit — same story as the
    sparse placement's fusion differences."""
    leaves_one, _, _, _ = _run("hotcold", 1)
    leaves_big, _, _, _ = _run("hotcold", 100)
    for k in leaves_one:
        np.testing.assert_allclose(leaves_one[k], leaves_big[k],
                                   atol=1e-7, rtol=0, err_msg=k)


def test_matches_sparse_and_dense_references():
    """Same stream through the sparse placement and the dense substrate:
    agreement within the framework's 1e-5 budget. (Not bitwise vs sparse —
    the two step graphs fuse differently under XLA, so isolated lanes land
    an ulp apart; see the module docstring.)"""
    leaves_hc, _, _, _ = _run("hotcold", 4)
    leaves_sp, _, _, _ = _run("sparse")
    leaves_d, _, _, _ = _run("substrate")
    for k, v in leaves_hc.items():
        np.testing.assert_allclose(v, leaves_sp[k], atol=1e-7, rtol=0,
                                   err_msg=k)
        np.testing.assert_allclose(v, leaves_d[k], atol=1e-5, rtol=0,
                                   err_msg=k)


def test_pending_depth_and_flush():
    """Zipf tails leave rows un-decayed mid-run (max_pending_depth > 0 is
    an upper bound for hotcold — the cold view of a resident row is
    stale); after flush both tiers are reconciled and nothing is
    pending."""
    _, state, _, depth_preflush = _run("hotcold", 4)
    assert depth_preflush > 0
    assert max_pending_depth(state) == 0
    for ls in jax.tree.leaves(state["last_step"]):
        assert (np.asarray(ls) == int(state["step"])).all()


# ---------------------------------------------------------------------------
# hit rate monotone in capacity
# ---------------------------------------------------------------------------


def test_hit_rate_monotone_in_capacity():
    """On a fixed Zipf stream the cumulative hit rate never decreases with
    capacity: the hot set is the top-C of a capacity-independent ranking,
    so the hit sets nest across C."""
    rates = []
    for cap in (1, 2, 4, 8, 16, 100):
        _, _, auxes, _ = _run("hotcold", cap)
        hits = sum(a["hot_hit_rows"] for a in auxes)
        total = sum(a["hot_lookup_rows"] for a in auxes)
        assert hits <= total
        rates.append(hits / total)
    assert all(b >= a - 1e-12 for a, b in zip(rates, rates[1:])), rates
    # capacity pressure is real at the low end and eases at vocab size
    assert rates[0] < rates[-1]


def test_evictions_under_pressure_only():
    _, _, auxes_small, _ = _run("hotcold", 2)
    _, _, auxes_big, _ = _run("hotcold", 100)
    assert sum(a["evictions"] for a in auxes_small) > 0
    assert sum(a["evictions"] for a in auxes_big) == 0


# ---------------------------------------------------------------------------
# device-resident working set
# ---------------------------------------------------------------------------


def test_hot_tier_bytes_scale_with_capacity_not_vocab():
    _, st_small, _, _ = _run("hotcold", 2)
    _, st_big, _, _ = _run("hotcold", 100)
    small, big = hot_tier_bytes(st_small), hot_tier_bytes(st_big)
    assert small < big
    # hot_tier_bytes counts only the O(capacity) working set now
    table_bytes = sum(
        v.size * v.dtype.itemsize for v in jax.tree.leaves(
            ctr.init(jax.random.key(0), _cfg())["embed"]))
    assert small < table_bytes


def test_residency_map_bytes_reported_separately():
    """The O(vocab) slot_of/freq maps are bookkeeping, not working set:
    hot_tier_bytes excludes them (it must scale with capacity only) and
    residency_map_bytes reports them apart — identical across capacities,
    because both maps are vocab-sized."""
    _, st_small, _, _ = _run("hotcold", 2)
    _, st_big, _, _ = _run("hotcold", 100)
    rm_small = residency_map_bytes(st_small)
    assert rm_small == residency_map_bytes(st_big) > 0
    # exact accounting: slot_of (int32 per vocab) + freq (f32 per vocab)
    assert rm_small == 2 * 4 * sum(VOCABS)
