"""The benchmark's seeded generator: the same seed gives the same pool, and
each field's ids follow Zipf(1.1) over its whole vocab."""

import numpy as np
import pytest

from benchlib import gen

VOCABS = (3, 584, 93146, 10131227)


def test_same_seed_same_pool_other_seed_other_pool():
    seed = 2**33 + 17
    a = gen.make_pool(20_000, VOCABS, 13, zipf_a=1.1, seed=seed)
    b = gen.make_pool(20_000, VOCABS, 13, zipf_a=1.1, seed=seed, threads=1)
    c = gen.make_pool(20_000, VOCABS, 13, zipf_a=1.1, seed=seed + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.ids, c.ids)
    assert a.ids.dtype == np.int32 and a.dense.dtype == np.float32
    assert ((a.ids >= 0) & (a.ids < np.array(VOCABS))).all()
    assert 0.2 < a.labels.mean() < 0.3


@pytest.mark.parametrize("vocab", [584, 93146, 10131227])
def test_ranks_follow_zipf(vocab):
    """Rank frequencies match k**-1.1 / H over the head and in bands of
    the tail, within 5 standard errors."""
    n = 400_000
    u = np.random.default_rng(5).random(n)
    ranks = gen.zipf_ranks(u, vocab, 1.1)
    assert ranks.min() >= 1 and ranks.max() <= vocab
    exact = np.arange(1, min(vocab, 10**6) + 1, dtype=np.float64) ** -1.1
    if vocab <= 10**6:
        z = exact.sum()
    else:   # the sum's tail by the integral, to well under the test's noise
        z = exact.sum() + ((10**6 + 0.5) ** -0.1 - (vocab + 0.5) ** -0.1) / 0.1
    edges = [1, 2, 3, 5, 9, 17, 65, 257, 1025, 4097, vocab + 1]
    edges = sorted({e for e in edges if e <= vocab + 1})
    for lo, hi in zip(edges[:-1], edges[1:]):
        ks = np.arange(lo, hi, dtype=np.float64)
        p = (ks ** -1.1).sum() / z
        got = np.mean((ranks >= lo) & (ranks < hi))
        se = np.sqrt(p * (1 - p) / n)
        assert abs(got - p) < 5 * se + 1e-4, (lo, hi, got, p)


def test_ids_are_a_bijection_of_ranks():
    v = 5000
    a, b = gen.affine_bijection(v, 3)
    assert len(np.unique((np.arange(v) * a + b) % v)) == v
