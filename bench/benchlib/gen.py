"""Seeded host pool of CTR rows, O(rows) in time and memory.

Adapted from the program's ``data/synthetic.make_ctr_dataset`` so that the
benchmark owns its inputs. That generator draws ids with
``rng.choice(vocab, p=...)`` and permutes each vocab, both O(vocab) per
field: at Criteo's 33.8M ids that is most of a minute of set-up. Here:

* each field's ids are Zipf(a) ranks over the full vocab: exact inverse-CDF
  over the first ``HEAD`` ranks, and the continuous power law (midpoint
  rule) beyond them, so a draw costs O(1);
* rank -> id is an affine bijection ``(rank * A + B) mod V`` with ``A``
  coprime to ``V``, so hot ids are scattered over the table as in a hashed
  layout, with no O(vocab) permutation;
* labels come from an FM teacher whose first-order weight and rank-2 latent
  vector of an id are hashed from ``(seed, field, id)``, calibrated to a
  25 % positive rate.

Everything is a pure function of ``(seed, sizes)``.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Sequence

import numpy as np

HEAD = 64
BUCKETS = 1 << 16
TARGET_POS_RATE = 0.25


class Pool(NamedTuple):
    ids: np.ndarray        # [n, F] int32
    dense: np.ndarray      # [n, n_dense] float32
    labels: np.ndarray     # [n] float32 in {0, 1}


def seed32(seed: int, *salt: int) -> int:
    """A 32-bit key from an arbitrary non-negative seed (any size)."""
    return int(np.random.SeedSequence([int(seed), *salt]).generate_state(1)[0])


def zipf_head(vocab: int, a: float):
    """``(cdf over ranks 1..H, mass of the head)``."""
    h = min(vocab, HEAD)
    p = np.arange(1, h + 1, dtype=np.float64) ** (-a)
    tail = 0.0
    if vocab > h:
        lo, hi = h + 0.5, vocab + 0.5
        tail = (lo ** (1 - a) - hi ** (1 - a)) / (a - 1)
    z = p.sum() + tail
    return np.cumsum(p) / z, p.sum() / z


def zipf_ranks(u: np.ndarray, vocab: int, a: float) -> np.ndarray:
    """1-based Zipf(a) ranks in [1, vocab] from uniforms ``u`` in [0, 1)."""
    cdf, head = zipf_head(vocab, a)
    h = len(cdf)
    # head: a bucket table gives the rank at each bucket's low edge; every
    # head step is wider than a bucket, so one correction pass is exact
    edges = np.arange(BUCKETS, dtype=np.float64) / BUCKETS
    table = np.searchsorted(cdf, edges, side="right")
    ranks = table[(u * BUCKETS).astype(np.int64)]
    ranks += u >= cdf[np.minimum(ranks, h - 1)]
    ranks = ranks.astype(np.int64) + 1
    tail = u >= head
    if tail.any():
        # continuous power law on [h + 0.5, vocab + 0.5), rounded
        lo, hi = h + 0.5, vocab + 0.5
        q = (u[tail] - head) / (1.0 - head)
        x = (lo ** (1 - a) - q * (lo ** (1 - a) - hi ** (1 - a))) \
            ** (1.0 / (1 - a))
        ranks[tail] = np.clip(np.floor(x + 0.5), h + 1, vocab)
    return np.minimum(ranks, vocab)


def affine_bijection(vocab: int, key: int):
    """``(A, B)`` with gcd(A, vocab) == 1: ``(r * A + B) % vocab`` permutes."""
    rng = np.random.default_rng(key)
    if vocab == 1:
        return 1, 0
    while True:
        a = int(rng.integers(1, vocab))
        if math.gcd(a, vocab) == 1:
            return a, int(rng.integers(0, vocab))


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over uint64 (wraps by design)."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _unit(bits: np.ndarray) -> np.ndarray:
    """16 bits -> centred uniform in [-1, 1)."""
    return (bits.astype(np.float32) / np.float32(32768.0)) - np.float32(1.0)


def _field(u: np.ndarray, vocab: int, zipf_a: float, key: int, salt):
    """One field's ids and the teacher's per-id terms ``(ids, w, l0, l1)``."""
    ranks = zipf_ranks(u, vocab, zipf_a)
    a, b = affine_bijection(vocab, key)
    col = ((ranks - 1) * a + b) % vocab
    with np.errstate(over="ignore"):
        h = _mix(col.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + salt)
    return (col.astype(np.int32), _unit(h & np.uint64(0xFFFF)),
            _unit((h >> np.uint64(16)) & np.uint64(0xFFFF)),
            _unit((h >> np.uint64(32)) & np.uint64(0xFFFF)))


def make_pool(rows: int, vocabs: Sequence[int], n_dense: int, *,
              zipf_a: float, seed: int, threads: int = 4) -> Pool:
    """``rows`` seeded rows over ``vocabs`` (one Zipf(zipf_a) field each).

    Fields are drawn on ``threads`` threads (NumPy releases the GIL); the
    result does not depend on their number."""
    rng = np.random.default_rng(seed32(seed, 0))
    ids = np.empty((rows, len(vocabs)), np.int32)
    score = np.zeros(rows, np.float32)
    lat_sum = np.zeros((2, rows), np.float32)
    lat_sq = np.zeros((2, rows), np.float32)
    jobs = [(rng.random(rows), v, seed32(seed, 2, f), np.uint64(seed32(seed, 1, f)))
            for f, v in enumerate(vocabs)]
    with ThreadPoolExecutor(max_workers=threads) as ex:
        futures = [ex.submit(_field, u, v, zipf_a, key, salt)
                   for u, v, key, salt in jobs]
        del jobs
        for f, fut in enumerate(futures):
            ids[:, f], w, l0, l1 = fut.result()
            score += w
            for i, lat in enumerate((l0, l1)):
                lat_sum[i] += lat
                lat_sq[i] += lat * lat
    score += 0.5 * (lat_sum * lat_sum - lat_sq).sum(axis=0)
    dense = rng.standard_normal((rows, n_dense), dtype=np.float32)
    wd = rng.standard_normal(n_dense).astype(np.float32) / np.sqrt(n_dense)
    score += dense @ wd
    score = (score - score.mean()) / max(float(score.std()), 1e-6)
    # bias for the target positive rate, bisected on a subsample
    sub = score[:: max(1, rows // 65536)].astype(np.float64)
    lo, hi = -20.0, 20.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        if (1.0 / (1.0 + np.exp(-(2.0 * sub + mid)))).mean() > TARGET_POS_RATE:
            hi = mid
        else:
            lo = mid
    probs = 1.0 / (1.0 + np.exp(-(2.0 * score + 0.5 * (lo + hi))))
    labels = (rng.random(rows, dtype=np.float32) < probs).astype(np.float32)
    return Pool(ids, dense, labels)
