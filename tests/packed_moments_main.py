"""Packed-moment exactness check: the sparse placement with its Adam
moments packed (as ``init`` builds them) against the same placement with
``[V, dim]`` moments, over the same batches.

Run as a script in its own subprocess (tests/test_packed_moments.py does).
The comparison is bit for bit, and the two forms compile to different
programs: on a CPU with fused multiply-add, XLA contracts ``a * b + c``
where a fusion happens to hold both, so the same arithmetic may round
differently in the two programs. This process caps the CPU's instruction
set below FMA before jax initializes, so only the data movement can make
the two forms differ.

Each case reports, per compared point (every eager step, one scanned chunk
of several steps, and ``flush``), whether w, m, v (unpacked) and
``last_step`` are bitwise equal, whether every pad lane and pad row of the
packed tables is exactly 0, and the largest relative gap between a packed
moment table's norm and its unpacked form's — one JSON line per case.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_cpu_max_isa=AVX"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import json
import sys

import numpy as np

K = 3
BATCH = 48


def _batches(n_steps, vocabs, seed):
    """Ids that crowd a few lane rows (neighbours share a lane row), the
    last id of each vocab (its lane row is the packed table's last, part
    pad), and fields whose batch holds fewer distinct ids than its slots
    (pad slots with uid == vocab)."""
    rng = np.random.default_rng(seed)
    for _ in range(n_steps):
        cols = []
        for v in vocabs:
            pool = np.unique(np.clip([0, 1, 2, 11, 12, 13, 25, v // 2,
                                      v - 3, v - 2, v - 1], 0, v - 1))
            cols.append(rng.choice(pool, size=BATCH))
        yield {
            "ids": np.stack(cols, axis=1).astype(np.int32),
            "dense": rng.normal(size=(BATCH, 3)).astype(np.float32),
            "labels": (rng.random(BATCH) < 0.3).astype(np.float32),
        }


def run_case(name, emb_dim, vocabs, unique_capacity=0):
    import jax
    import jax.numpy as jnp

    from repro.core import scale_hyperparams
    from repro.kernels.cowclip import ref as cc_ref
    from repro.models import ctr
    from repro.train import engine as engine_lib
    from repro.train.loop import make_sparse_train_step

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=vocabs, n_dense=3,
                        emb_dim=emb_dim, mlp_dims=(16, 16), emb_sigma=1e-2,
                        sparse=True, unique_capacity=unique_capacity)
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=64, batch_size=64, base_dense_lr=2e-3)
    step, init, flush = make_sparse_train_step(cfg, hp)
    params0 = ctr.init(jax.random.key(3), cfg)
    shapes = jax.tree.map(lambda w: w.shape, params0["embed"])

    def unpacked(moments):
        return jax.tree.map(
            lambda a, s: cc_ref.unpack_rows(a, *s) if a.shape != s else a,
            moments, shapes, is_leaf=lambda x: isinstance(x, tuple))

    def fresh(form):
        p = jax.tree.map(jnp.copy, params0)
        s = init(p)
        if form == "unpacked":
            s = dict(s, m=unpacked(s["m"]), v=unpacked(s["v"]))
        return p, s

    def same(pa, sa, pb, sb):
        leaves = lambda t: [np.asarray(x) for x in jax.tree.leaves(t)]
        pairs = [(pa, pb), (unpacked(sa["m"]), sb["m"]),
                 (unpacked(sa["v"]), sb["v"]),
                 (sa["last_step"], sb["last_step"])]
        return all(np.array_equal(x, y) for a, b in pairs
                   for x, y in zip(leaves(a), leaves(b)))

    def pads_zero(state):
        ok = True
        for key in ("m", "v"):
            for a, s in zip(jax.tree.leaves(state[key]),
                            jax.tree.leaves(shapes, is_leaf=lambda x:
                                            isinstance(x, tuple))):
                if a.shape == s:
                    continue
                vocab, dim = s
                k = cc_ref.LANES // dim
                flat = np.asarray(a)[:, :k * dim].reshape(-1, dim)
                ok &= bool((np.asarray(a)[:, k * dim:] == 0).all())
                ok &= bool((flat[vocab:] == 0).all())
        return ok

    def norm_gap(state):
        gap = 0.0
        for key in ("m", "v"):
            for a, b in zip(jax.tree.leaves(state[key]),
                            jax.tree.leaves(unpacked(state[key]))):
                na = float(jnp.linalg.norm(a.ravel()))
                nb = float(jnp.linalg.norm(b.ravel()))
                gap = max(gap, abs(na - nb) / max(nb, 1e-30))
        return gap

    batches = [{k: jnp.asarray(v) for k, v in b.items()}
               for b in _batches(K, vocabs, seed=5)]
    n_packed = sum(a.shape != s for a, s in zip(
        jax.tree.leaves(init(params0)["m"]),
        jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))))

    # eager: one dispatch per step, compared after each
    pa, sa = fresh("packed")
    pb, sb = fresh("unpacked")
    eager = []
    for b in batches:
        pa, sa, _ = step(pa, sa, dict(b))
        pb, sb, _ = step(pb, sb, dict(b))
        eager.append(same(pa, sa, pb, sb))
    pads = [pads_zero(sa)]
    gaps = [norm_gap(sa)]
    pa, sa = flush(pa, sa)
    pb, sb = flush(pb, sb)
    eager_flush = same(pa, sa, pb, sb)

    # scanned: the K steps as one chunk
    chunk = {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}
    runner = engine_lib.make_chunk_runner(step.scan_step)
    pa, sa = fresh("packed")
    pb, sb = fresh("unpacked")
    pa, sa, _ = runner(pa, sa, chunk)
    pb, sb, _ = runner(pb, sb, chunk)
    scan = same(pa, sa, pb, sb)
    pads.append(pads_zero(sa))
    gaps.append(norm_gap(sa))
    pa, sa = flush(pa, sa)
    pb, sb = flush(pb, sb)
    return {
        "name": name,
        "n_packed": int(n_packed),
        "eager_steps_bitwise_equal": eager,
        "eager_flush_bitwise_equal": eager_flush,
        "scan_bitwise_equal": scan,
        "scan_flush_bitwise_equal": same(pa, sa, pb, sb),
        "pad_lanes_zero": all(pads),
        "norm_rel_gap": max(gaps),
    }


# vocabs that 128 // dim does not divide (a part-pad last lane row), and
# one that it does
CASES = {
    "dim8": dict(emb_dim=8, vocabs=(61, 13, 5)),
    "dim10": dict(emb_dim=10, vocabs=(61, 13, 5)),
    "dim10_divides": dict(emb_dim=10, vocabs=(60, 24, 12)),
    "dim10_overflow": dict(emb_dim=10, vocabs=(61, 13, 5),
                           unique_capacity=6),
    "dim16": dict(emb_dim=16, vocabs=(61, 13, 5)),
}


def main(argv):
    names = argv[1:] or list(CASES)
    for name in names:
        print(json.dumps(run_case(name, **CASES[name])), flush=True)


if __name__ == "__main__":
    main(sys.argv)
