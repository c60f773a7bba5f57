"""sharded_sparse on a (2, 2) mesh of four virtual CPU devices against the
sparse placement on one, through ``repro.launch.train.run_ctr``, in float64
or float32.

In float32 the two can drift apart as they train: the mesh sums the
"data" halves' gradients in another order than one device does, and Adam
can grow those last-bit differences step by step (4.1e-5 after 8 steps
at Criteo's field 20, the second command below). In float64 the rounding
is 2**29 times finer, so a drift that stays at float64's own scale (about
1e-16) shows that the two placements compute the same update, row for
row; a fault in the mesh path (a row updated twice or not at all, a pad
row read as a real one, an overflow) would leave a gap of the order of
one Adam step (the learning rate) at any precision.

Run in its own process (tests/test_sharded_sparse.py does), because the
device count and float64 must be set before jax starts:

    python tests/mesh_f64_main.py                         # the test's size
    python tests/mesh_f64_main.py --field-20 7046547 --batch 8192 \\
        --samples 131072 --precision f32                  # Criteo's field 20

It prints one JSON line: the largest param difference after ``--steps``
steps, the leaf it is in, and the largest per-step loss difference.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json

import numpy as np


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--precision", choices=("f64", "f32"), default="f64")
    ap.add_argument("--cap", type=int, default=20_000,
                    help="every deepfm-criteo field but field 20 is cut to "
                         "at most this many ids")
    ap.add_argument("--field-20", type=int, default=100_003,
                    help="field 20's vocab (Criteo's is 7,046,547; an odd "
                         "vocab leaves a pad row on one of the two shards)")
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--samples", type=int, default=32_768)
    ap.add_argument("--steps", type=int, default=8)
    return ap.parse_args(argv)


def main(argv=None):
    opts = parse(argv)
    import jax

    if opts.precision == "f64":
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp

        # the CTR path names float32 for its tables, moments, counts and
        # casts; read as float64 before repro is imported, all of it runs
        # in float64
        jnp.float32 = jnp.float64

    from repro.configs.deepfm_criteo import CRITEO_VOCABS
    from repro.data.synthetic import make_ctr_dataset
    from repro.launch import train as train_lib

    vocabs = [min(v, opts.cap) for v in CRITEO_VOCABS]
    vocabs[20] = opts.field_20
    data = make_ctr_dataset(opts.samples, vocabs, n_dense=13, zipf_a=1.1,
                            seed=0)
    argv = ["--task", "ctr", "--arch", "deepfm-criteo", "--rule", "cowclip",
            "--base-batch", "1024", "--base-lr", "1e-4", "--base-l2", "0",
            "--batch", str(opts.batch), "--samples", str(opts.samples),
            "--steps", str(opts.steps), "--scan-steps", str(opts.steps),
            "--epochs", "1", "--seed", "0"]
    runs = {}
    for placement, extra in (("sharded_sparse", ["--mesh", "2,2"]),
                             ("sparse", [])):
        args = train_lib.parse_args(argv + ["--placement", placement] + extra)
        if opts.precision == "f64":
            args.compute_dtype = "float64"
        with jax.default_matmul_precision("float32"):
            res = train_lib.run_ctr(args, data=data)
        runs[placement] = (jax.device_get(res.params), res.losses)
        del res

    mesh_params, one_params = runs["sharded_sparse"][0], runs["sparse"][0]
    worst = (0.0, "")
    dtypes = set()
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(mesh_params),
                            jax.tree.leaves(one_params)):
        x, y = np.asarray(x), np.asarray(y)
        dtypes.add(str(y.dtype))
        # the mesh's tables carry zero pad rows past the vocab
        err = float(np.max(np.abs((x[:y.shape[0]] if y.ndim else x) - y)))
        worst = max(worst, (err, jax.tree_util.keystr(path)))
    loss_gap = max(abs(a - b) for a, b in zip(runs["sharded_sparse"][1],
                                              runs["sparse"][1]))
    print(json.dumps({
        "precision": opts.precision, "field_20": opts.field_20,
        "cap": opts.cap, "batch": opts.batch, "steps": opts.steps,
        "param_dtypes": sorted(dtypes), "max_abs_err": worst[0],
        "at": worst[1], "max_loss_gap": loss_gap}), flush=True)


if __name__ == "__main__":
    main()
