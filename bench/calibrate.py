#!/usr/bin/env python3
"""Readings from which a cell's limits are set (``bench/limits/``).

    python bench/calibrate.py --workload deepfm-criteo.b128k \\
        --program 12 --control 3 --high 3 --default 3 --bf16 3 \\
        --half-batch 3 --first-seed 4000000000

In one process, on the chip and at the cell's own size, each on seeds of
its own: the program as the configuration states it (``--program``); the
control, the reference with its tower's products in three bfloat16 passes
put in the program's place (``--control``); the program with its products
at JAX's ``high`` (three passes) and ``default`` (one pass) matmul
precision, and with its ``bfloat16`` compute path (``--high``,
``--default``, ``--bf16``); and the fault of half of each batch left out,
the mean taken over the rest (``--half-batch``). Each reads the first chunk
against the plain reference as a benchmark run does, with no timed window.
One JSON line per seed; a summary line last: the largest reading of each
number over the program's seeds and the smallest over each of the others'.
A state left unchanged reads 1 on ``change_gap`` and ``m_gap`` by
construction and needs no run.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent

# kind -> what it changes in the configuration
VARIANTS = {"program": {}, "high": {"matmul_precision": "high"},
            "default": {"matmul_precision": "default"},
            "bf16": {"compute_dtype": "bfloat16"}, "half_batch": {}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--program", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    for kind in ("high", "default", "bf16", "half-batch"):
        ap.add_argument(f"--{kind}", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    from benchlib import check, harness, spec as spec_lib
    from repro.launch import train as train_lib
    from repro.train import metrics

    train_lib.use_compile_cache()
    spec = spec_lib.load(args.workload, BENCH.parent)
    full = metrics.logloss

    def half(z, y):
        return full(z[: z.shape[0] // 2], y[: y.shape[0] // 2])

    counts = {"program": args.program, "control": args.control,
              "high": args.high, "default": args.default, "bf16": args.bf16,
              "half_batch": args.half_batch}
    seed = args.first_seed
    seen = {}
    for kind, n in counts.items():
        metrics.logloss = half if kind == "half_batch" else full
        for _ in range(n):
            t = time.perf_counter()
            if kind == "control":
                read = harness.control_check(spec, seed)
            else:
                variant = spec._replace(
                    config={**spec.config, **VARIANTS[kind]})
                read, _, _ = harness.check_first_chunk(
                    harness.set_up(variant, seed, t))
            row = {n_: read[n_]["value"] for n_ in check.NAMES}
            print(json.dumps({"kind": kind, "seed": seed, **row,
                              "diag": read["_diag"],
                              "left_out": read["_left_out"],
                              "seconds": time.perf_counter() - t}),
                  flush=True)
            seen.setdefault(kind, []).append(row)
            seed += 1
    metrics.logloss = full
    summary = {k: {n_: (max if k == "program" else min)(r[n_] for r in rows)
                   for n_ in check.NAMES} for k, rows in seen.items()}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
