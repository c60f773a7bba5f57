#!/usr/bin/env python3
"""Benchmark of CTR training on TPU chips: one cell of ``BENCHMARK.json``.

    python bench/run.py --workload deepfm-criteo.b128k --seed 7 \\
        --seconds 30 --trace 0

Resolves the cell's configuration, traffic mix, limits and metric readers by
name (``benchlib.spec``), trains the program on the cell's traffic for
``--seconds`` after set-up (``benchlib.harness``), checks the first chunk
against the plain reference, and prints as the last line of stdout one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``check``, each compared number beside
its limit. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of the window.

Without a TPU, with fewer chips than the cell asks for, or without the
program beside it, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    sys.path.insert(0, str(BENCH))
    from benchlib import spec as spec_lib

    try:
        spec = spec_lib.load(args.workload, ROOT)
    except spec_lib.SpecError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro" / "launch" / "train.py").is_file():
        print(f"[bench] the program is not at {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    kind = f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}"
    if devices[0].platform != "tpu":
        print(f"[bench {kind}] no TPU: JAX found {devices[0].platform}",
              file=sys.stderr)
        return 3
    if len(devices) < spec.chips:
        print(f"[bench {kind}] the cell needs {spec.chips} chips",
              file=sys.stderr)
        return 3
    try:
        spec_lib.peaks_for(spec, devices[0].device_kind)
    except spec_lib.SpecError as e:
        print(f"[bench {kind}] {e}", file=sys.stderr)
        return 2

    from benchlib import harness
    from repro.launch import train as train_lib

    train_lib.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
