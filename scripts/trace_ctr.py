#!/usr/bin/env python3
"""Profile the sparse CTR step on a TPU chip and say where the device time
goes, grouped by the shape of what each op writes.

    python scripts/trace_ctr.py --out chiprun_out/trace          # capture
    python scripts/trace_ctr.py --summarize chiprun_out/trace_b8192 \\
        --batch 8192 --host-s 0.8654659739999886                 # re-read

Capture trains ``deepfm-criteo`` (26 fields, 33,762,591 ids, dim 10, MLP
3x400) with the sparse placement, the CowClip rule and the scan engine on
262,144 seeded synthetic rows, through the CLI's own functions
(``repro.launch.train``). For each case ``BATCH:SCAN:CHUNKS`` it runs one
chunk of ``SCAN`` steps to compile, then ``CHUNKS`` chunks under
``jax.profiler.trace`` into ``<out>_b<BATCH>``, and prints the host seconds
per step and the summary below.

The summary reads the trace's ``/device:TPU:0`` plane:

- ``busy_over_host_window``: device time of the ``XLA Modules`` line (the
  traced programs) over the host seconds of the traced chunks;
- the ``XLA Ops`` line's op time (``while``/``conditional``/``call``
  wrappers left out, their bodies counted), split by each op's output:
  ``table`` (leading dim is the vocab of a field with more ids than the
  batch: an op that writes a whole ``[V, dim]`` or ``[V]`` table where
  the batch touches at most ``batch`` rows), ``small_table`` (the same
  for the other fields, whose whole table is no bigger than the batch),
  ``batch`` (leading dim is the batch: row gathers and per-example work),
  ``sort``, and ``other``;
- the ``top`` ops by total time.

One JSON line per figure; the top ops as text lines after them.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CONTAINERS = ("while", "conditional", "call")


def parse_op(text: str):
    """``(opcode, leading dim of the (first) output)`` of one HLO line such
    as ``%fusion.3 = f32[7046547,10]{0,1:T(8,128)} fusion(...)``."""
    _, _, rest = text.partition(" = ")
    if rest.startswith("("):          # tuple output: skip to its ')'
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        out, tail = rest[1:i], rest[i + 1:]
    else:
        out, _, tail = rest.partition(" ")
    opcode = tail.strip().split("(", 1)[0]
    dims = out.split("[", 1)[1].split("]", 1)[0] if "[" in out else ""
    lead = dims.split(",", 1)[0]
    return opcode, int(lead) if lead.isdigit() else None


def summarize(trace_dir: str, *, batch: int, vocabs, host_s: float,
              top: int = 12) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    plane = next((p for p in data.planes
                  if p.name.startswith("/device:TPU:0")), None)
    if plane is None:
        raise SystemExit(f"{files[-1]} has no /device:TPU:0 plane")
    lines = {line.name: line for line in plane.lines}
    modules_ms = sum(e.duration_ns for e in lines["XLA Modules"].events) / 1e6
    vocabs = set(vocabs) - {batch}
    groups = defaultdict(float)
    per_op = defaultdict(lambda: [0.0, 0])
    for e in lines["XLA Ops"].events:
        opcode, lead = parse_op(e.name)
        if opcode in CONTAINERS:
            continue
        ms = e.duration_ns / 1e6
        group = ("sort" if opcode == "sort" else
                 "table" if lead in vocabs and lead > batch else
                 "small_table" if lead in vocabs else
                 "batch" if lead == batch else "other")
        groups[group] += ms
        per_op[e.name[:110]][0] += ms
        per_op[e.name[:110]][1] += 1
    ops_ms = sum(groups.values())
    fig = {
        "trace": files[-1], "batch": batch, "host_s": host_s,
        "modules_ms": modules_ms,
        "busy_over_host_window": modules_ms / (1e3 * host_s),
        "ops_ms": ops_ms,
        "share": {g: groups[g] / ops_ms
                  for g in ("table", "small_table", "batch", "other",
                            "sort")},
    }
    print(json.dumps(fig), flush=True)
    for name, (ms, n) in sorted(per_op.items(), key=lambda kv: -kv[1][0])[:top]:
        print(f"  {ms:10.3f} ms  x{n:<5d} {name}", flush=True)
    return fig


def capture(out: str, cases, seed: int):
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.configs.deepfm_criteo import CRITEO_VOCABS
    from repro.embed import store_for
    from repro.launch import train as train_lib
    from repro.models import ctr
    from repro.train import engine as engine_lib

    train_lib.use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform}")
    data = None
    for batch, scan, chunks in cases:
        argv = ["--task", "ctr", "--arch", "deepfm-criteo",
                "--base-batch", "1024", "--base-lr", "1e-4",
                "--base-l2", "1e-5", "--rule", "cowclip",
                "--placement", "sparse", "--batch", str(batch),
                "--samples", "262144", "--seed", str(seed)]
        args = train_lib.parse_args(argv)
        if data is None:
            data = train_lib.make_ctr_data(args)
        train, _ = data.split(0.9)
        cfg = train_lib.make_ctr_config(args, data, "sparse")
        bundle = train_lib.make_ctr_bundle(args, cfg, store_for(cfg),
                                           len(train))
        runner = engine_lib.make_chunk_runner(
            engine_lib.resolve_scan_step(bundle, bundle.step))
        params = bundle.prepare(ctr.init(jax.random.key(seed), cfg))
        state = bundle.init(params)
        epoch = [0]

        def steps(params, state, n):
            while n > 0:
                params, state, ran, _ = engine_lib.run_epoch(
                    runner, params, state, train, batch, scan,
                    seed=seed + epoch[0], max_steps=n)
                epoch[0] += 1
                n -= ran
            return jax.block_until_ready((params, state))

        params, state = steps(params, state, scan)          # compile
        trace_dir = f"{out}_b{batch}"
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            params, state = steps(params, state, scan * chunks)
            host_s = time.perf_counter() - t0
        print(json.dumps({"batch": batch, "steps": scan * chunks,
                          "host_s": host_s,
                          "s_per_step": host_s / (scan * chunks)}),
              flush=True)
        del params, state
        summarize(trace_dir, batch=batch, vocabs=CRITEO_VOCABS,
                  host_s=host_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/trace",
                    help="capture: traces go to <out>_b<batch>")
    ap.add_argument("--cases", default="8192:8:2,131072:1:2",
                    help="capture: BATCH:SCAN:CHUNKS, comma-separated")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--summarize", metavar="DIR",
                    help="only re-read the trace in DIR (with --batch and "
                         "--host-s)")
    ap.add_argument("--batch", type=int)
    ap.add_argument("--host-s", type=float)
    args = ap.parse_args(argv)
    if args.summarize:
        if args.batch is None or args.host_s is None:
            ap.error("--summarize needs --batch and --host-s")
        sys.path.insert(0, str(ROOT / "src"))
        from repro.configs.deepfm_criteo import CRITEO_VOCABS

        summarize(args.summarize, batch=args.batch, vocabs=CRITEO_VOCABS,
                  host_s=args.host_s)
    else:
        cases = [tuple(int(x) for x in c.split(":"))
                 for c in args.cases.split(",")]
        capture(args.out, cases, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
