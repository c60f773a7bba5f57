#!/usr/bin/env python3
"""Smoke run of the CTR training path on TPU chips, through the CLI's own
entry points (``repro.launch.train.parse_args`` / ``run_ctr``).

    python chip_smoke.py            # one chip: phases a, b and d
    python chip_smoke.py --chips 4  # four chips: the mesh phase only

Phases on one chip:

  a  DeepFM at deepfm-criteo widths (26 fields, 33,762,591 ids, 13 dense
     features, dim 10, MLP 3x400), sparse placement, CowClip rule, scan
     engine x8, batch 8,192, 16 steps.
  b  The same model at the paper's top batch, 131,072, for 2 steps.
  d  Five default fields, base l2 0, base lr 1e-3: the sparse and fused
     placements against the dense substrate oracle over 8 steps, params
     within 1e-5.

With ``--chips 4``: sharded_sparse on a 2x2 (data, model) mesh at
deepfm-criteo widths against the sparse placement on one chip, from the
same init and data (base l2 0), both with f32 matmuls: params within 1e-5
after one step and after eight, per-step losses within 1e-5; prints each
device's bytes in use while the sharded tables are live.

Everything runs in this one process, which owns the chips. Data is made
from ``--seed``. Weights are random (seeded) and the data synthetic, so
AUC says nothing about the model's quality. Any failed check or error
exits non-zero; the last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# the paper's DeepFM/Criteo job and its base recipe (batch 1K, Adam lr
# 1e-4, L2 1e-5), scaled to the run's batch by the CowClip rule
CRITEO = ["--task", "ctr", "--arch", "deepfm-criteo", "--base-batch", "1024",
          "--base-lr", "1e-4", "--base-l2", "1e-5"]
SMOKE_ROWS = 262_144          # one dataset serves phases a and b
EXACT_TOL = 1e-5              # the CPU tests' param tolerance


def fail(msg: str):
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, read per phase."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration

    def lap(self) -> float:
        seconds, self.seconds = self.seconds, 0.0
        return seconds


def memory(jax, device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def train_phase(tag, argv, data, clock, jax, *, steps):
    """One ``run_ctr`` call; prints its figures and checks them."""
    from repro.launch import train as train_lib

    res = train_lib.run_ctr(train_lib.parse_args(argv), data=data)
    k0, t0 = res.first_chunk
    check(res.steps == steps, f"[{tag}] ran {res.steps} steps, not {steps}")
    check(len(res.losses) == steps, f"[{tag}] {len(res.losses)} losses")
    first, last = res.losses[0], res.losses[-1]
    check(math.isfinite(first) and math.isfinite(last),
          f"[{tag}] loss not finite: {first} .. {last}")
    auc = res.final_eval["auc"]
    check(math.isfinite(auc) and 0.0 < auc < 1.0, f"[{tag}] AUC {auc}")
    fig = {
        "compile_s": clock.lap(),
        "first_chunk_steps": k0, "first_chunk_s": t0,
        "s_per_step_after_first_chunk": (
            (res.train_seconds - t0) / (steps - k0) if steps > k0 else None),
        "loss_first": first, "loss_last": last, "auc": auc,
        **memory(jax, jax.devices()[0]),
    }
    print(f"[{tag}] " + json.dumps(fig), flush=True)
    return res


def max_abs_err(jax, a, b) -> tuple:
    """``(max |a - b|, path of the leaf where it is)`` over two host param
    trees; rows of ``a`` past ``b``'s (the sharded placements' zero pad
    rows) are left out."""
    import numpy as np

    def err(x, y):
        x, y = np.asarray(x), np.asarray(y)
        return float(np.max(np.abs((x[:y.shape[0]] if y.ndim else x) - y)))

    return max((err(x, y), jax.tree_util.keystr(path)) for (path, x), y in
               zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)))


def exactness(seed, clock, jax):
    """Phase d: sparse and fused against the dense substrate oracle."""
    from repro.launch import train as train_lib

    # the CPU tests' lr: at the CLI's default 2e-2 the dynamics amplify the
    # fused update's last-bit rounding differences past 1e-5 within 8 steps
    argv = ["--task", "ctr", "--base-l2", "0", "--base-lr", "1e-3",
            "--batch", "4096", "--samples", "65536", "--steps", "8",
            "--epochs", "1", "--seed", str(seed)]
    data = train_lib.make_ctr_data(train_lib.parse_args(argv))
    runs = {p: train_lib.run_ctr(
                train_lib.parse_args(argv + ["--placement", p]), data=data)
            for p in ("substrate", "sparse", "fused")}
    oracle = jax.device_get(runs["substrate"].params)
    errs = {p: max_abs_err(jax, jax.device_get(runs[p].params), oracle)
            for p in ("sparse", "fused")}
    print("[d] " + json.dumps({"max_abs_err_vs_substrate": errs,
                               "compile_s": clock.lap()}), flush=True)
    for p, (err, leaf) in errs.items():
        check(err <= EXACT_TOL,
              f"[d] {p} vs substrate max abs err {err} at {leaf}")


def four_chips(seed, clock, jax):
    """sharded_sparse on a 2x2 mesh vs sparse on one chip, from the same
    init and data, base l2 0, both with f32 matmuls.

    The params agree within 1e-5 after one step and after eight, and the
    per-step losses within 1e-5. A row the mesh updated wrongly or not at
    all would be off by one Adam step, the learning rate (1e-4).

    The "data" axis sums every gradient in another order than one chip
    does, and training can grow those last-bit differences: on four
    virtual CPU devices (field 20 at its 7,046,547 ids, the others cut to
    20,000) the f32 params end eight steps 4.1e-5 apart, and on the chips
    eight steps in one scan dispatch ended 7.5e-5 apart. The same CPU
    comparison in float64 (``tests/mesh_f64_main.py``) stays within 1e-16,
    so that drift is rounding, not a fault of the mesh path. With one step
    per dispatch, as here, the chips stay within 3e-8 after eight. The
    TPU's default matmul precision, which rounds f32 inputs to bf16, would
    turn the last-bit differences into 2**-8 relative steps; f32 keeps the
    check on the sharding.

    One step per dispatch also lets the one- and eight-step runs of a
    placement share one compiled program."""
    from repro.launch import train as train_lib

    argv = CRITEO + ["--rule", "cowclip", "--base-l2", "0", "--batch", "8192",
                     "--samples", "131072", "--epochs", "1",
                     "--scan-steps", "1", "--seed", str(seed)]
    data = train_lib.make_ctr_data(train_lib.parse_args(argv))
    sharded = ["--placement", "sharded_sparse", "--mesh", "2,2"]
    sparse = ["--placement", "sparse"]
    runs = {}
    with jax.default_matmul_precision("float32"):
        for steps in (1, 8):
            n = ["--steps", str(steps)]
            res = train_phase(f"4chip-sharded_sparse-{steps}",
                              argv + sharded + n, data, clock, jax,
                              steps=steps)
            if steps == 8:
                per_device = {str(d.id): memory(jax, d)["bytes_in_use"]
                              for d in jax.devices()}
                print("[4chip] bytes_in_use per device with the sharded "
                      "tables live: " + json.dumps(per_device), flush=True)
            runs["sharded", steps] = (jax.device_get(res.params), res.losses)
            del res
            res = train_phase(f"4chip-sparse-{steps}", argv + sparse + n,
                              data, clock, jax, steps=steps)
            runs["sparse", steps] = (jax.device_get(res.params), res.losses)
            del res
    # sharded tables carry zero pad rows past the vocab; compare real rows
    err1, leaf1 = max_abs_err(jax, runs["sharded", 1][0], runs["sparse", 1][0])
    err8, leaf8 = max_abs_err(jax, runs["sharded", 8][0], runs["sparse", 8][0])
    loss_err = max(abs(a - b) for a, b in zip(runs["sharded", 8][1],
                                              runs["sparse", 8][1]))
    print("[4chip] " + json.dumps({
        "max_abs_err_params_1_step": err1, "at_1": leaf1,
        "max_abs_err_losses_8_steps": loss_err,
        "max_abs_err_params_8_steps": err8, "at_8": leaf8}), flush=True)
    check(err1 <= EXACT_TOL,
          f"[4chip] sharded_sparse vs sparse params after 1 step: {err1}")
    check(err8 <= EXACT_TOL,
          f"[4chip] sharded_sparse vs sparse params after 8 steps: {err8}")
    check(loss_err <= EXACT_TOL,
          f"[4chip] sharded_sparse vs sparse losses over 8 steps: {loss_err}")
    held = list(per_device.values())
    check(all(isinstance(b, int) for b in held)
          and max(held) < 2 * min(held),
          "[4chip] tables are not split across the devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "launch" / "train.py").is_file():
        fail(f"the repro package is not at {SRC}")
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch import train as train_lib

    train_lib.use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")
    print(f"[chip_smoke] {len(devices)} x {dev.device_kind}", flush=True)
    clock = CompileClock(jax)

    if args.chips == 4:
        four_chips(args.seed, clock, jax)
    else:
        argv_a = CRITEO + [
            "--placement", "sparse", "--rule", "cowclip", "--engine", "scan",
            "--scan-steps", "8", "--batch", "8192", "--steps", "16",
            "--epochs", "1", "--samples", str(SMOKE_ROWS),
            "--seed", str(args.seed)]
        data = train_lib.make_ctr_data(train_lib.parse_args(argv_a))
        train_phase("a", argv_a, data, clock, jax, steps=16)
        argv_b = list(argv_a)
        argv_b[argv_b.index("--batch") + 1] = "131072"
        argv_b[argv_b.index("--steps") + 1] = "2"
        argv_b[argv_b.index("--epochs") + 1] = "2"
        train_phase("b", argv_b, data, clock, jax, steps=2)
        exactness(args.seed, clock, jax)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
