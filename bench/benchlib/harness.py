"""One benchmark run of a training cell, in pieces that ``run`` chains:
``set_up``, ``timed_window``, ``traced_window`` and ``check_first_chunk``.

The window drives the program's own objects, those ``repro.launch.train``'s
``run_ctr`` builds: ``make_ctr_config`` and ``make_ctr_bundle`` over
``store_for(cfg)`` with the configuration's own CLI arguments,
``engine.make_chunk_runner(resolve_scan_step(bundle))``, and chunks from
``data.prefetch.prefetch`` over ``chunk_epoch`` of a seeded host pool,
chained over epochs with shuffle seed ``seed + epoch``.

The program runs at the configuration's ``compute_dtype`` and
``matmul_precision``, which is JAX's default matmul precision for the whole
process: the program's products follow it.

Set-up makes the pool, the weights (on the device, in one jitted call, from
the seed) and the state, and runs the first chunk through the runner: that
compiles the one chunk program and is the chunk the check compares. The
program's ``flush`` and the per-leaf norms read from it are the check's
own work, timed apart and left out of ``setup_s``. The window then loops
over chunks until ``seconds`` have passed and ends on a chunk boundary
after ``block_until_ready``.

After the window the peak device memory is read, the program's state is
freed, and the plain reference (``benchlib.reference``) replays the first
chunk's rows, which it draws again from the seed on its own.
"""

from __future__ import annotations

import gc
import itertools
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from . import check, gen, reference
from . import trace as trace_lib
from .spec import Spec, peaks_for


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    backend compiles ran, since the last ``lap``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.by_event = dict.fromkeys(self.EVENTS, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration
            self.by_event[event] += duration
            self.compiles += event == self.EVENTS[-1]

    def lap(self) -> tuple:
        out = (self.seconds, self.compiles)
        self.seconds, self.compiles = 0.0, 0
        return out

    def split(self) -> str:
        """Seconds by phase since the last call: trace, lower, compile."""
        out = " / ".join(f"{v:.3f}" for v in self.by_event.values())
        self.by_event = dict.fromkeys(self.EVENTS, 0.0)
        return out


def log(tag: str, msg: str):
    print(f"[bench {tag}] {msg}", file=sys.stderr, flush=True)


def program_args(spec: Spec, seed: int) -> list:
    tr, conf = spec.traffic, spec.config
    return [*conf["cli"], "--compute-dtype", conf["compute_dtype"],
            "--batch", str(tr["batch"]), "--scan-steps", str(tr["scan_steps"]),
            "--seed", str(seed)]


def _check_program_config(cfg, args, conf: dict):
    """The program's CTRConfig and arguments must be the configuration."""
    want = {"name": conf["model"], "vocab_sizes": tuple(conf["vocab_sizes"]),
            "n_dense": conf["n_dense"], "emb_dim": conf["emb_dim"],
            "mlp_dims": tuple(conf["mlp_dims"]), "n_cross": conf["n_cross"],
            "placement": conf["placement"],
            "compute_dtype": conf["compute_dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    got["vocab_sizes"] = tuple(got["vocab_sizes"])
    got["mlp_dims"] = tuple(got["mlp_dims"])
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    for key, arg in (("base_batch", "base_batch"), ("base_lr", "base_lr"),
                     ("base_l2", "base_l2"), ("rule", "rule"),
                     ("clip_zeta", "zeta")):
        if getattr(args, arg) != conf[key]:
            bad[key] = (getattr(args, arg), conf[key])
    if bad:
        raise ValueError(f"the program's config is not the configuration "
                         f"(program, file): {bad}")


def _flat(tree, prefix=""):
    """``{path: leaf}`` of a nested dict."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        out.update(_flat(v, p + "/") if isinstance(v, dict) else {p: v})
    return out


def _adam_mu(dense_state, jax):
    for node in jax.tree.leaves(dense_state,
                                is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise ValueError("the dense optimizer state has no Adam first moment")


TRACE_S = 2.0


def control_check(spec: Spec, seed: int) -> dict:
    """The control in the program's place: the reference with its tower's
    products in three bfloat16 passes (``reference.high_dot``, JAX's
    ``high`` precision), read against the reference as a run reads the
    program. Returns ``check.readings``."""
    import jax

    conf = spec.config
    pool = gen.make_pool(conf["train_rows"], conf["vocab_sizes"],
                         conf["n_dense"], zipf_a=spec.traffic["zipf_a"],
                         seed=seed)
    key = gen.seed32(seed, 4)
    low = reference_readings(spec, pool, key, seed, jax,
                             dot=reference.high_dot)
    return check.readings(low, reference_readings(spec, pool, key, seed, jax))


class _Loop:
    """The timed loop: fetch the next chunk, dispatch it, then wait for the
    chunk before it. Two chunks are in flight at most: the device always
    has the next one queued, and the host never runs further ahead."""

    def __init__(self, jax, runner, feed, k):
        self.jax, self.runner, self.feed, self.k = jax, runner, feed, k
        self.steps, self.wait = 0, 0.0

    def run(self, params, state, done):
        note = self.jax.profiler.TraceAnnotation
        prev, chunks = None, 0
        while True:
            with note("bench.next_chunk"):
                a = time.perf_counter()
                chunk = next(self.feed)
                self.wait += time.perf_counter() - a
            with note("bench.dispatch"):
                params, state, aux = self.runner(params, state, chunk)
            del chunk
            self.steps += self.k
            chunks += 1
            if prev is not None:
                with note("bench.block"):
                    prev.block_until_ready()
            prev = aux["loss"]
            if done(chunks):
                break
        with note("bench.block"):
            self.jax.block_until_ready((params, state, aux))
        return params, state, aux


def first_chunk_rows(n: int, batch: int, steps: int, seed: int):
    """Row indices of an epoch's first ``steps`` batches, ``[steps,
    batch]``: the shuffle of ``arange(n)`` with ``default_rng(seed)``. The
    reference draws its rows with this copy of the epoch order, not from
    the program's feed, so a feed that drops or repeats rows shows."""
    order = np.arange(n)
    np.random.default_rng(seed).shuffle(order)
    return order[: batch * steps].reshape(steps, batch)


def reference_readings(spec: Spec, pool, key: int, seed: int, jax,
                       dot=None) -> dict:
    """Losses, per-leaf moment norms and change norms of the reference over
    the first chunk; ``dot`` replaces the tower's matrix product."""
    import jax.numpy as jnp

    conf, tr = spec.config, spec.traffic
    b, k = tr["batch"], tr["scan_steps"]
    n = len(pool.labels)
    hp = reference.hyper(conf, b, max(1, n // b))
    step = (reference.make_step(conf, hp) if dot is None
            else reference.make_step(conf, hp, dot))
    params = reference.init_params(conf, key)
    opt = reference.init_opt(params)
    losses = []
    for i, idx in enumerate(first_chunk_rows(n, b, k, seed)):
        batch = {"ids": jnp.asarray(pool.ids[idx]),
                 "dense": jnp.asarray(pool.dense[idx]),
                 "labels": jnp.asarray(pool.labels[idx])}
        params, opt, value = step(params, opt, batch, jnp.int32(i + 1))
        losses.append(value)
    norms = jax.jit(lambda p, m, s: (
        jax.tree.map(lambda a, b: jnp.linalg.norm((a - b).ravel()), p,
                     reference.init_tree(conf, s)),
        jax.tree.map(lambda a: jnp.linalg.norm(a.ravel()), m)))
    change, m = jax.device_get(norms(params, opt["m"], jnp.uint32(key)))
    return {"losses": [float(x) for x in jax.device_get(losses)],
            "m": {p: float(v) for p, v in _flat(m).items()},
            "change": {p: float(v) for p, v in _flat(change).items()}}


@dataclass
class Program:
    """The program as set-up leaves it for the window: its compiled chunk
    runner, state and feed, and what set-up read from the first chunk."""
    jax: Any
    spec: Spec
    seed: int
    tag: str
    pool: Any
    key: int
    runner: Any
    feed: Any
    params: Any
    state: Any
    readings: dict          # losses, per-leaf norms of the first chunk
    setup_s: float
    compile_s: float
    clock: CompileClock


def set_up(spec: Spec, seed: int, t_start: float,
           wrap_runner: Optional[Callable] = None) -> Program:
    """Pool, weights, state and the compiled chunk program, as the
    configuration states them; the first chunk through the window's own
    runner and feed; then the check's readings of it (timed apart).
    ``wrap_runner`` plants a fault under the runner, for the tests."""
    import jax
    import jax.numpy as jnp

    from repro.data.prefetch import chunk_epoch, prefetch
    from repro.data.synthetic import CTRDataset
    from repro.embed import store_for
    from repro.launch import train as train_lib
    from repro.models import ctr as ctr_lib
    from repro.train import engine

    devices = jax.devices()
    dev = devices[0]
    tag = f"{dev.platform} {dev.device_kind} x{len(devices)}"
    clock = CompileClock(jax)
    conf, tr = spec.config, spec.traffic
    b, k, n = tr["batch"], tr["scan_steps"], conf["train_rows"]
    if n % (b * k):
        raise ValueError(f"train_rows {n} is not a multiple of batch x "
                         f"scan_steps {b * k}: a ragged chunk would compile")
    jax.config.update("jax_default_matmul_precision", conf["matmul_precision"])

    pool_s = time.perf_counter()
    pool = gen.make_pool(n, conf["vocab_sizes"], conf["n_dense"],
                         zipf_a=tr["zipf_a"], seed=seed)
    pool_s = time.perf_counter() - pool_s
    ds = CTRDataset(pool.ids, pool.dense, pool.labels,
                    tuple(conf["vocab_sizes"]))
    args = train_lib.parse_args(program_args(spec, seed))
    cfg = train_lib.make_ctr_config(args, ds, args.placement)
    _check_program_config(cfg, args, conf)
    bundle = train_lib.make_ctr_bundle(args, cfg, store_for(cfg), n)
    runner = engine.make_chunk_runner(engine.resolve_scan_step(bundle))
    if wrap_runner is not None:
        runner = wrap_runner(runner)
    key = gen.seed32(seed, 4)
    params = reference.init_params(conf, key)
    want = jax.eval_shape(lambda: ctr_lib.init(jax.random.key(0), cfg))
    if (jax.tree.structure(want) != jax.tree.structure(params)
            or any(a.shape != b_.shape for a, b_ in
                   zip(jax.tree.leaves(want), jax.tree.leaves(params)))):
        raise ValueError("the program's parameters differ in form from the "
                         "configuration's")
    params = bundle.prepare(params)
    state = jax.jit(bundle.init)(params)
    epochs = (chunk_epoch(ds, b, k, seed=seed + e) for e in itertools.count())
    feed = prefetch(itertools.chain.from_iterable(epochs), buffer_size=2)
    params, state, aux = runner(params, state, next(feed))
    first_losses = [float(x) for x in jax.device_get(aux["loss"])]
    jax.block_until_ready((params, state))
    compile_s, compiles = clock.lap()
    split = clock.split()

    # the check's own work: flush, and per-leaf norms of the first chunk
    t_check = time.perf_counter()
    params, state = bundle.flush(params, state)
    norms = jax.jit(lambda p, s, key: (
        jax.tree.map(lambda a, b_: jnp.linalg.norm((a - b_).ravel()), p,
                     reference.init_tree(conf, key)),
        jax.tree.map(lambda a: jnp.linalg.norm(a.ravel()),
                     {"embed": s["m"], "dense": _adam_mu(s["dense"], jax)})))
    change, m = jax.device_get(norms(params, state, jnp.uint32(key)))
    readings = {"losses": first_losses,
                "m": {p: float(v) for p, v in _flat(m).items()},
                "change": {p: float(v) for p, v in _flat(change).items()}}
    check_s = time.perf_counter() - t_check
    clock.lap()
    setup_s = time.perf_counter() - t_start - check_s
    log(tag, f"set-up {setup_s:.3f} s (compile {compile_s:.3f} s, "
             f"{compiles} compiles, trace / lower / backend {split} s; "
             f"pool {pool_s:.3f} s; check {check_s:.3f} s apart)")
    return Program(jax, spec, seed, tag, pool, key, runner, feed, params,
                   state, readings, setup_s, compile_s, clock)


def timed_window(p: Program, seconds: float) -> dict:
    """Chunks until ``seconds`` have passed, ending on a chunk boundary at
    device completion."""
    loop = _Loop(p.jax, p.runner, p.feed, p.spec.traffic["scan_steps"])
    t0 = time.perf_counter()
    p.params, p.state, aux = loop.run(p.params, p.state, lambda n: (
        time.perf_counter() - t0 >= seconds))
    t1 = time.perf_counter()
    last = [float(x) for x in p.jax.device_get(aux["loss"])]
    compile_s, compiles = p.clock.lap()
    stats = p.jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(p.tag, f"window {t1 - t0:.6f} s, {loop.steps} steps, {compiles} "
               f"compiles in it ({compile_s:.3f} s); peak {peak} bytes")
    return {"seconds": t1 - t0, "steps": loop.steps, "wait_s": loop.wait,
            "finite": all(math.isfinite(x) for x in last), "peak": peak,
            "compiles": compiles, "compile_s": compile_s}


def traced_window(p: Program, chunk_s: float) -> dict:
    """A traced run of its own after the window: the chunks that fill about
    ``TRACE_S`` seconds at the window's pace, at least two; the reduced
    trace, and the steps and rows it holds."""
    jax = p.jax
    dev = jax.devices()[0]
    conf, tr = p.spec.config, p.spec.traffic
    n_chunks = max(2, math.ceil(TRACE_S / chunk_s))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    loop = _Loop(jax, p.runner, p.feed, tr["scan_steps"])
    try:
        with jax.profiler.trace(trace_dir):
            p.params, p.state, _ = loop.run(p.params, p.state,
                                            lambda n: n >= n_chunks)
        ops, spans = trace_lib.load(trace_dir,
                                    f"/device:{dev.platform.upper()}:0")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    # from the device's first op to the end of the last wait on it
    w0 = min(e.start_ns for e in ops)
    w1 = max((e.start_ns + e.dur_ns for e in spans if e.name == "bench.block"),
             default=max(e.start_ns + e.dur_ns for e in ops))
    reduced = trace_lib.reduce(ops, spans, batch=tr["batch"],
                               vocabs=conf["vocab_sizes"], window=(w0, w1))
    log(p.tag, f"traced {loop.steps} steps: busy {reduced['busy_s']} s of "
               f"{reduced['window_s']} s")
    return {"trace": reduced, "steps": loop.steps,
            "rows": loop.steps * tr["batch"]}


def check_first_chunk(p: Program) -> tuple:
    """Free the program's state, run the plain reference over the first
    chunk, and compare: ``(readings, {name: {value, limit}}, ok)``."""
    p.feed.close()
    p.runner = p.feed = p.params = p.state = None
    gc.collect()
    ref = reference_readings(p.spec, p.pool, p.key, p.seed, p.jax)
    read = check.readings(p.readings, ref)
    read["_ref_losses"] = ref["losses"]
    compared, ok = check.verdict(read, p.spec.limits)
    for name, v in compared.items():
        log(p.tag, f"check {name} {v['value']!r} limit {v['limit']!r} "
                   f"(worst at {read[name]['at']})")
    return read, compared, ok


def run(spec: Spec, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """One benchmark run; returns the result line's object (``correct``,
    ``attempted``, ``failed``, ``metrics``, ``device``, with ``trace``
    ``breakdown``, and last ``check``)."""
    p = set_up(spec, seed, t_start)
    w = timed_window(p, seconds)
    traced = None
    if trace:
        k = spec.traffic["scan_steps"]
        traced = traced_window(p, w["seconds"] * k / max(w["steps"], 1))
    read, compared, ok = check_first_chunk(p)
    correct = bool(ok and w["finite"])

    dev = p.jax.devices()[0]
    b = spec.traffic["batch"]
    metrics = {}
    if not trace:
        values = {"rows_per_s": (w["steps"] * b / w["seconds"], "rows/s"),
                  "peak_hbm_gb": (w["peak"] / 1e9 if w["peak"] else None,
                                  "GB"),
                  "setup_s": (p.setup_s, "s")}
        metrics = {m: {"value": values[m][0], "unit": values[m][1]}
                   for m in spec.end_to_end if values[m][0] is not None}
    else:
        r = {"compile_s": p.compile_s, "input_wait_s": w["wait_s"],
             "window_steps": w["steps"], "steps": traced["steps"],
             "rows": traced["rows"], "trace": traced["trace"],
             "chips": spec.chips, "peaks": peaks_for(spec, dev.device_kind),
             "flops_per_row": spec.counts.flops_per_row(spec.config),
             "least_bytes_per_step": _least_bytes(spec, p.pool, seed)}
        for name, mod in spec.per_layer.items():
            value = mod.read(r)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
    result = {"correct": correct, "attempted": w["steps"],
              "failed": 0 if w["finite"] else w["steps"], "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(p.jax.devices()),
                         "memory_peak_bytes": w["peak"]}}
    if traced is not None:
        reduced = traced["trace"]
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["readings"] = {"first_losses": p.readings["losses"],
                          "ref_losses": read["_ref_losses"],
                          "left_out": read["_left_out"],
                          "diag": read["_diag"],
                          "window_compiles": w["compiles"],
                          "window_compile_s": w["compile_s"]}
    result["check"] = compared
    return result


def _least_bytes(spec: Spec, pool, seed: int) -> float:
    """Least bytes per step, averaged over the unique ids of the first
    chunk's batches: every chunk draws its batches alike from the pool."""
    tr = spec.traffic
    rows = first_chunk_rows(len(pool.labels), tr["batch"], tr["scan_steps"],
                            seed)
    return float(np.mean([spec.counts.least_bytes_per_step(
        spec.config, [len(np.unique(pool.ids[r, f]))
                      for f in range(pool.ids.shape[1])], tr["batch"])
        for r in rows]))
