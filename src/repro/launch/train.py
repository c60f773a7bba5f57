"""Distributed training driver (``--arch`` selectable, mesh-aware).

On real hardware this launches the pjit'd train step over
``make_production_mesh()``; on the CPU container it runs the same code path
on a 1x1 host mesh (same shardings, trivially satisfied), which is how the
examples exercise the full production path end-to-end.

Two families:
  * CTR (the paper's own task): DeepFM/W&D/DCN/DCNv2 on synthetic-Zipf or
    Criteo TSV data, CowClip large-batch recipe.
  * LM: any assigned architecture (reduced or full), CowClip on the token
    table, next-token loss on a Zipf token stream.

Usage:
  PYTHONPATH=src python -m repro.launch.train --task ctr --model deepfm \
      --batch 8192 --epochs 2 --rule cowclip
  # the paper's DeepFM at Criteo widths (26 fields, ~33.8M ids) on one chip:
  PYTHONPATH=src python -m repro.launch.train --task ctr --arch deepfm-criteo \
      --placement sparse --batch 8192 --samples 262144 --steps 16
  # mesh-sharded embeddings on 8 virtual CPU devices (2-way data, 4-way row):
  PYTHONPATH=src python -m repro.launch.train --task ctr --placement sharded \
      --mesh 2,4 --host-devices 8 --batch 8192 --epochs 1
  # the sharded+sparse hybrid (per-shard unique-id updates) on the same mesh:
  PYTHONPATH=src python -m repro.launch.train --task ctr \
      --placement sharded_sparse --mesh 2,4 --host-devices 8 --batch 8192
  # streaming online training on the hot/cold two-tier placement:
  PYTHONPATH=src python -m repro.launch.train --task ctr --mode stream \
      --placement hotcold --hot-capacity 4096 --batch 8192 --steps 200
  PYTHONPATH=src python -m repro.launch.train --task lm --arch gemma3-12b \
      --reduced --steps 100
"""

from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduce_config
from ..core import apply_updates, build_optimizer, scale_hyperparams
from ..data import make_ctr_dataset, make_lm_tokens, load_criteo_tsv
from ..models import ctr as ctr_lib, embedding, lm
from ..train import checkpoint, train_ctr
from . import mesh as mesh_lib
from .mesh import make_ctr_mesh, parse_mesh


MESH_PLACEMENTS = ("sharded", "sharded_sparse")

# the five synthetic fields CTR runs use when --arch names no CTR config
DEFAULT_VOCABS = (30000, 80000, 5000, 1000, 200)

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    stands. Otherwise the cache lives in ``<checkout>/.jax_cache``: a fixed
    path, since the path is part of what a later run must find again.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def resolve_placement(placement, sparse_flag, *,
                      warn=print) -> "str | None":
    """Combine ``--placement`` with the deprecated ``--sparse`` alias.

    ``--sparse`` is exactly ``--placement sparse``; passing both with a
    different placement is a hard error (the two knobs used to be able to
    disagree silently — e.g. ``--sparse --placement sharded`` trained
    sharded while cfg.sparse claimed otherwise). Documented in docs/cli.md.
    """
    if sparse_flag:
        if placement is not None and placement != "sparse":
            raise SystemExit(
                f"--sparse conflicts with --placement {placement}: --sparse "
                "is a deprecated alias for --placement sparse; drop one of "
                "the two flags")
        warn("[train] --sparse is deprecated; use --placement sparse")
        return "sparse"
    return placement


def ctr_arch(arch: str) -> Optional[ctr_lib.CTRConfig]:
    """The registered CTR config ``--arch`` names, or None for an LM arch
    (CTR runs then use the five synthetic ``DEFAULT_VOCABS`` fields)."""
    cfg = get_config(arch)
    return cfg if isinstance(cfg, ctr_lib.CTRConfig) else None


def make_ctr_data(args):
    """The run's dataset: ``--criteo`` TSV rows, else the seeded synthetic
    generator at the ``--arch`` config's vocabs (or the default fields)."""
    if args.criteo:
        return load_criteo_tsv(args.criteo, max_rows=args.max_rows)
    arch = ctr_arch(args.arch)
    if arch is not None:
        vocabs, n_dense = arch.vocab_sizes, arch.n_dense
    else:
        vocabs = tuple(v * args.vocab_scale for v in DEFAULT_VOCABS)
        n_dense = 4
    return make_ctr_dataset(args.samples, vocabs, n_dense=n_dense,
                            zipf_a=1.1, seed=args.seed)


def make_ctr_config(args, ds, placement) -> ctr_lib.CTRConfig:
    """Model widths from the ``--arch`` CTR config when it names one, else
    from ``--emb-dim``/``--mlp-dim``; vocabs from the data."""
    arch = ctr_arch(args.arch)
    return ctr_lib.CTRConfig(
        name=args.model, vocab_sizes=ds.vocab_sizes,
        n_dense=ds.dense.shape[1],
        emb_dim=arch.emb_dim if arch else args.emb_dim,
        mlp_dims=arch.mlp_dims if arch else (args.mlp_dim,) * 3,
        emb_sigma=1e-2, sparse=placement == "sparse",
        unique_capacity=args.unique_capacity, placement=placement,
        compute_dtype=args.compute_dtype,
    )


def make_ctr_bundle(args, cfg, store, n_train: int):
    """The run's train-step bundle: the ``--rule`` scaled hyperparameters
    and the CowClip clip (rule cowclip) through ``store``'s placement."""
    hp = scale_hyperparams(
        args.rule, base_lr=args.base_lr, base_l2=args.base_l2,
        base_batch=args.base_batch, batch_size=args.batch,
        base_dense_lr=2 * args.base_lr,
    )
    clip = "adaptive_column" if args.rule == "cowclip" else "none"
    return store.make_bundle(cfg, hp, clip_kind=clip, zeta=args.zeta,
                             warmup_steps=max(1, n_train // args.batch),
                             nonfinite_guard=args.nonfinite_guard)


def run_ctr(args, data=None):
    """Train a CTR model as ``args`` (``parse_args``) says and return the
    ``train.loop.TrainResult``. ``data`` is a ``CTRDataset`` to train on in
    place of ``make_ctr_data(args)`` (one generated set shared by several
    in-process runs)."""
    from ..embed import store_for

    ds = data if data is not None else make_ctr_data(args)
    tr, te = ds.split(0.9)
    placement = resolve_placement(args.placement, args.sparse)
    if args.mode == "stream" and args.steps is None:
        raise SystemExit("[train] --mode stream has no epoch boundary; pass "
                         "--steps to bound the run")
    if args.cold_store != "none":
        if placement != "hotcold":
            raise SystemExit("[train] --cold-store needs --placement hotcold "
                             "(the out-of-core tier backs the hot/cold "
                             "placement)")
        if args.mode != "stream":
            raise SystemExit("[train] --cold-store trains online only; add "
                             "--mode stream (the migration planner runs on "
                             "the stream's worker thread)")
        if args.cold_store == "mmap" and not args.cold_dir:
            raise SystemExit("[train] --cold-store mmap needs --cold-dir "
                             "(the on-disk table directory)")
    if args.snapshot_dir:
        if args.mode != "stream":
            raise SystemExit("[train] --snapshot-dir rides the stream "
                             "cursor; add --mode stream (docs/robustness.md)")
        if args.snapshot_every <= 0:
            raise SystemExit("[train] --snapshot-dir needs --snapshot-every "
                             "N (steps between snapshots)")
    elif args.resume:
        raise SystemExit("[train] --resume needs --snapshot-dir (where the "
                         "snapshots live)")
    cfg = make_ctr_config(args, ds, placement)
    mesh = None
    if placement in MESH_PLACEMENTS:
        mesh = make_ctr_mesh(*(parse_mesh(args.mesh) if args.mesh else (0, 0)))
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(
            jax.eval_shape(lambda: ctr_lib.init(jax.random.key(0), cfg)))
    )
    store = store_for(cfg, mesh=mesh, partition=args.partition,
                      hot_capacity=args.hot_capacity,
                      cold_store=args.cold_store, cold_dir=args.cold_dir,
                      admission=args.admission, half_life=args.half_life)
    engine_desc = (f"scan x{args.scan_steps}" if args.engine == "scan"
                   else "eager")
    mode_desc = ("stream (online, no epochs)" if args.mode == "stream"
                 else "epochs")
    print(f"[train] {args.model}: {n_params/1e6:.1f}M params "
          f"({len(tr)} train rows, batch {args.batch}, rule {args.rule}, "
          f"embedding store {store.describe(cfg)}, engine {engine_desc}, "
          f"mode {mode_desc}, compute {args.compute_dtype})")

    # every placement goes through the one EmbeddingStore bundle interface
    bundle = make_ctr_bundle(args, cfg, store, len(tr))
    import contextlib

    trace_ctx = contextlib.nullcontext()
    if args.profile_trace:
        # per-phase timeline of the train step: each device op carries its
        # named_scope in its op_name. The sparse step's are
        # loop.STEP_SCOPES (dedup / row_gather_catchup / tower_fwd_bwd /
        # row_update_scatter / dense_update / step_counters); the sharded
        # steps add dedup_allgather / embed_lookup_psum / rowgrad_psum /
        # row_update / ..., so collective/compute overlap is read off the
        # trace directly. The input feed writes host spans prefetch.stack
        # (worker thread), prefetch.wait and prefetch.put (consumer), each
        # with the chunk's number. Open the perfetto .gz under
        # <dir>/plugins/perfetto in ui.perfetto.dev.
        trace_ctx = jax.profiler.trace(args.profile_trace,
                                       create_perfetto_trace=True)
        print(f"[train] profiling to {args.profile_trace} (perfetto trace)")
    # -- crash safety: snapshots, resume, deterministic fault injection --
    from ..testing import FaultPlan
    from ..train import snapshot as snapshot_lib

    fault_plan = FaultPlan.from_env()
    snap_mgr = None
    token = snapshot_lib.placement_token(store)
    start_step = 0
    init_state = None
    if args.snapshot_dir:
        snap_mgr = snapshot_lib.SnapshotManager(
            args.snapshot_dir, retain=args.snapshot_retain,
            fault_plan=fault_plan)
    if args.resume:
        restored = snapshot_lib.resume(
            snap_mgr, bundle,
            ctr_lib.init(jax.random.key(args.seed), cfg),
            token=token, cold_dir=args.cold_dir, warn=print)
        if restored is None:
            print(f"[train] --resume: no valid snapshot under "
                  f"{args.snapshot_dir}; starting fresh")
        else:
            p0, s0, start_step, cursor = restored
            init_state = (p0, s0)
            print(f"[train] resumed from snapshot step {start_step} "
                  f"(cursor {cursor})")
    snap_meta = {"placement": token, "snapshot_every": args.snapshot_every,
                 "seed": args.seed, "batch": args.batch}

    snapshot_cb = None
    if snap_mgr is not None or fault_plan is not None:
        # one callback per chunk boundary: snapshot when the cadence says
        # so (capture flushes — the returned pair replaces the live one in
        # BOTH the original and the resumed run, keeping them bitwise
        # aligned), then give the fault plan its step-boundary kill window
        last_snap = [start_step]

        def snapshot_cb(params, state, n):
            if (snap_mgr is not None
                    and n - last_snap[0] >= args.snapshot_every):
                params, state = snapshot_lib.capture(
                    snap_mgr, bundle, params, state, step=n,
                    cursor={"rows_consumed": n * args.batch},
                    meta=snap_meta)
                last_snap[0] = n
            if fault_plan is not None:
                fault_plan.maybe_kill(n)
            return params, state

    def make_events(skip_rows: int = 0):
        # online training: the train split replayed as an endless event
        # stream (the CLI stand-in for a production log tail), re-batched
        # and chunk-stacked on a worker thread; ``skip_rows`` replays the
        # deterministic source up to a resume cursor
        events = stream_lib.synthetic_event_stream(
            tr, rows_per_event=max(1, args.batch // 2), seed=args.seed)
        if skip_rows:
            events = stream_lib.skip_rows(events, skip_rows)
        return events

    stream = None
    make_transform = getattr(bundle, "stream_transform", None)
    if args.mode == "stream":
        from ..data import stream as stream_lib

        if make_transform is not None:
            # async cold store: chunks of 1 step, planned on the worker
            # thread one lookahead window (buffer_size) ahead of the
            # device; the transform carries the step budget so no planned
            # step is ever dropped
            if snap_mgr is None:
                stream = stream_lib.stream_chunks(
                    make_events(start_step * args.batch), args.batch, 1,
                    buffer_size=4,
                    transform=make_transform(max_steps=args.steps),
                    start_rows=start_step * args.batch)
        else:
            stream = stream_lib.stream_chunks(
                make_events(start_step * args.batch), args.batch,
                args.scan_steps if args.engine == "scan" else 1,
                start_rows=start_step * args.batch)

    if args.mode == "stream" and make_transform is not None \
            and snap_mgr is not None:
        # async hotcold snapshots run the stream in segments: the planner
        # races ahead of the device on the worker thread, so a mid-stream
        # flush would wait on eviction handles of planned-but-undispatched
        # steps. Ending each segment's stream at the snapshot boundary
        # (the transform's step budget) dispatches every planned step
        # first, making the flush — and the snapshot — safe. The
        # uninterrupted run takes the same segment boundaries, so resumed
        # and uninterrupted runs stay bitwise identical.
        from ..train.loop import TrainResult, make_eval_fn

        if init_state is None:
            params = bundle.prepare(ctr_lib.init(
                jax.random.key(args.seed), cfg))
            state = bundle.init(params)
        else:
            params, state = init_state
        n = start_step
        t0 = time.perf_counter()
        with trace_ctx:
            while n < args.steps:
                target = min(n + args.snapshot_every, args.steps)
                seg = stream_lib.stream_chunks(
                    make_events(n * args.batch), args.batch, 1,
                    buffer_size=4,
                    transform=make_transform(max_steps=target),
                    start_rows=n * args.batch)
                try:
                    params, state, ran, _ = bundle.stream_driver(
                        params, state, seg, max_steps=None)
                finally:
                    seg.close()
                n += ran
                params, state = snapshot_lib.capture(
                    snap_mgr, bundle, params, state, step=n,
                    cursor={"rows_consumed": n * args.batch},
                    meta=snap_meta)
                if fault_plan is not None:
                    fault_plan.maybe_kill(n)
                if ran == 0:
                    raise SystemExit("[train] stream ended before the "
                                     f"segment target {target}")
        seconds = time.perf_counter() - t0
        final = make_eval_fn(cfg)(params, te) if te is not None else {}
        res = TrainResult(history=[], final_eval=dict(final),
                          seconds=seconds, steps=n, params=params,
                          opt_state=state)
    else:
        with trace_ctx:
            res = train_ctr(cfg, None, tr, te, batch_size=args.batch,
                            epochs=args.epochs, seed=args.seed, log_fn=print,
                            step_bundle=bundle, max_steps=args.steps,
                            engine=args.engine, scan_steps=args.scan_steps,
                            mode=args.mode, stream=stream,
                            init_state=init_state, start_step=start_step,
                            snapshot_cb=snapshot_cb)
    print(f"[train] done: {res.steps} steps in {res.seconds:.1f}s "
          f"-> AUC {100*res.final_eval['auc']:.2f} "
          f"logloss {res.final_eval['logloss']:.4f}")
    if args.checkpoint:
        from ..serve import id_frequencies

        # export strips placement-specific layout (the sharded path's pad
        # rows) so the checkpoint restores against a fresh ctr.init template
        # under any placement; id_freq is the serving hot-cache admission
        # signal (training-time per-field id counts — what CowClip's per-step
        # ``cnt`` sums to over the data)
        checkpoint.save(args.checkpoint, {
            "params": bundle.export(res.params),
            "final_eval": {k: jnp.asarray(v)
                           for k, v in res.final_eval.items()
                           if k in ("auc", "logloss")},
            "id_freq": id_frequencies(tr.ids, cfg.vocab_sizes),
        })
        print(f"[train] final params checkpointed to {args.checkpoint} "
              "(with id_freq for serving)")
    return res


def run_lm(args) -> None:
    from ..sharding.specs import infer_param_shardings
    from .mesh import make_host_mesh

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = make_host_mesh()
    print(f"[train-lm] {cfg.name}: "
          f"{lm.param_counts(cfg)['total']/1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")

    if args.steps is None:
        args.steps = 100
    stream = make_lm_tokens(args.samples, cfg.vocab_size, seed=args.seed)
    seq, batch = args.seq, args.batch
    n_steps_epoch = len(stream) // (seq * batch)

    params = lm.init(jax.random.key(args.seed), cfg)
    hp = scale_hyperparams("cowclip", base_lr=args.base_lr,
                           base_l2=args.base_l2, base_batch=1024,
                           batch_size=batch * seq,
                           base_dense_lr=2 * args.base_lr)
    tx = build_optimizer(hp, warmup_steps=10)
    opt_state = tx.init(params)
    p_shard = infer_param_shardings(params, mesh)
    params = jax.device_put(params, p_shard)

    @jax.jit
    def step(p, o, tokens, prefix):
        def loss(pp):
            return lm.loss_fn(pp, cfg, tokens, prefix)[0]

        l, g = jax.value_and_grad(loss)(p)
        counts = {"tokens": embedding.token_counts(tokens, cfg.padded_vocab)}
        u, o = tx.update(g, o, p, counts=counts)
        return apply_updates(p, u), o, l

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    losses = []
    with mesh:
        for i in range(args.steps):
            off = (i % n_steps_epoch) * seq * batch
            tokens = jnp.asarray(
                stream[off: off + seq * batch].reshape(batch, seq))
            prefix = None
            if cfg.frontend:
                prefix = jnp.asarray(rng.normal(
                    scale=0.1, size=(batch, cfg.n_prefix, cfg.d_model)),
                    cfg.dtype)
            params, opt_state, loss = step(params, opt_state, tokens, prefix)
            losses.append(float(loss))
            if i % max(1, args.steps // 10) == 0:
                print(f"  step {i:4d}: loss {losses[-1]:.4f}")
    dt = time.perf_counter() - t0
    print(f"[train-lm] {args.steps} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if args.checkpoint:
        checkpoint.save(args.checkpoint, params)
        print(f"[train-lm] params checkpointed to {args.checkpoint}")
    assert losses[-1] < losses[0], "training did not reduce loss"


def parse_args(argv=None) -> argparse.Namespace:
    """The CLI's options (``argv=None`` reads ``sys.argv``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--task", choices=("ctr", "lm"), default="ctr")
    # ctr
    ap.add_argument("--model", default="deepfm",
                    choices=ctr_lib.MODEL_NAMES)
    ap.add_argument("--criteo", default=None, help="path to Criteo TSV")
    ap.add_argument("--max-rows", type=int, default=None)
    ap.add_argument("--samples", type=int, default=200_000)
    ap.add_argument("--vocab-scale", type=int, default=1,
                    help="multiply synthetic vocab sizes (86 ~ 100M params)")
    ap.add_argument("--emb-dim", type=int, default=10)
    ap.add_argument("--mlp-dim", type=int, default=400)
    ap.add_argument("--rule", default="cowclip",
                    choices=("no_scale", "sqrt", "sqrt_star", "linear",
                             "n2_lambda", "cowclip"))
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--base-batch", type=int, default=256)
    ap.add_argument("--base-lr", type=float, default=2e-2)
    ap.add_argument("--base-l2", type=float, default=1e-5)
    ap.add_argument("--zeta", type=float, default=1e-5)
    ap.add_argument("--placement", default=None,
                    choices=("substrate", "fused", "sparse", "sharded",
                             "sharded_sparse", "hotcold"),
                    help="embedding store placement (repro.embed); default "
                         "substrate. sharded_sparse = row-sharded tables "
                         "with per-shard unique-id updates (docs/cli.md); "
                         "hotcold = device-resident hot working set over a "
                         "host cold tier (docs/streaming.md)")
    ap.add_argument("--mode", default="epochs", choices=("epochs", "stream"),
                    help="'stream' trains online from an endless event "
                         "stream (no epochs; requires --steps) — the "
                         "streaming path docs/streaming.md describes")
    ap.add_argument("--hot-capacity", type=int, default=4096,
                    help="hotcold placement: device-resident hot rows per "
                         "field (admission by cumulative id frequency)")
    ap.add_argument("--cold-store", default="none",
                    choices=("none", "mem", "mmap"),
                    help="hotcold placement: move the cold tier out of the "
                         "jitted step into a host ColdStore ('mem') or an "
                         "np.memmap directory ('mmap', vocab bounded by "
                         "disk); migration plans on the stream worker "
                         "thread, overlapped with the device step "
                         "(docs/streaming.md). Requires --mode stream")
    ap.add_argument("--cold-dir", default=None, metavar="DIR",
                    help="--cold-store mmap: directory holding the on-disk "
                         "tables (created/reopened; flush/reopen/resume is "
                         "bit-exact)")
    ap.add_argument("--admission", default="cumulative",
                    choices=("cumulative", "decayed"),
                    help="hotcold admission frequency: 'cumulative' sums "
                         "batch counts forever; 'decayed' halves the score "
                         "every --half-life steps (recency-weighted working "
                         "set)")
    ap.add_argument("--half-life", type=int, default=0,
                    help="--admission decayed: steps for an id's frequency "
                         "score to halve (must be > 0)")
    ap.add_argument("--sparse", action="store_true",
                    help="DEPRECATED alias for --placement sparse; errors "
                         "if --placement names anything else")
    ap.add_argument("--unique-capacity", type=int, default=0,
                    help="padded per-field unique-id capacity; 0 = exact "
                         "min(batch, vocab) default")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="mesh axes for --placement sharded/sharded_sparse, "
                         "e.g. '2,4' = 2-way batch split x 4-way table "
                         "row-sharding; default (1, n_devices)")
    ap.add_argument("--partition", default="div", choices=("div", "mod"),
                    help="sharded row mapping: div = contiguous blocks, "
                         "mod = round-robin (balances Zipf-hot low ids)")
    ap.add_argument("--engine", default="scan", choices=("eager", "scan"),
                    help="training hot loop (repro.train.engine): 'scan' "
                         "(default) fuses --scan-steps updates into one "
                         "lax.scan dispatch over prefetched batch chunks; "
                         "'eager' dispatches one jit per step (debugging)")
    ap.add_argument("--scan-steps", type=int, default=8,
                    help="updates fused per dispatch under --engine scan; "
                         "results are bit-identical for any value")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="forward/backward activation dtype; masters, "
                         "CowClip stats and Adam moments stay float32 "
                         "(docs/cli.md)")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="take periodic crash-safe snapshots into DIR "
                         "(atomic write + checksummed manifest, retain "
                         "--snapshot-retain); requires --mode stream and "
                         "--snapshot-every (docs/robustness.md)")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help="steps between snapshots; also the flush cadence, "
                         "so a resumed run is bitwise identical to an "
                         "uninterrupted run with the same value")
    ap.add_argument("--snapshot-retain", type=int, default=3,
                    help="keep the newest K snapshots (default 3)")
    ap.add_argument("--resume", action="store_true",
                    help="restart from the latest *valid* snapshot in "
                         "--snapshot-dir (corrupt/torn ones are skipped); "
                         "falls back to a fresh start when none exists")
    ap.add_argument("--nonfinite-guard", action="store_true",
                    help="skip any update whose batch loss is NaN/Inf "
                         "(counted in aux['skipped_steps']); value-exact "
                         "on clean data; not available with --cold-store "
                         "mem/mmap")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="simulate N CPU devices (sets XLA_FLAGS; must act "
                         "before jax initializes, so it is handled first "
                         "thing in main)")
    ap.add_argument("--epochs", type=int, default=10)
    # lm
    ap.add_argument("--arch", default="gemma3-12b",
                    help="registered config (repro.configs): an LM arch for "
                         "--task lm; with --task ctr a CTR config such as "
                         "deepfm-criteo sets the vocabs, dense features, "
                         "embedding dim and MLP widths (an LM arch keeps the "
                         "five synthetic fields)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=None,
                    help="lm: number of train steps (default 100); ctr: "
                         "optional hard cap on total steps (smoke runs, "
                         "scripts/docs_check.sh); default uncapped")
    # common
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--profile-trace", default=None, metavar="DIR",
                    help="ctr: dump a jax.profiler trace (with a perfetto "
                         "trace file) of the training run to DIR")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    if args.host_devices:
        # must land before the first jax backend touch (nothing above this
        # point creates arrays or queries devices — imports alone don't)
        mesh_lib.force_host_device_count(args.host_devices)
        if jax.device_count() < args.host_devices:
            raise SystemExit(
                "[train] --host-devices was set after jax initialized in "
                "this process; set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={args.host_devices} in the environment "
                "instead")

    use_compile_cache()
    if args.task == "ctr":
        run_ctr(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
