#!/usr/bin/env python3
"""Compile a benchmark cell's chunk program for a described TPU v5e chip and
write its HLO text with the metadata stripped.

The program is the one ``bench/benchlib/harness.set_up`` runs: the cell's
configuration and traffic through ``launch.train.make_ctr_bundle`` and
``train.engine.make_chunk_runner``, at the cell's full widths. Nothing runs
and no chip is needed: the arguments are shapes. What is stripped is what a
refactor may change without changing the program: each instruction's
``metadata={...}`` (``op_name``, source file and line) and the source tables
that follow the module header. Two checkouts compile the same program when
their outputs are equal:

    JAX_PLATFORMS=cpu python scripts/cell_program.py --root OLD CELL old.hlo
    JAX_PLATFORMS=cpu python scripts/cell_program.py CELL new.hlo
    diff old.hlo new.hlo

where ``CELL`` names a workload of ``BENCHMARK.json`` and ``OLD`` is another
checkout, such as a ``git archive`` of the parent commit.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import types
from pathlib import Path

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip(text: str) -> str:
    """``text`` without instruction metadata and the source tables."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    out, skip = [], False
    for line in text.splitlines(keepends=True):
        if line.strip() in TABLES:
            skip = True
        elif skip and line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(line)
    return "".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload", help="a cell of BENCHMARK.json")
    ap.add_argument("out", help="where to write the stripped HLO text")
    ap.add_argument("--root", default=str(Path(__file__).resolve().parents[1]),
                    help="the checkout to compile (default: this one)")
    args = ap.parse_args(argv)
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root / "src"), str(root)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.benchlib import harness, spec as spec_lib
    from repro.embed import store_for
    from repro.launch import train as train_lib
    from repro.models import ctr as ctr_lib
    from repro.train import engine

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    spec = spec_lib.load(args.workload, root)
    conf, tr = spec.config, spec.traffic
    jax.config.update("jax_default_matmul_precision", conf["matmul_precision"])
    ds = types.SimpleNamespace(vocab_sizes=tuple(conf["vocab_sizes"]),
                               dense=np.zeros((1, conf["n_dense"])))
    cli = train_lib.parse_args(harness.program_args(spec, 0))
    cfg = train_lib.make_ctr_config(cli, ds, cli.placement)
    harness._check_program_config(cfg, cli, conf)
    bundle = train_lib.make_ctr_bundle(cli, cfg, store_for(cfg),
                                       conf["train_rows"])
    runner = engine.make_chunk_runner(engine.resolve_scan_step(bundle))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda: ctr_lib.init(jax.random.key(0), cfg))
    state = jax.eval_shape(bundle.init, params)
    k, b = tr["scan_steps"], tr["batch"]
    chunk = {"ids": ((k, b, len(conf["vocab_sizes"])), jnp.int32),
             "dense": ((k, b, conf["n_dense"]), jnp.float32),
             "labels": ((k, b), jnp.float32)}
    chunk = {key: jax.ShapeDtypeStruct(*sd) for key, sd in chunk.items()}
    compiled = runner.lower(on_chip(params), on_chip(state),
                            on_chip(chunk)).compile()
    text = strip(compiled.as_text())
    Path(args.out).write_text(text)
    mem = compiled.memory_analysis()
    print(f"{args.workload} ({root}): {len(text)} bytes of HLO, arguments "
          f"{mem.argument_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, "
          f"tpu_custom_call x{text.count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
