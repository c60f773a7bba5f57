"""Resolve a benchmark cell by name into its files, without importing JAX.

Everything that belongs to one configuration, traffic mix, per-layer metric
or model sits in files of its own, found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the traffic mix;
* ``bench/limits/<workload>.json``: the limit of each number compared;
* ``bench/metrics/<metric>.py``: a per-layer metric's reader, ``read(r)``;
* ``bench/counts/<model>.py``: the model's FLOPs and least bytes;
* ``bench/peaks.json``: the chips' peaks, keyed by ``device_kind``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import NamedTuple

BENCH = Path(__file__).resolve().parents[1]


class SpecError(Exception):
    """A cell, or a file it names, cannot be resolved."""


class Spec(NamedTuple):
    workload: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # names this cell reports with --trace 0
    per_layer: dict       # name -> reader module, this cell's --trace 1
    counts: object        # module with flops_per_row / least_bytes
    peaks: dict           # device_kind -> peaks


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None


def _module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load(workload: str, root: Path | None = None) -> Spec:
    """The files of ``workload`` under the checkout ``root``."""
    root = Path(root) if root is not None else BENCH.parent
    bench = root / "bench"
    top = _json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in top.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in top.get("configs", [])}
    if cell["config"] not in configs:
        raise SpecError(f"{workload}: no config {cell['config']!r}")
    config = _json(root / configs[cell["config"]]["file"])
    traffic = _json(bench / "traffic" / f"{cell['traffic']}.json")
    limits = _json(bench / "limits" / f"{workload}.json")
    per_layer = {m["name"]: _module(bench / "metrics" / f"{m['name']}.py",
                                    f"bench_metric_{m['name']}")
                 for m in top.get("per_layer", []) if _applies(m, workload)}
    counts = _module(bench / "counts" / f"{config['model']}.py",
                     f"bench_counts_{config['model']}")
    return Spec(
        workload=workload, chips=int(cell["chips"]), config=config,
        traffic=traffic, limits=limits,
        end_to_end=[m["name"] for m in top.get("end_to_end", [])
                    if _applies(m, workload)],
        per_layer=per_layer, counts=counts,
        peaks=_json(bench / "peaks.json"))


def peaks_for(spec: Spec, device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown chip is an error."""
    table = {k: v for k, v in spec.peaks.items() if not k.startswith("_")}
    if device_kind not in table:
        raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
