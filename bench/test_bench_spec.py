"""Every cell resolves by name, a new cell needs only new files and an
entry, and the command refuses to run without a TPU or without the
program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchlib import spec as spec_lib

ROOT = Path(__file__).resolve().parents[1]
TOP = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in TOP["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    spec = spec_lib.load(cell, ROOT)
    assert spec.config["model"] in ("deepfm", "dcnv2")
    assert spec.config["train_rows"] % (
        spec.traffic["batch"] * spec.traffic["scan_steps"]) == 0
    assert spec.limits and set(spec.limits) <= {"loss_gap", "m_gap",
                                                "change_gap"}
    assert "setup_s" in spec.end_to_end and "rows_per_s" in spec.end_to_end
    declared = {m["name"] for m in TOP["per_layer"]
                if cell in m.get("workloads", [cell])}
    assert set(spec.per_layer) == declared
    units = {m["name"]: m["unit"] for m in TOP["per_layer"]}
    for name, reader in spec.per_layer.items():
        assert reader.UNIT == units[name]
    spec_lib.peaks_for(spec, "TPU v5 lite")
    with pytest.raises(spec_lib.SpecError):
        spec_lib.peaks_for(spec, "TPU v9 imaginary")


def test_configs_hold_what_they_state():
    for c in TOP["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in conf
        assert conf["source"] == c["source"]


def test_readers_find_nothing_without_a_trace():
    spec = spec_lib.load(CELLS[0], ROOT)
    r = {"compile_s": 0.0, "input_wait_s": 0.0, "window_steps": 0,
         "steps": 0, "rows": 0,
         "window_s": 0.0, "trace": None, "chips": 1, "peaks": None,
         "flops_per_row": 1.0, "least_bytes_per_step": 1.0}
    assert {n: m.read(r) for n, m in spec.per_layer.items()} == {
        n: None for n in spec.per_layer}


def test_new_cell_from_files_and_an_entry_only(bench_copy):
    bench = bench_copy / "bench"
    (bench / "traffic" / "b1k.json").write_text(json.dumps(
        {"batch": 1024, "scan_steps": 8, "zipf_a": 1.1}))
    (bench / "limits" / "dcnv2-criteo.b1k.json").write_text(json.dumps(
        {"loss_gap": 1e-5, "m_gap": 1e-3, "change_gap": 1e-3}))
    (bench / "metrics" / "rows_seen.py").write_text(
        'UNIT = "rows"\n\ndef read(r):\n    return r["rows"] or None\n')
    top = json.loads((bench_copy / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "dcnv2-criteo",
                           "source": "https://arxiv.org/abs/2204.06240",
                           "file": "bench/configs/dcnv2-criteo.json",
                           "reduced": ["train_rows"], "why": "DCN-v2"})
    top["workloads"].append({"name": "dcnv2-criteo.b1k",
                             "config": "dcnv2-criteo", "traffic": "b1k",
                             "chips": 1, "why": "batch 1K"})
    top["per_layer"].append({"name": "rows_seen", "unit": "rows",
                             "better": "higher", "source": "host_clock",
                             "layer": "device", "moves": "rows_per_s",
                             "workloads": ["dcnv2-criteo.b1k"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(top))
    spec = spec_lib.load("dcnv2-criteo.b1k", bench_copy)
    assert spec.traffic["batch"] == 1024
    assert "rows_seen" in spec.per_layer
    assert spec.per_layer["rows_seen"].read({"rows": 5}) == 5
    assert spec_lib.load(CELLS[0], bench_copy).per_layer.keys() == \
        spec_lib.load(CELLS[0], ROOT).per_layer.keys()


def test_unknown_cell_or_missing_file_is_an_error(bench_copy):
    with pytest.raises(spec_lib.SpecError):
        spec_lib.load("no-such-cell", bench_copy)
    (bench_copy / "bench" / "limits" / f"{CELLS[0]}.json").unlink()
    with pytest.raises(spec_lib.SpecError):
        spec_lib.load(CELLS[0], bench_copy)


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2**32 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_fails_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
