"""Fixtures of the benchmark's own tests: a tiny copy of the benchmark.

These tests run on the CPU in seconds and never load the TPU library.
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def bench_copy(tmp_path):
    """A checkout root holding ``BENCHMARK.json`` and a copy of ``bench/``'s
    data and readers."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for sub in ("configs", "traffic", "limits", "metrics", "counts"):
        shutil.copytree(BENCH / sub, tmp_path / "bench" / sub)
    shutil.copy(BENCH / "peaks.json", tmp_path / "bench")
    return tmp_path


@pytest.fixture
def tiny_root(bench_copy):
    """``bench_copy`` with ``deepfm-criteo`` cut to 4 fields, MLP 3x16 and a
    4,096-row pool, and ``b128k`` to batch 512 x 4 steps, with limits of
    that size. They were set as a cell's are (``bench/calibrate.py`` on the
    CPU, this copy): over 12 seeds the program read at most 7.3e-8 /
    1.1e-7 / 3.5e-6 (loss / m / change), the control at least 3.2e-7 /
    8.9e-7 / 1.1e-6 over 3, half a batch at least 0.011 / 0.075 / 0.079."""
    conf_path = bench_copy / "bench" / "configs" / "deepfm-criteo.json"
    conf = json.loads(conf_path.read_text())
    conf.update(vocab_sizes=[50, 3000, 7, 1200], n_dense=3, emb_dim=4,
                mlp_dims=[16, 16, 16], train_rows=4096, base_batch=128)
    conf["cli"] = ["--task", "ctr", "--model", "deepfm", "--emb-dim", "4",
                   "--mlp-dim", "16", "--placement", "sparse", "--rule",
                   "cowclip", "--base-batch", "128", "--base-lr", "1e-4",
                   "--base-l2", "1e-5", "--zeta", "1e-5"]
    conf_path.write_text(json.dumps(conf))
    (bench_copy / "bench" / "traffic" / "b128k.json").write_text(
        json.dumps({"batch": 512, "scan_steps": 4, "zipf_a": 1.1}))
    (bench_copy / "bench" / "limits" / "deepfm-criteo.b128k.json").write_text(
        json.dumps({"loss_gap": 1.5e-7, "m_gap": 3e-7, "change_gap": 1e-4}))
    return bench_copy


@pytest.fixture(autouse=True)
def _matmul_precision():
    """The harness sets JAX's default matmul precision for its process;
    put it back after each test."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)
