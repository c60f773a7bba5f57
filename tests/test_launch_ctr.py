"""The CTR side of ``repro.launch.train``: ``--arch`` resolution, the
in-process ``run_ctr`` result, and where the compile cache goes."""

import math

import jax
import pytest

from repro.configs.deepfm_criteo import CONFIG as CRITEO, CRITEO_VOCABS
from repro.data.synthetic import make_ctr_dataset
from repro.launch import train as train_lib


def test_ctr_arch_names_a_ctr_config_or_nothing():
    assert train_lib.ctr_arch("deepfm-criteo") is CRITEO
    assert train_lib.ctr_arch("gemma3-12b") is None     # an LM arch


@pytest.mark.parametrize("arch", ["deepfm-criteo", "gemma3-12b"])
def test_ctr_config_widths_follow_arch(arch):
    args = train_lib.parse_args(["--task", "ctr", "--arch", arch,
                                 "--emb-dim", "4", "--mlp-dim", "32"])
    ds = make_ctr_dataset(64, (50, 7, 3), n_dense=2, seed=0)
    cfg = train_lib.make_ctr_config(args, ds, "sparse")
    assert cfg.vocab_sizes == (50, 7, 3) and cfg.n_dense == 2
    if arch == "deepfm-criteo":
        assert (cfg.emb_dim, cfg.mlp_dims) == (CRITEO.emb_dim,
                                               CRITEO.mlp_dims)
    else:
        assert (cfg.emb_dim, cfg.mlp_dims) == (4, (32, 32, 32))


def test_criteo_arch_sets_the_synthetic_fields(monkeypatch):
    seen = {}

    def fake(samples, vocabs, *, n_dense, **kw):
        seen.update(samples=samples, vocabs=vocabs, n_dense=n_dense)

    monkeypatch.setattr(train_lib, "make_ctr_dataset", fake)
    train_lib.make_ctr_data(train_lib.parse_args(
        ["--task", "ctr", "--arch", "deepfm-criteo", "--samples", "128"]))
    assert seen == dict(samples=128, vocabs=CRITEO_VOCABS,
                        n_dense=CRITEO.n_dense)
    train_lib.make_ctr_data(train_lib.parse_args(["--task", "ctr"]))
    assert seen["vocabs"] == train_lib.DEFAULT_VOCABS
    assert seen["n_dense"] == 4


def test_run_ctr_returns_its_result():
    args = train_lib.parse_args([
        "--task", "ctr", "--placement", "sparse", "--samples", "4000",
        "--batch", "512", "--steps", "4", "--epochs", "1", "--scan-steps",
        "2", "--emb-dim", "4", "--mlp-dim", "16"])
    res = train_lib.run_ctr(args)
    assert res.steps == 4 and len(res.losses) == 4
    assert all(math.isfinite(x) for x in res.losses)
    assert res.first_chunk[0] == 2 and res.train_seconds > 0
    assert 0.0 < res.final_eval["auc"] < 1.0


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert train_lib.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = train_lib.use_compile_cache()
        assert path == str(train_lib.CHECKOUT_ROOT / ".jax_cache")
        assert (train_lib.CHECKOUT_ROOT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
