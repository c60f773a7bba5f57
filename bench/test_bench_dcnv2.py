"""DCN-v2 against the plain reference, a whole run at a tiny size on the
CPU: a sound program is correct, and the control and each fault a training
cell can have are not. And its cross network, forward and backward, runs
under ``repro.models.ctr.CROSS_SCOPE`` in the compiled chunk program."""

import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import check, harness, spec as spec_lib
from benchlib.scopes import op_names, scope_of

CELL = "dcnv2-criteo.b128k"   # cut to a tiny size by ``dcnv2_root``
SEED = 2**31 + 11


@pytest.fixture
def dcnv2_root(bench_copy):
    """``bench_copy`` with ``dcnv2-criteo`` cut to 4 fields, dim 4, MLP
    3x16 and a 4,096-row pool, its 3 cross layers kept, and ``b128k`` to
    batch 512 x 4 steps, with limits of that size. They were set as a
    cell's are (``bench/calibrate.py`` on the CPU, this copy, seeds
    2147483748 on): over 12 seeds the program read at most 9.1e-8 /
    1.5e-7 / 6.3e-7 (loss / m / change), the control at least 3.7e-7 /
    1.5e-6 / 1.1e-6 over 3, the program's bfloat16 path at least 3.1e-4 /
    3.0e-3 / 1.5e-3 over 3, half a batch at least 0.017 / 0.078 / 0.052
    over 3. The control fails loss_gap and m_gap; its change_gap reads
    under twice the program's."""
    bench = bench_copy / "bench"
    conf_path = bench / "configs" / "dcnv2-criteo.json"
    conf = json.loads(conf_path.read_text())
    conf.update(vocab_sizes=[50, 3000, 7, 1200], n_dense=3, emb_dim=4,
                mlp_dims=[16, 16, 16], train_rows=4096, base_batch=128)
    conf["cli"] = ["--task", "ctr", "--model", "dcnv2", "--emb-dim", "4",
                   "--mlp-dim", "16", "--placement", "sparse", "--rule",
                   "cowclip", "--base-batch", "128", "--base-lr", "1e-4",
                   "--base-l2", "1e-5", "--zeta", "1e-5"]
    conf_path.write_text(json.dumps(conf))
    (bench / "traffic" / "b128k.json").write_text(
        json.dumps({"batch": 512, "scan_steps": 4, "zipf_a": 1.1}))
    (bench / "limits" / f"{CELL}.json").write_text(
        json.dumps({"loss_gap": 2e-7, "m_gap": 7e-7, "change_gap": 1e-4}))
    return bench_copy


def _run(root):
    return harness.run(spec_lib.load(CELL, root), SEED, 0.2, False,
                       t_start=time.perf_counter())


def test_sound_dcnv2_run_is_correct(dcnv2_root):
    result = _run(dcnv2_root)
    assert result["correct"], result["check"]
    assert set(result["check"]) == set(check.NAMES)
    assert result["readings"]["left_out"] == []
    assert result["attempted"] > 0 and result["failed"] == 0


def test_dcnv2_half_batch_is_not_correct(dcnv2_root, monkeypatch):
    """Half of each batch left out, the mean taken over the rest."""
    from repro.train import metrics

    full = metrics.logloss
    monkeypatch.setattr(metrics, "logloss", lambda z, y: full(
        z[: z.shape[0] // 2], y[: y.shape[0] // 2]))
    result = _run(dcnv2_root)
    assert not result["correct"], result["check"]


def test_dcnv2_state_left_unchanged_is_not_correct(dcnv2_root):
    def unchanged(runner):
        def run(params, state, chunk):
            keep = jax.tree.map(jnp.copy, (params, state))
            _, _, aux = runner(params, state, chunk)
            return keep[0], keep[1], aux
        return run

    program = harness.set_up(spec_lib.load(CELL, dcnv2_root), SEED,
                             time.perf_counter(), wrap_runner=unchanged)
    harness.timed_window(program, 0.2)
    _, compared, ok = harness.check_first_chunk(program)
    assert not ok
    assert compared["change_gap"]["value"] > 0.9


def test_dcnv2_control_is_not_correct(dcnv2_root):
    """The reference with its tower's products, cross layers included, in
    three bfloat16 passes, put in the program's place."""
    spec = spec_lib.load(CELL, dcnv2_root)
    read = harness.control_check(spec, SEED)
    compared, ok = check.verdict(read, spec.limits)
    assert not ok, compared
    assert read["m_gap"]["value"] > spec.limits["m_gap"]


VOCABS = (97, 61, 37)
BATCH, STEPS, DIM, N_DENSE, N_CROSS, MLP = 16, 2, 8, 3, 2, 16
D0 = len(VOCABS) * DIM + N_DENSE     # the tower's input width


def _chunk_hlo(model):
    """HLO text of the compiled chunk program: sparse ``model``, tiny."""
    from repro.core import build_train_step, scale_hyperparams
    from repro.models import ctr
    from repro.train import engine

    cfg = ctr.CTRConfig(name=model, vocab_sizes=VOCABS, n_dense=N_DENSE,
                        emb_dim=DIM, mlp_dims=(MLP,) * 3, n_cross=N_CROSS,
                        emb_sigma=1e-2, sparse=True)
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=BATCH, batch_size=BATCH,
                           base_dense_lr=2e-3)
    bundle = build_train_step(cfg, hp, path="sparse", use_kernel=False)
    params = bundle.prepare(ctr.init(jax.random.key(0), cfg))
    state = bundle.init(params)
    rng = np.random.default_rng(0)
    chunk = {
        "ids": jnp.asarray(np.stack(
            [rng.integers(0, v, size=(STEPS, BATCH)) for v in VOCABS],
            axis=-1).astype(np.int32)),
        "dense": jnp.asarray(
            rng.normal(size=(STEPS, BATCH, N_DENSE)).astype(np.float32)),
        "labels": jnp.asarray(
            (rng.random((STEPS, BATCH)) < 0.3).astype(np.float32)),
    }
    runner = engine.make_chunk_runner(engine.resolve_scan_step(bundle))
    return runner.lower(params, state, chunk).compile().as_text()


def _dots(hlo_text):
    """``{instruction: (rows, cols)}`` of every 2-d float32 dot."""
    dot = re.compile(
        r"^\s*(?:ROOT\s+)?%?([^\s=]+) = f32\[(\d+),(\d+)\]\S* dot\(")
    return {m.group(1): (int(m.group(2)), int(m.group(3))) for m in
            map(dot.match, hlo_text.splitlines()) if m}


def test_cross_layers_belong_to_cross():
    """The cross layers' forward products ``x_l W_l`` and their weights'
    gradients run under ``cross``, inside ``tower_fwd_bwd``; the MLP's do
    not."""
    from repro.models.ctr import CROSS_SCOPE
    from repro.train.loop import STEP_SCOPES

    text = _chunk_hlo("dcnv2")
    names = op_names(text)
    dots = _dots(text)
    scope = {n: scope_of(names[n], (*STEP_SCOPES, CROSS_SCOPE))
             for n in dots}
    forward = {n for n in dots if "transpose(" not in names[n]}
    # x_l W_l: [batch, d0] out of [batch, d0] x [d0, d0], one per layer
    fwd_cross = {n for n in forward if dots[n] == (BATCH, D0)}
    assert len(fwd_cross) == N_CROSS
    assert {scope[n] for n in fwd_cross} == {CROSS_SCOPE}
    # the gradient of each W_l: [d0, d0], one per layer
    w_grads = {n for n in set(dots) - forward if dots[n] == (D0, D0)}
    assert len(w_grads) == N_CROSS
    assert {scope[n] for n in w_grads} == {CROSS_SCOPE}
    assert all("tower_fwd_bwd" in names[n] for n in fwd_cross | w_grads)
    # the gradient of the first MLP layer's weight stays in the tower
    mlp = {n for n in dots if dots[n] == (D0, MLP)}
    assert mlp and {scope[n] for n in mlp} == {"tower_fwd_bwd"}


def test_deepfm_has_no_cross_ops_and_step_scopes_hold():
    """``cross`` is not a step phase, and a DeepFM chunk has no op under
    it."""
    from repro.models.ctr import CROSS_SCOPE
    from repro.train.loop import STEP_SCOPES

    assert CROSS_SCOPE not in STEP_SCOPES
    names = op_names(_chunk_hlo("deepfm"))
    found = {scope_of(v, (*STEP_SCOPES, CROSS_SCOPE)) for v in names.values()}
    assert CROSS_SCOPE not in found
