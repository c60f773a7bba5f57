"""A whole run at a tiny size on the CPU, with the chip check skipped: a
sound program is correct, and the control and each fault a training cell
can have are not."""

import time

import jax
import jax.numpy as jnp
import pytest

from benchlib import check, harness, spec as spec_lib

TINY_CELL = "deepfm-criteo.b128k"   # cut to a tiny size by ``tiny_root``


def _run(root):
    spec = spec_lib.load(TINY_CELL, root)
    return harness.run(spec, 2**31 + 11, 0.2, False,
                       t_start=time.perf_counter())


def test_sound_run_is_correct(tiny_root):
    result = _run(tiny_root)
    assert result["correct"], result["check"]
    assert list(result)[-1] == "check"
    assert set(result["check"]) == set(check.NAMES)
    assert result["metrics"]["rows_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0


def _unchanged(runner):
    """The step returns its state unchanged."""
    def run(params, state, chunk):
        keep = jax.tree.map(jnp.copy, (params, state))
        _, _, aux = runner(params, state, chunk)
        return keep[0], keep[1], aux
    return run


def test_state_left_unchanged_is_not_correct(tiny_root):
    spec = spec_lib.load(TINY_CELL, tiny_root)
    program = harness.set_up(spec, 2**31 + 11, time.perf_counter(),
                             wrap_runner=_unchanged)
    harness.timed_window(program, 0.2)
    _, compared, ok = harness.check_first_chunk(program)
    assert not ok
    assert compared["change_gap"]["value"] > 0.9


def test_half_batch_is_not_correct(tiny_root, monkeypatch):
    """Half of each batch left out, the mean taken over the rest."""
    from repro.train import metrics

    full = metrics.logloss
    monkeypatch.setattr(metrics, "logloss", lambda z, y: full(
        z[: z.shape[0] // 2], y[: y.shape[0] // 2]))
    result = _run(tiny_root)
    assert not result["correct"], result["check"]


def test_leaf_gap_uses_the_median_leaf_as_a_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.0, "b": 2.2, "c": 2e-9}
    worst, at = check.leaf_gap(prog, ref, ref)
    assert at == "b" and worst == pytest.approx(0.1)


def test_negligible_leaves_are_left_out():
    ref = {"losses": [0.7], "m": {"a": 1.0, "b": 1.0, "c": 1e-6},
           "change": {"a": 1.0, "b": 1.0, "c": 1.0}}
    prog = {"losses": [0.7], "m": {"a": 1.0, "b": 1.0, "c": 5e-6},
            "change": {"a": 1.0, "b": 1.0, "c": 0.0}}
    read = check.readings(prog, ref)
    assert read["_left_out"] == ["c"]
    assert read["m_gap"]["value"] == 0 and read["change_gap"]["value"] == 0


def test_a_missing_or_nan_number_fails():
    ref = {"losses": [0.7, 0.6], "m": {"a": 1.0}, "change": {"a": 1.0}}
    prog = {"losses": [0.7, float("nan")], "m": {"a": 1.0},
            "change": {"a": 1.0}}
    read = check.readings(prog, ref)
    _, ok = check.verdict(read, {n: 1.0 for n in check.NAMES})
    assert not ok


def test_only_the_numbers_a_cell_limits_are_compared():
    ref = {"losses": [0.7], "m": {"a": 1.0}, "change": {"a": 1.0}}
    prog = {"losses": [0.8], "m": {"a": 1.0}, "change": {"a": 1.0}}
    read = check.readings(prog, ref)
    compared, ok = check.verdict(read, {"m_gap": 0.1, "change_gap": 0.1})
    assert ok and set(compared) == {"m_gap", "change_gap"}
    with pytest.raises(ValueError):
        check.verdict(read, {"m_gap": 0.1, "loss": 0.1})


def test_traced_run_reports_every_per_layer_metric(tiny_root, monkeypatch):
    """The per-layer path on a synthetic device trace: one 1 ms op of each
    class per step, back to back, under the harness's own host spans."""
    from benchlib import trace as trace_lib

    shapes = ["f32[3000,4]{1,0} fusion", "f32[512,4]{1,0} gather",
              "f32[50]{0} fusion", "s32[512]{0} sort"]

    def fake_load(trace_dir, device):
        ops = [trace_lib.Event(f"%op.{i} = {shapes[i % 4]}(x)", i * 1e6, 1e6)
               for i in range(4 * 4 * 8)]
        spans = [trace_lib.Event("bench.block", 0, 4 * 4 * 8 * 1e6 + 5e5)]
        return ops, spans

    monkeypatch.setattr(harness.trace_lib, "load", fake_load)
    monkeypatch.setattr(harness, "peaks_for", lambda spec, kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    spec = spec_lib.load(TINY_CELL, tiny_root)
    result = harness.run(spec, 3, 0.2, True, t_start=time.perf_counter())
    assert result["correct"], result["check"]
    assert set(result["metrics"]) == set(spec.per_layer)
    units = {n: m.UNIT for n, m in spec.per_layer.items()}
    for name, v in result["metrics"].items():
        assert v["unit"] == units[name] and v["value"] > 0, (name, v)
    assert result["metrics"]["table_write_ms"]["value"] == pytest.approx(
        result["metrics"]["batch_op_ms"]["value"])
    assert result["device"]["busy_s"] == pytest.approx(0.128)
    assert result["device"]["window_s"] == pytest.approx(0.1285)
    assert result["breakdown"]["idle_gaps"] == [
        ["bench.block", pytest.approx(5e-4)]]
    assert result["metrics"]["mfu"]["value"] < 100
    assert result["metrics"]["step_bytes_roofline"]["value"] < 100


def test_control_is_not_correct(tiny_root):
    """The control: the reference with its tower's products in three
    bfloat16 passes, put in the program's place."""
    spec = spec_lib.load(TINY_CELL, tiny_root)
    read = harness.control_check(spec, 2**31 + 11)
    _, ok = check.verdict(read, spec.limits)
    assert not ok, {n: read[n]["value"] for n in check.NAMES}
