"""Model FLOPs and least bytes of each model, against sums by hand at the
Criteo widths (26 fields of dim 10, 13 dense features, MLP 3x400)."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent


def _counts(model):
    spec = importlib.util.spec_from_file_location(
        f"counts_{model}", BENCH / "counts" / f"{model}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_deepfm_flops_per_row():
    # tower 273 -> 400 -> 400 -> 400 -> 1: 109,200 + 160,000 + 160,000 + 400
    macs = 273 * 400 + 400 * 400 + 400 * 400 + 400
    assert macs == 429_600
    fm = 4 * 26 * 10
    assert _counts("deepfm").flops_per_row(_conf("deepfm-criteo")) == \
        3 * (2 * macs + fm) == 2_580_720


def test_dcnv2_flops_per_row():
    # MLP 273 -> 3x400, three 273x273 cross layers, combiner 673 -> 1
    macs = 273 * 400 + 2 * 400 * 400 + 3 * 273 * 273 + 673
    cross_elementwise = 2 * 3 * 273
    assert _counts("dcnv2").flops_per_row(_conf("dcnv2-criteo")) == \
        3 * (2 * macs + cross_elementwise) == 3_925_674


def test_deepfm_least_bytes():
    uniques = [100] * 26
    # per touched id: fm w, m, v (3 x 10 x 4 B) and last_step (4 B), lin
    # w, m, v (3 x 4 B) and last_step (4 B), each read and written
    rows = 2600 * 2 * ((120 + 4) + (12 + 4))
    inputs = 8192 * 4 * (26 + 13 + 1)
    dense = 2 * 3 * 4 * (109_600 + 160_400 + 160_400 + 401 + 1)
    assert _counts("deepfm").least_bytes_per_step(
        _conf("deepfm-criteo"), uniques, 8192) == rows + inputs + dense


def test_dcnv2_least_bytes():
    uniques = [100] * 26
    rows = 2600 * 2 * (120 + 4)
    inputs = 8192 * 4 * 40
    dense = 2 * 3 * 4 * (109_600 + 160_400 + 160_400
                         + 3 * (273 * 273 + 273) + 674)
    assert _counts("dcnv2").least_bytes_per_step(
        _conf("dcnv2-criteo"), uniques, 8192) == pytest.approx(
            rows + inputs + dense)
