"""Device ops put down to the program's step phases: the compiled chunk
program of the sparse placement names every phase of
``repro.train.loop.STEP_SCOPES``, and the op-to-scope map finds them."""

import re

import numpy as np
import pytest

from benchlib.scopes import (UNSCOPED, instruction, op_names, scope_of,
                             scope_seconds)
from benchlib.trace import Event

VOCABS = (97, 61, 37)
BATCH, STEPS, DIM = 16, 2, 8


@pytest.fixture(scope="module")
def chunk_hlo():
    """HLO text of the compiled chunk program: sparse DeepFM, tiny."""
    import jax
    import jax.numpy as jnp

    from repro.core import build_train_step, scale_hyperparams
    from repro.models import ctr
    from repro.train import engine

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                        emb_dim=DIM, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                        sparse=True)
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
            rng.normal(size=(STEPS, BATCH, 3)).astype(np.float32)),
        "labels": jnp.asarray(
            (rng.random((STEPS, BATCH)) < 0.3).astype(np.float32)),
    }
    runner = engine.make_chunk_runner(engine.resolve_scan_step(bundle))
    return runner.lower(params, state, chunk).compile().as_text()


def test_every_step_scope_has_ops(chunk_hlo):
    from repro.train.loop import STEP_SCOPES

    names = op_names(chunk_hlo)
    found = {scope_of(v, STEP_SCOPES) for v in names.values()}
    assert set(STEP_SCOPES) <= found


def test_table_scatters_belong_to_row_update_scatter(chunk_hlo):
    from repro.train.loop import STEP_SCOPES

    names = op_names(chunk_hlo)
    scatter = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = "
                         rf"f32\[(\d+),{DIM}\]\S* scatter\(")
    lead = {m.group(1): int(m.group(2)) for m in
            map(scatter.match, chunk_hlo.splitlines()) if m}
    scope = {n: scope_of(names[n], STEP_SCOPES) for n in lead}
    # scatters into a whole [vocab, dim] table: the row update
    tables = {n for n, v in lead.items() if v in VOCABS}
    assert {lead[n] for n in tables} == set(VOCABS)
    assert {scope[n] for n in tables} == {"row_update_scatter"}
    # scatter-adds into [capacity, dim] rows: the tower's backward
    assert {scope[n] for n in set(lead) - tables} == {"tower_fwd_bwd"}
    assert {lead[n] for n in set(lead) - tables} == {BATCH}


def test_scope_of_takes_the_innermost_scope():
    scopes = ("dedup", "tower_fwd_bwd", "row_update_scatter")
    path = ("jit(run)/while/body/closed_call/row_update_scatter/"
            "jit(sparse_update_scatter)/scatter")
    assert scope_of(path, scopes) == "row_update_scatter"
    assert scope_of("a/dedup/b/tower_fwd_bwd/c", scopes) == "tower_fwd_bwd"
    assert scope_of("transpose(jvp(tower_fwd_bwd))/dot", scopes) == (
        "tower_fwd_bwd")
    assert scope_of("jit(run)/while/body/dynamic_slice", scopes) == UNSCOPED
    assert scope_of("", scopes) == UNSCOPED


def test_fusion_without_metadata_takes_its_roots():
    text = "\n".join([
        "HloModule m",
        "%fused_computation.3 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        '  ROOT %neg.1 = f32[8]{0} negate(%p), metadata={op_name="a/dedup/neg"}',
        "}",
        "ENTRY %main.9 (x: f32[8]) -> f32[8] {",
        "  %x = f32[8]{0} parameter(0)",
        "  ROOT %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, "
        "calls=%fused_computation.3",
        "}",
    ])
    names = op_names(text)
    assert names["fusion.3"] == "a/dedup/neg"
    assert "x" not in names
    assert instruction("%fusion.3 = f32[8]{0} fusion(%x)") == "fusion.3"


def test_scope_seconds_over_a_window():
    names = {"fusion.1": "jit(run)/while/body/dedup/sort",
             "scatter.2": "jit(run)/while/body/row_update_scatter/scatter",
             "copy.3": "jit(run)/while/body/copy"}
    ops = [Event("%fusion.1 = s32[8]{0} fusion(x)", 0, 10e3),
           Event("%scatter.2 = f32[97,8]{1,0} scatter(x)", 10e3, 30e3),
           Event("%copy.3 = f32[8]{0} copy(x)", 40e3, 5e3),
           Event("%copy.4 = f32[8]{0} copy(x)", 45e3, 5e3),      # no name
           Event("%while.5 = (s32[]) while(x)", 0, 50e3),        # container
           Event("%scatter.2 = f32[97,8]{1,0} scatter(x)", 90e3, 20e3)]
    r = scope_seconds(ops, names, ("dedup", "row_update_scatter"),
                      window=(0, 100e3))
    assert r["scope_s"] == {"dedup": pytest.approx(10e-6),
                            "row_update_scatter": pytest.approx(40e-6),
                            UNSCOPED: pytest.approx(10e-6)}
    assert r["total_s"] == pytest.approx(60e-6)
    assert r["matched_s"] == pytest.approx(55e-6)
