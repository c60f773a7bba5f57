"""The Pallas kernel and the XLA paths compile for a TPU v5e chip: the
WKV6 kernel at production widths; the CowClip row update at the Criteo
tables' widths and the train step of each CTR tower at tiny widths, both
with no Pallas kernel in them; the sparse path's dedup without scatter or
gather.

Nothing here runs on a chip: the TPU compiler, which ships with jaxlib,
compiles for a described v5e topology while the process stays on the CPU
backend. That catches what interpret mode cannot — block shapes the TPU
tiling refuses, shape casts Mosaic has no layout for, VMEM overuse.

The topology is described inside a module fixture, never at import: the
TPU library can be loaded by one process at a time, and the test workers
all import this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.deepfm_criteo import CRITEO_VOCABS
from repro.core import build_train_step, scale_hyperparams
from repro.kernels.cowclip import ops as cc_ops
from repro.kernels.cowclip import ref as cc_ref
from repro.kernels.wkv6.wkv6 import chunked_wkv6
from repro.models import ctr, embedding
from repro.train import engine

BIGGEST_VOCAB = max(CRITEO_VOCABS)          # 10,131,227 rows
CAP = 8192                                  # unique slots at batch 8K


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jaxlib
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip as XLA alone: no Pallas
    kernel (``tpu_custom_call``) in the program."""
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" not in text, "a Pallas kernel in the program"
    return text


def test_dense_kernel_compiles(one_chip):
    vocab, dim = 93_146, 10
    fn = functools.partial(cc_ops.fused_cowclip_adam, lr=1e-3, l2=1e-5)
    table = ((vocab, dim), jnp.float32)
    _compile(fn, one_chip, table, table, ((vocab,), jnp.float32), table,
             table, ((), jnp.int32))


@pytest.mark.parametrize("dim,row_offset,packed", [
    (10, 0, False), (1, 0, False), (10, BIGGEST_VOCAB // 2, False),
    (10, 0, True),
], ids=["fm", "lin", "fm_offset", "fm_packed"])
def test_sparse_kernels_compile(one_chip, dim, row_offset, packed):
    rows = BIGGEST_VOCAB - row_offset
    table = ((rows, dim), jnp.float32)
    moment = ((cc_ref.packed_shape(rows, dim) if packed else (rows, dim)),
              jnp.float32)
    slab = ((CAP, dim), jnp.float32)
    ls = ((rows,), jnp.int32)
    uids = ((CAP,), jnp.int32)
    step = ((), jnp.int32)
    kw = dict(lr=1e-3, l2=1e-5, row_offset=row_offset)
    _compile(functools.partial(cc_ops.sparse_gather_catchup, **kw),
             one_chip, table, moment, moment, ls, uids, step)
    _compile(functools.partial(cc_ops.sparse_update_scatter, **kw),
             one_chip, table, moment, moment, ls, uids,
             ((CAP,), jnp.float32), slab, slab, slab, slab, step)


@pytest.mark.parametrize("model,path", [
    ("deepfm", "sparse"), ("wd", "sparse"), ("dcn", "sparse"),
    ("dcnv2", "sparse"), ("deepfm", "fused"), ("deepfm", "hotcold"),
])
def test_step_compiles_without_custom_call(one_chip, model, path):
    """The step ``build_train_step`` builds, compiled for the chip at tiny
    widths, is XLA alone: the row update runs no Pallas kernel."""
    vocabs, n_dense, batch = (60, 13, 5), 3, 32
    cfg = ctr.CTRConfig(name=model, vocab_sizes=vocabs, n_dense=n_dense,
                        emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                        sparse=path == "sparse")
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=batch, batch_size=batch,
                           base_dense_lr=2e-3)
    bundle = build_train_step(cfg, hp, path=path, hot_capacity=8)
    params = jax.eval_shape(lambda: ctr.init(jax.random.key(0), cfg))
    state = jax.eval_shape(bundle.init, params)
    tree = (params, state, {"ids": jnp.zeros((batch, len(vocabs)), jnp.int32),
                            "dense": jnp.zeros((batch, n_dense)),
                            "labels": jnp.zeros((batch,))})
    leaves, treedef = jax.tree.flatten(tree)
    step = engine.resolve_scan_step(bundle)
    _compile(lambda *xs: step(*jax.tree.unflatten(treedef, xs)), one_chip,
             *((x.shape, x.dtype) for x in leaves))


def test_wkv6_kernel_compiles(one_chip):
    bh, s, n = 64, 4096, 64
    seq = ((bh, s, n), jnp.bfloat16)
    text = _compiled_text(chunked_wkv6, one_chip, seq, seq, seq, seq,
                          ((bh, n), jnp.bfloat16))
    assert "tpu_custom_call" in text, "kernel missing from the program"


def test_unique_ids_compiles_without_scatter_or_gather(one_chip):
    """One field's dedup at batch 131,072 (the b128k cells) is sorts, a
    cumsum and elementwise ops: no scatter or gather, which is where
    ``jnp.unique``'s lowering spent its time on the chip."""
    batch = 131_072
    fn = functools.partial(embedding.unique_ids, vocab=BIGGEST_VOCAB,
                           capacity=batch)
    col = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    text = jax.jit(fn).lower(col).compile().as_text()
    opcodes = set(re.findall(r" ([a-z][a-z-]*)\(", text))
    assert "sort" in opcodes
    assert not {"scatter", "gather"} & opcodes
