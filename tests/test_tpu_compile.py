"""Every Pallas kernel compiles for a TPU v5e chip at production widths,
and the sparse path's dedup compiles there without scatter or gather.

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
from repro.kernels.cowclip import cowclip as cc_dense
from repro.kernels.cowclip import sparse as cc_sparse
from repro.kernels.wkv6.wkv6 import chunked_wkv6
from repro.models import embedding

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


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel missing from the program"
    return text


def test_dense_kernel_compiles(one_chip):
    vocab, dim = 93_146, 10
    fn = functools.partial(cc_dense.cowclip_adam_update, lr=1e-3, l2=1e-5)
    table = ((vocab, dim), jnp.float32)
    _compile(fn, one_chip, table, table, ((vocab,), jnp.float32), table,
             table, ((), jnp.int32))


@pytest.mark.parametrize("dim,row_offset", [
    (10, 0), (1, 0), (10, BIGGEST_VOCAB // 2),
], ids=["fm", "lin", "fm_offset"])
def test_sparse_kernels_compile(one_chip, dim, row_offset):
    rows = BIGGEST_VOCAB - row_offset
    table = ((rows, dim), jnp.float32)
    slab = ((CAP, dim), jnp.float32)
    ls = ((rows,), jnp.int32)
    uids = ((CAP,), jnp.int32)
    step = ((), jnp.int32)
    kw = dict(lr=1e-3, l2=1e-5, row_offset=row_offset)
    _compile(functools.partial(cc_sparse.sparse_gather_catchup, **kw),
             one_chip, table, table, table, ls, uids, step)
    _compile(functools.partial(cc_sparse.sparse_update_scatter, **kw),
             one_chip, table, table, table, uids, ((CAP,), jnp.float32),
             slab, slab, slab, slab, step)


def test_wkv6_kernel_compiles(one_chip):
    bh, s, n = 64, 4096, 64
    seq = ((bh, s, n), jnp.bfloat16)
    _compile(chunked_wkv6, one_chip, seq, seq, seq, seq,
             ((bh, n), jnp.bfloat16))


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
