"""jit'd public wrappers for the fused CowClip updates (dense + sparse).

Each wrapper runs the pure-jnp oracle (``ref``) unless the caller passes
``use_kernel=True``, which selects the Pallas kernel: compiled Mosaic on a
TPU, interpret mode elsewhere (the kernel body executed as jnp, for
correctness checks). The oracle is the default on every backend.
``fused_cowclip_adam`` is the dense update, ``sparse_gather_catchup`` /
``sparse_update_scatter`` the unique-id-path pair.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import interpret_off_tpu
from . import ref, sparse
from .cowclip import cowclip_adam_update
from .ref import cowclip_adam_reference as reference


@partial(
    jax.jit,
    static_argnames=(
        "r", "zeta", "lr", "l2", "b1", "b2", "eps", "block_rows", "use_kernel"
    ),
)
def fused_cowclip_adam(
    w, g, cnt, m, v, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
    block_rows=0, use_kernel=False,
):
    if not use_kernel:
        return reference(w, g, cnt, m, v, step, r=r, zeta=zeta, lr=lr, l2=l2,
                         b1=b1, b2=b2, eps=eps)
    return cowclip_adam_update(
        w, g, cnt, m, v, step, r=r, zeta=zeta, lr=lr, l2=l2, b1=b1, b2=b2,
        eps=eps, block_rows=block_rows, interpret=interpret_off_tpu(),
    )


@partial(
    jax.jit,
    static_argnames=("lr", "l2", "b1", "b2", "eps", "use_kernel"),
)
def sparse_gather_catchup(
    w, m, v, last_step, uids, step, *,
    lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8, use_kernel=False,
    row_offset=0,
):
    """Gather unique rows + apply pending lazy-L2 decay (through step - 1)
    in closed form — ``w *= (1 - lr*l2)**k``, O(1) in pending depth.

    ``uids`` are the slot uids (pads out of range). ``row_offset`` is the
    shard-offset form: ``w``/``m``/``v``/``last_step`` are one row-shard
    and ``uids`` global ids of rows that shard owns. ``m``/``v`` are
    ``[rows, dim]`` or packed (``ref.pack_rows``), told apart by shape.
    Returns f32 (w_rows, m_rows, v_rows).
    """
    kw = dict(lr=lr, l2=l2, b1=b1, b2=b2, eps=eps, row_offset=row_offset)
    if not use_kernel:
        return ref.sparse_gather_catchup_reference(
            w, m, v, last_step, uids, step, **kw)
    return sparse.sparse_gather_catchup(
        w, m, v, last_step, uids, step, interpret=interpret_off_tpu(), **kw)


@partial(
    jax.jit,
    static_argnames=("r", "zeta", "lr", "l2", "b1", "b2", "eps", "use_kernel",
                     "clip"),
    donate_argnums=(0, 1, 2, 3),
)
def sparse_update_scatter(
    w, m, v, last_step, uids, counts, w_rows, g_rows, m_rows, v_rows, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
    use_kernel=False, clip=True, row_offset=0,
):
    """CowClip+L2+Adam on caught-up rows, scattered back into the tables.

    Returns (w, m, v, last_step); absent ids' rows are untouched (decay
    stays pending in ``last_step``). ``row_offset`` as in
    ``sparse_gather_catchup``.
    """
    kw = dict(r=r, zeta=zeta, lr=lr, l2=l2, b1=b1, b2=b2, eps=eps, clip=clip,
              row_offset=row_offset)
    if not use_kernel:
        return ref.sparse_update_scatter_reference(
            w, m, v, last_step, uids, counts, w_rows, g_rows, m_rows, v_rows,
            step, **kw)
    w, m, v = sparse.sparse_update_scatter(
        w, m, v, uids, counts, w_rows, g_rows, m_rows, v_rows, step,
        interpret=interpret_off_tpu(), **kw)
    loc = jnp.where(counts > 0, uids - row_offset, w.shape[0])
    last_step = last_step.at[loc].set(
        step.astype(last_step.dtype), mode="drop")
    return w, m, v, last_step
