"""jit'd public wrappers for the fused CowClip updates (dense + sparse).

The row math is ``ref``'s jnp, compiled by XLA on every backend.
``fused_cowclip_adam`` is the dense update, ``sparse_gather_catchup`` /
``sparse_update_scatter`` the unique-id-path pair.
"""

from __future__ import annotations

from functools import partial

import jax

from . import ref
from .ref import cowclip_adam_reference as reference


@partial(
    jax.jit,
    static_argnames=("r", "zeta", "lr", "l2", "b1", "b2", "eps"),
)
def fused_cowclip_adam(
    w, g, cnt, m, v, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
):
    return reference(w, g, cnt, m, v, step, r=r, zeta=zeta, lr=lr, l2=l2,
                     b1=b1, b2=b2, eps=eps)


@partial(
    jax.jit,
    static_argnames=("lr", "l2", "b1", "b2", "eps"),
)
def sparse_gather_catchup(
    w, m, v, last_step, uids, step, *,
    lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8, row_offset=0,
):
    """Gather unique rows + apply pending lazy-L2 decay (through step - 1)
    in closed form — ``w *= (1 - lr*l2)**k``, O(1) in pending depth.

    ``uids`` are the slot uids (pads out of range). ``row_offset`` is the
    shard-offset form: ``w``/``m``/``v``/``last_step`` are one row-shard
    and ``uids`` global ids of rows that shard owns. ``m``/``v`` are
    ``[rows, dim]`` or packed (``ref.pack_rows``), told apart by shape.
    Returns f32 (w_rows, m_rows, v_rows).
    """
    return ref.sparse_gather_catchup_reference(
        w, m, v, last_step, uids, step, lr=lr, l2=l2, b1=b1, b2=b2, eps=eps,
        row_offset=row_offset)


@partial(
    jax.jit,
    static_argnames=("r", "zeta", "lr", "l2", "b1", "b2", "eps", "clip"),
    donate_argnums=(0, 1, 2, 3),
)
def sparse_update_scatter(
    w, m, v, last_step, uids, counts, w_rows, g_rows, m_rows, v_rows, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
    clip=True, row_offset=0,
):
    """CowClip+L2+Adam on caught-up rows, scattered back into the tables.

    Returns (w, m, v, last_step); absent ids' rows are untouched (decay
    stays pending in ``last_step``). ``row_offset`` as in
    ``sparse_gather_catchup``.
    """
    return ref.sparse_update_scatter_reference(
        w, m, v, last_step, uids, counts, w_rows, g_rows, m_rows, v_rows,
        step, r=r, zeta=zeta, lr=lr, l2=l2, b1=b1, b2=b2, eps=eps, clip=clip,
        row_offset=row_offset)
