"""The fused CowClip+L2+Adam row update (dense + sparse) in jnp.

This is the embedding row update every placement runs, on every backend:
``ops`` jits these functions and XLA compiles them. They compose the
framework's own building blocks (``core.cowclip.cowclip_table`` + coupled
L2 + Adam with bias correction), so the update is the exact math the
optimizer substrate uses. Rows absent from the batch (``cnt == 0``) take
one geometric L2 decay step — ``w *= 1 - lr*l2`` with the Adam moments
held — matching ``core.optim.lazy_coupled_adam``. The sparse functions
additionally compose ``core.optim.decay_catchup_rows`` /
``sparse_adam_rows`` — the closed-form lazy-decay semantics the unique-id
path must preserve.

The sparse path's moment tables may be stored packed (``pack_rows``): the
row functions here read and write either form, telling them apart by
shape.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ...core.cowclip import cowclip_rows, cowclip_table
from ...core.optim import decay_catchup_rows, decay_factor, sparse_adam_rows


def cowclip_adam_reference(
    w, g, cnt, m, v, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
):
    w32 = w.astype(jnp.float32)
    m_in = m.astype(jnp.float32)
    v_in = v.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    g32 = cowclip_table(g32, w32, cnt, r=r, zeta=zeta)
    g32 = g32 + l2 * w32

    m32 = b1 * m_in + (1.0 - b1) * g32
    v32 = b2 * v_in + (1.0 - b2) * jnp.square(g32)
    t = step.astype(jnp.float32)
    m_hat = m32 / (1.0 - b1**t)
    v_hat = v32 / (1.0 - b2**t)
    touched = (cnt > 0.0)[:, None]
    w32 = jnp.where(touched,
                    w32 - lr * m_hat / (jnp.sqrt(v_hat) + eps),
                    w32 * jnp.float32(decay_factor(lr, l2)))
    m32 = jnp.where(touched, m32, m_in)
    v32 = jnp.where(touched, v32, v_in)
    return w32.astype(w.dtype), m32.astype(m.dtype), v32.astype(v.dtype)


# ---------------------------------------------------------------------------
# sparse unique-id path
# ---------------------------------------------------------------------------


def sparse_gather_catchup_reference(
    w, m, v, last_step, uids, step, *,
    lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8, row_offset=0,
):
    """Gather unique rows and apply their pending decay in closed form.

    ``uids`` is [capacity] int32 (pad slots out of range — their gather
    clips to the last row and produces garbage that is masked downstream).
    ``row_offset`` is subtracted from uids first: the shard-offset form
    used when ``w`` is one row-shard of a partitioned table and ``uids``
    are global ids. A pad uid minus the offset may land back in range (the
    global ``vocab`` sentinel on a late shard) — harmless here, since a
    pad slot's gathered rows are garbage under every convention and
    callers mask them by ``counts``; only *scatters* must force pads out
    of range, which ``sparse_update_scatter_reference`` does itself.
    Rows come out caught up **through step - 1**, i.e. as the dense path
    would see them at the start of step ``step``. Returns f32
    (w_rows, m_rows, v_rows).
    """
    loc = uids - row_offset
    dim = w.shape[1]
    w_rows = w[loc]
    m_rows = gather_rows(m, loc, dim)
    v_rows = gather_rows(v, loc, dim)
    ls = last_step[loc]
    return decay_catchup_rows(
        w_rows, m_rows, v_rows, ls, step - 1,
        lr=lr, l2=l2, b1=b1, b2=b2, eps=eps,
    )


def sparse_update_scatter_reference(
    w, m, v, last_step, uids, counts, w_rows, g_rows, m_rows, v_rows, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
    clip=True, row_offset=0,
):
    """CowClip + coupled L2 + Adam on caught-up rows, scattered back.

    Pad slots carry out-of-range uids and are dropped by the scatter; their
    row values never land. ``row_offset`` as in
    ``sparse_gather_catchup_reference`` — pad uids must stay out of range
    after subtraction, which the pad-slot masking here enforces regardless
    (a pad slot is any slot with ``counts == 0``). Returns
    (w, m, v, last_step) full tables.
    """
    # pad slots (counts == 0) are forced out of range — with a row_offset
    # the raw pad uid (vocab) minus the offset could otherwise land in range
    keep = counts > 0
    loc = uids - row_offset
    out = jnp.where(keep, loc, w.shape[0])
    g32 = g_rows.astype(jnp.float32)
    if clip:
        g32 = cowclip_rows(g32, w_rows, counts, r=r, zeta=zeta)
    w_new, m_new, v_new = sparse_adam_rows(
        g32, w_rows, m_rows, v_rows, step,
        lr=lr, l2=l2, b1=b1, b2=b2, eps=eps,
    )
    w = w.at[out].set(w_new.astype(w.dtype), mode="drop")
    m = scatter_rows(m, loc, m_new, keep)
    v = scatter_rows(v, loc, v_new, keep)
    last_step = last_step.at[out].set(
        step.astype(last_step.dtype), mode="drop")
    return w, m, v, last_step


def sparse_cowclip_adam_reference(
    w, m, v, last_step, uids, counts, g_rows, step, *,
    r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5, b1=0.9, b2=0.999, eps=1e-8,
    row_offset=0,
):
    """Full sparse step oracle (gather -> catch-up -> clip -> Adam -> scatter)
    given the task-loss gradient on gathered rows. The per-step dense
    equivalent is ``cowclip_adam_reference`` over the whole table."""
    kw = dict(lr=lr, l2=l2, b1=b1, b2=b2, eps=eps, row_offset=row_offset)
    w_rows, m_rows, v_rows = sparse_gather_catchup_reference(
        w, m, v, last_step, uids, step, **kw)
    return sparse_update_scatter_reference(
        w, m, v, last_step, uids, counts, w_rows, g_rows, m_rows, v_rows,
        step, r=r, zeta=zeta, clip=True, **kw)


# ---------------------------------------------------------------------------
# packed moment tables
# ---------------------------------------------------------------------------
#
# A TPU tiles a 2-D f32 array in (8, 128) blocks, so a ``[V, dim]`` table
# with a narrow ``dim`` is either padded to 128 lanes per row (row-major,
# 128 / dim times its size) or stored dim-major, where one row is ``dim``
# sublanes of one lane and a row gather or scatter touches it element by
# element. The packed form puts ``k = 128 // dim`` rows side by side in one
# 128-lane row: row ``i`` lives in lane row ``i // k``, lanes
# ``(i % k) * dim`` to ``+ dim``. The table is then row-major with at most
# ``128 - k * dim`` pad lanes per lane row, and a row's read or write
# touches one lane row. Pad lanes (and the pad rows past ``V``) hold zeros,
# and no write reaches them.

LANES = 128


def packs(dim: int) -> bool:
    """Whether a ``[V, dim]`` moment table is stored packed: rows of two or
    more values, two or more to a lane row. A ``[V, 1]`` table is not: XLA
    lays it out with ids along the lanes already, and its one-value row
    writes cost less than a packed table's lane-row writes."""
    return dim >= 2 and LANES // dim >= 2


def packed_shape(vocab: int, dim: int) -> tuple:
    k = LANES // dim
    return (-(-vocab // k), LANES)


def packed_moment_bytes(tables) -> tuple:
    """``(tables packed, tables, bytes, bytes packed)`` of the two Adam
    moments of ``tables`` (``[V, dim]`` arrays or their shapes and dtypes):
    what the packed form engages, static, from shapes alone."""
    n_packed = raw = stored = 0
    for t in tables:
        size = t.dtype.itemsize * 2
        raw += size * t.shape[0] * t.shape[1]
        if packs(t.shape[1]):
            n_packed += 1
            rows, lanes = packed_shape(*t.shape)
            stored += size * rows * lanes
        else:
            stored += size * t.shape[0] * t.shape[1]
    return n_packed, len(tables), raw, stored


def pack_rows(t):
    """``[V, dim]`` -> the packed ``[ceil(V / k), 128]`` form."""
    vocab, dim = t.shape
    k = LANES // dim
    n = packed_shape(vocab, dim)[0]
    t = jnp.pad(t, ((0, n * k - vocab), (0, 0))).reshape(n, k * dim)
    return jnp.pad(t, ((0, 0), (0, LANES - k * dim)))


def unpack_rows(p, vocab: int, dim: int):
    """The packed form -> ``[vocab, dim]``."""
    k = LANES // dim
    return p[:, :k * dim].reshape(-1, dim)[:vocab]


def gather_packed(p, loc, dim: int):
    """Rows ``loc`` (``[n]``) of a packed table as ``[n, dim]``: one gather
    of whole lane rows, then each slot's ``dim`` lanes picked exactly (the
    other rows of its lane row masked to -inf under a max, so the one kept
    value comes out bit for bit, -0.0 and NaN included). ``loc`` past the
    table reads garbage, as an out-of-range gather from ``[V, dim]`` does."""
    k = LANES // dim
    lanes = p[loc // k][:, :k * dim].reshape(-1, k, dim)
    mine = jnp.arange(k)[None, :, None] == (loc % k)[:, None, None]
    return jnp.where(mine, lanes, -jnp.inf).max(axis=1)


def scatter_packed(p, loc, rows, keep):
    """Set rows ``loc`` of a packed table to ``rows`` (``[n, dim]``) where
    ``keep``, and write nothing else. The kept slots' ``loc`` ascend, each
    at most once, ahead of the dropped ones (``models.embedding``'s unique
    slots), so slots that share a lane row sit next to each other.

    Each such run of slots is merged into its lane row's old contents (the
    slots own disjoint lanes) in ``log2(k)`` steps that take the lanes of
    the slot 1, 2, 4, ... places back, and the run's last slot writes the
    whole lane row, as a row-major table's rows are written. A dropped slot
    goes out of range before the division by ``k``: its ``loc`` could name
    a pad row of the last lane row."""
    n, dim = rows.shape
    k = LANES // dim
    row = jnp.where(keep, loc // k, p.shape[0])
    owned = (jnp.arange(LANES) // dim)[None, :] == (loc % k)[:, None]
    new = jnp.pad(jnp.tile(rows.astype(p.dtype), (1, k)),
                  ((0, 0), (0, LANES - k * dim)))
    # the gather phase's own index expression: XLA merges the two gathers
    # of a step into one and hands its lane rows to this merge
    lanes = jnp.where(owned, new, p[loc // k])
    back = 1
    while back < k:
        same = (jnp.pad(row, (back, 0), constant_values=-1)[:n]
                == row)[:, None]
        took = same & jnp.pad(owned, ((back, 0), (0, 0)))[:n]
        lanes = jnp.where(took, jnp.pad(lanes, ((back, 0), (0, 0)))[:n],
                          lanes)
        owned = owned | took
        back *= 2
    last = row != jnp.pad(row[1:], (0, 1), constant_values=-1)
    return p.at[jnp.where(last, row, p.shape[0])].set(lanes, mode="drop")


def gather_rows(t, loc, dim: int):
    """Rows ``loc`` of a moment table in either form."""
    return t[loc] if t.shape[1] == dim else gather_packed(t, loc, dim)


def scatter_rows(t, loc, rows, keep):
    """``t`` with rows ``loc`` set to ``rows`` where ``keep``, in either
    form (for the packed one, as ``scatter_packed`` asks)."""
    if t.shape[1] == rows.shape[1]:
        return t.at[jnp.where(keep, loc, t.shape[0])].set(
            rows.astype(t.dtype), mode="drop")
    return scatter_packed(t, loc, rows, keep)
