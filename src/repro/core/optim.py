"""From-scratch gradient-transformation algebra (optax-style, pure JAX).

optax is not available offline, so the framework carries its own minimal but
complete optimizer substrate: composable ``GradientTransformation``s, the
standard optimizers (SGD / Adam / AdamW-style L2), schedules, and a
``partition`` combinator used to run the paper's two parameter groups
(embedding tables vs. dense tower) under different rules.

Conventions
-----------
* ``update`` returns *updates* to be **added** to params (they already carry
  the negative sign after ``scale_by_neg_lr``).
* Extra per-step side inputs (CowClip's per-id batch counts) flow through the
  keyword-only ``**extras`` channel; transforms ignore extras they don't use.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

PyTree = Any
Schedule = Callable[[jnp.ndarray], jnp.ndarray]
ScalarOrSchedule = Union[float, Schedule]


class GradientTransformation(NamedTuple):
    """A pair of pure functions ``(init, update)``.

    init:   params -> state
    update: (grads, state, params, **extras) -> (updates, state)
    """

    init: Callable[[PyTree], PyTree]
    update: Callable[..., tuple[PyTree, PyTree]]


class EmptyState(NamedTuple):
    pass


def identity() -> GradientTransformation:
    def init_fn(params):
        del params
        return EmptyState()

    def update_fn(updates, state, params=None, **extras):
        del params, extras
        return updates, state

    return GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# elementary transforms
# ---------------------------------------------------------------------------


class ScaleState(NamedTuple):
    pass


def scale(step_size: float) -> GradientTransformation:
    def init_fn(params):
        del params
        return ScaleState()

    def update_fn(updates, state, params=None, **extras):
        del params, extras
        return jax.tree.map(lambda g: step_size * g, updates), state

    return GradientTransformation(init_fn, update_fn)


class ScaleByScheduleState(NamedTuple):
    count: jnp.ndarray  # int32 scalar


def scale_by_schedule(schedule: Schedule) -> GradientTransformation:
    def init_fn(params):
        del params
        return ScaleByScheduleState(count=jnp.zeros([], jnp.int32))

    def update_fn(updates, state, params=None, **extras):
        del params, extras
        step_size = schedule(state.count)
        updates = jax.tree.map(lambda g: step_size * g, updates)
        return updates, ScaleByScheduleState(count=state.count + 1)

    return GradientTransformation(init_fn, update_fn)


def scale_by_neg_lr(lr: ScalarOrSchedule) -> GradientTransformation:
    if callable(lr):
        return scale_by_schedule(lambda c: -lr(c))
    return scale(-lr)


class ScaleByAdamState(NamedTuple):
    count: jnp.ndarray
    mu: PyTree
    nu: PyTree


def scale_by_adam(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> GradientTransformation:
    """Standard Adam preconditioner with bias correction (Kingma & Ba 2015)."""

    def init_fn(params):
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        return ScaleByAdamState(count=jnp.zeros([], jnp.int32), mu=mu, nu=nu)

    def update_fn(updates, state, params=None, **extras):
        del params, extras
        count = state.count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, state.mu, updates)
        nu = jax.tree.map(
            lambda v, g: b2 * v + (1.0 - b2) * jnp.square(g), state.nu, updates
        )
        c = count.astype(jnp.float32)
        mu_hat_scale = 1.0 / (1.0 - b1**c)
        nu_hat_scale = 1.0 / (1.0 - b2**c)
        updates = jax.tree.map(
            lambda m, v: (m * mu_hat_scale) / (jnp.sqrt(v * nu_hat_scale) + eps),
            mu,
            nu,
        )
        return updates, ScaleByAdamState(count=count, mu=mu, nu=nu)

    return GradientTransformation(init_fn, update_fn)


def add_decayed_weights(weight_decay: float) -> GradientTransformation:
    """L2 regularization *through* the optimizer: g <- g + lambda * w.

    Matches the paper's setup: L2 loss ``(lambda/2)||w||^2`` contributes
    ``lambda * w`` to the gradient which then passes through Adam (this is the
    behaviour the paper's lambda-scaling analysis assumes, NOT decoupled
    AdamW decay).
    """

    def init_fn(params):
        del params
        return EmptyState()

    def update_fn(updates, state, params=None, **extras):
        del extras
        if params is None:
            raise ValueError("add_decayed_weights requires params")
        updates = jax.tree.map(lambda g, w: g + weight_decay * w, updates, params)
        return updates, state

    return GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# sparse row-wise variants (unique-id embedding update path)
# ---------------------------------------------------------------------------
#
# The dense embedding optimizer applies, to EVERY row of a [vocab, dim]
# table, every step:
#
#     touched (cnt > 0):  g <- clip(g) + l2 * w ;  Adam(m, v, g) ;
#                         w <- w - lr * update
#     absent  (cnt = 0):  w <- w * (1 - lr * l2) ;  m, v unchanged
#
# An absent id carries no loss gradient, so its step is a pure coupled-L2
# "decay" — the paper's "absent ids keep decaying" semantics. The decay is
# applied directly to the weight (not routed through Adam: running a zero
# gradient through the moment recursion would *also* drag m and v toward
# the L2 direction, which couples the denominator to the decayed weight
# and makes catch-up O(depth)). Under a constant (lr, l2) the absent-row
# recursion is geometric, so the sparse path keeps a per-row ``last_step``
# array and, when a row is next touched after k skipped steps, catches up
# in closed form:
#
#     w <- w * (1 - lr * l2) ** k        # O(1) in k
#
# with the factor rounded to f32 FIRST so the closed form tracks the
# dense path's repeated f32 multiply to a few ulps per step. When lr or
# l2 is a schedule (callable), the per-step factor is not constant and
# the closed form does not apply; ``decay_catchup_rows`` detects that at
# trace time and falls back to a capped vectorized replay window
# (``_window_decay_scale``). At l2 == 0 the factor is exactly 1 and decay
# is a no-op — once-touched rows hold still until their next gradient.


def decay_factor(lr: float, l2: float) -> float:
    """The per-step absent-row multiplier ``1 - lr * l2``, f32-rounded.

    Every path (substrate transform, fused row updates, sharded
    placements) derives the factor through this one helper so the rounding
    is identical everywhere. Returned as a Python float (exactly
    representable in f32) so it can also serve as a static kernel param.
    """
    import numpy as np

    return float(np.float32(1.0 - float(lr) * float(l2)))


def _factor_at(lr, l2, s):
    """Per-step decay factor under (possibly scheduled) lr/l2 at step(s) s."""
    s_f = s.astype(jnp.float32)
    lr_s = lr(s_f) if callable(lr) else lr
    l2_s = l2(s_f) if callable(l2) else l2
    return (jnp.float32(1.0)
            - jnp.asarray(lr_s, jnp.float32) * jnp.asarray(l2_s, jnp.float32))


def catchup_mode(lr, l2) -> str:
    """Which catch-up path ``decay_catchup_rows`` takes for these hypers.

    "closed_form" when both lr and l2 are constants (O(1) in pending
    depth), "replay_window" when either is a schedule (capped vectorized
    replay, exact up to ``replay_window`` pending steps)."""
    return "replay_window" if (callable(lr) or callable(l2)) else "closed_form"


def _window_decay_scale(last_step, k, *, lr, l2, window):
    """Per-row decay multiplier under a scheduled lr/l2: replay the newest
    ``window`` pending steps exactly (vectorized product, O(n * window)),
    and approximate any older steps geometrically at the first pending
    step's factor. Exact whenever k <= window, and at any depth when the
    schedule is constant-valued over the pending range."""
    last32 = last_step.astype(jnp.int32)
    i = jnp.arange(window, dtype=jnp.int32)
    # the newest min(k, window) global steps, descending from last_step + k
    s = (last32 + k)[:, None] - i[None, :]
    f = _factor_at(lr, l2, s)
    live = i[None, :] < jnp.minimum(k, window)[:, None]
    scale = jnp.prod(jnp.where(live, f, jnp.float32(1.0)), axis=1)
    k_exc = jnp.maximum(k - window, 0)
    tail = jnp.where(
        k_exc > 0,
        _factor_at(lr, l2, last32 + 1) ** k_exc.astype(jnp.float32),
        jnp.float32(1.0))
    return jnp.where(k > 0, scale * tail, jnp.float32(1.0))


def decay_catchup_rows(
    w_rows: jnp.ndarray,      # [n, dim] gathered rows (f32 math)
    m_rows: jnp.ndarray,      # [n, dim] Adam first moment rows
    v_rows: jnp.ndarray,      # [n, dim] Adam second moment rows
    last_step: jnp.ndarray,   # [n] int32, step each row was last updated at
    step: jnp.ndarray,        # scalar int32: rows catch up THROUGH this step
    *,
    lr,
    l2,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    replay_window: int = 64,
):
    """Apply each row's pending decay-only steps last_step+1 .. step.

    Closed form — ``w * (1 - lr*l2)**k`` with k = step - last_step — when
    lr and l2 are constants; a capped vectorized replay window when either
    is a schedule (detected at trace time). O(1) in pending depth either
    way; m and v pass through untouched (decay-only steps never move the
    Adam moments). b1/b2/eps are accepted for call-site compatibility with
    the touched-row update's hyper dict. Returns (w, m, v) in f32.

    k == 0 rows multiply by exactly 1.0, so a second flush is a bit-exact
    no-op.
    """
    del b1, b2, eps
    w = w_rows.astype(jnp.float32)
    m = m_rows.astype(jnp.float32)
    v = v_rows.astype(jnp.float32)
    k = jnp.maximum(step - last_step, 0)                     # [n]
    if callable(lr) or callable(l2):
        scale = _window_decay_scale(last_step, k, lr=lr, l2=l2,
                                    window=replay_window)
    else:
        factor = jnp.float32(decay_factor(lr, l2))
        scale = jnp.where(k > 0, factor ** k.astype(jnp.float32),
                          jnp.float32(1.0))
    return w * scale[:, None], m, v


def decay_replay_reference(
    w_rows: jnp.ndarray,      # [n, dim]
    last_step: jnp.ndarray,   # [n] int32
    step: jnp.ndarray,        # scalar int32: catch up THROUGH this step
    *,
    lr,
    l2,
):
    """Iterative one-multiply-per-step decay replay (the recursion the
    closed form collapses). O(max pending depth) — kept as the exactness
    oracle for property tests, not used on any hot path."""
    w = w_rows.astype(jnp.float32)
    k = jnp.maximum(step - last_step, 0)
    k_max = jnp.max(k) if k.size else jnp.zeros((), jnp.int32)
    const = not (callable(lr) or callable(l2))
    factor = jnp.float32(decay_factor(lr, l2)) if const else None

    def body(i, w):
        if const:
            w2 = w * factor
        else:
            w2 = w * _factor_at(lr, l2, last_step + 1 + i)[:, None]
        return jnp.where((i < k)[:, None], w2, w)

    return jax.lax.fori_loop(0, k_max, body, w)


def sparse_adam_rows(
    g_rows: jnp.ndarray,      # [n, dim] clipped task-loss gradient rows
    w_rows: jnp.ndarray,      # [n, dim] rows already caught up through t-1
    m_rows: jnp.ndarray,
    v_rows: jnp.ndarray,
    step: jnp.ndarray,        # scalar int32 t, 1-based
    *,
    lr: float,
    l2: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """The real update at step t on gathered rows: coupled L2 + Adam + apply.

    Identical math to ``add_decayed_weights`` -> ``scale_by_adam`` ->
    ``scale_by_neg_lr`` on a full table, restricted to the touched rows.
    Returns (w, m, v) in f32.
    """
    w = w_rows.astype(jnp.float32)
    g = g_rows.astype(jnp.float32) + l2 * w
    m = b1 * m_rows.astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_rows.astype(jnp.float32) + (1.0 - b2) * jnp.square(g)
    t = step.astype(jnp.float32)
    mu_hat_scale = 1.0 / (1.0 - b1**t)
    nu_hat_scale = 1.0 / (1.0 - b2**t)
    w = w - lr * (m * mu_hat_scale) / (jnp.sqrt(v * nu_hat_scale) + eps)
    return w, m, v


def lazy_coupled_adam(
    lr: ScalarOrSchedule,
    l2: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> GradientTransformation:
    """Count-aware embedding optimizer tail: coupled-L2 Adam on rows the
    batch touched, one geometric decay step on rows it did not.

    Replaces ``add_decayed_weights -> scale_by_adam -> scale_by_neg_lr`` in
    the embedding group. Touched rows (``counts > 0``) run bit-identical
    math to that chain; absent rows take ``w <- w * (1 - lr*l2)`` with m, v
    held — the dense-side counterpart of the sparse paths' lazy closed-form
    catch-up (see the decay section above). The absent-row update is emitted
    as ``w*factor - w``, which is exact (Sterbenz) for factors near 1, so
    ``apply_updates``' ``w + u`` lands on fl(w * factor) bit-for-bit — the
    same value the fused row updates write directly.

    Requires the per-id batch ``counts=`` extra (shape [vocab] per table,
    matching the params subtree); raises ValueError without it.
    """

    def init_fn(params):
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        return ScaleByAdamState(count=jnp.zeros([], jnp.int32), mu=mu, nu=nu)

    def update_fn(updates, state, params=None, *, counts=None, **extras):
        del extras
        if params is None:
            raise ValueError("lazy_coupled_adam requires params")
        if counts is None:
            raise ValueError(
                "lazy_coupled_adam requires counts= (per-id batch "
                "occurrence counts, one [vocab] array per table)")
        count = state.count + 1
        c = count.astype(jnp.float32)
        lr_t = lr(c) if callable(lr) else lr
        mu_hat_scale = 1.0 / (1.0 - b1**c)
        nu_hat_scale = 1.0 / (1.0 - b2**c)
        if callable(lr):
            factor = (jnp.float32(1.0)
                      - jnp.asarray(lr_t, jnp.float32) * jnp.float32(l2))
        else:
            factor = jnp.float32(decay_factor(lr, l2))

        def leaf(g, w, m, v, cnt):
            g = g + l2 * w
            m2 = b1 * m + (1.0 - b1) * g
            v2 = b2 * v + (1.0 - b2) * jnp.square(g)
            adam_u = (-lr_t) * (m2 * mu_hat_scale) / (
                jnp.sqrt(v2 * nu_hat_scale) + eps)
            touched = (cnt > 0.0)[:, None]
            u = jnp.where(touched, adam_u, w * factor - w)
            return u, jnp.where(touched, m2, m), jnp.where(touched, v2, v)

        triples = jax.tree.map(leaf, updates, params, state.mu, state.nu,
                               counts)
        is_triple = lambda x: isinstance(x, tuple) and len(x) == 3
        new_updates = jax.tree.map(lambda t: t[0], triples, is_leaf=is_triple)
        mu = jax.tree.map(lambda t: t[1], triples, is_leaf=is_triple)
        nu = jax.tree.map(lambda t: t[2], triples, is_leaf=is_triple)
        return new_updates, ScaleByAdamState(count=count, mu=mu, nu=nu)

    return GradientTransformation(init_fn, update_fn)


def global_norm(tree: PyTree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros([], jnp.float32)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def clip_by_global_norm(max_norm: float) -> GradientTransformation:
    def init_fn(params):
        del params
        return EmptyState()

    def update_fn(updates, state, params=None, **extras):
        del params, extras
        gnorm = global_norm(updates)
        scale_factor = jnp.minimum(1.0, max_norm / (gnorm + 1e-12))
        updates = jax.tree.map(lambda g: g * scale_factor, updates)
        return updates, state

    return GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# combinators
# ---------------------------------------------------------------------------


def chain(*transforms: GradientTransformation) -> GradientTransformation:
    def init_fn(params):
        return tuple(t.init(params) for t in transforms)

    def update_fn(updates, state, params=None, **extras):
        new_state = []
        for t, s in zip(transforms, state):
            updates, s = t.update(updates, s, params, **extras)
            new_state.append(s)
        return updates, tuple(new_state)

    return GradientTransformation(init_fn, update_fn)


class PartitionState(NamedTuple):
    inner_states: dict


def partition(
    transforms: dict,
    label_fn: Callable[[PyTree], PyTree],
) -> GradientTransformation:
    """Apply a different transformation per labelled parameter group.

    ``label_fn(params)`` returns a pytree of string labels with the same
    structure as ``params``. Extras are forwarded to every group (each group's
    transform picks what it needs); pytree-shaped extras must be passed
    pre-partitioned as ``{label: extra_subtree}`` via ``partitioned_extras``.
    """

    group_names = tuple(sorted(transforms))

    def _masked(tree, labels, name):
        return jax.tree.map(
            lambda x, lbl: x if lbl == name else None,
            tree,
            labels,
            is_leaf=lambda x: x is None,
        )

    def _merge(trees, labels):
        def pick(lbl, *vals):
            return vals[group_names.index(lbl)]

        return jax.tree.map(pick, labels, *trees, is_leaf=lambda x: x is None)

    def init_fn(params):
        labels = label_fn(params)
        states = {
            name: transforms[name].init(_masked(params, labels, name))
            for name in group_names
        }
        return PartitionState(inner_states=states)

    def update_fn(updates, state, params=None, *, partitioned_extras=None, **extras):
        labels = label_fn(updates)
        new_states = {}
        outs = []
        for name in group_names:
            sub_updates = _masked(updates, labels, name)
            sub_params = None if params is None else _masked(params, labels, name)
            group_extras = dict(extras)
            if partitioned_extras and name in partitioned_extras:
                group_extras.update(partitioned_extras[name])
            out, new_s = transforms[name].update(
                sub_updates, state.inner_states[name], sub_params, **group_extras
            )
            outs.append(out)
            new_states[name] = new_s
        merged = _merge(outs, labels)
        return merged, PartitionState(inner_states=new_states)

    return GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# canned optimizers
# ---------------------------------------------------------------------------


def sgd(lr: ScalarOrSchedule, l2: float = 0.0) -> GradientTransformation:
    steps = []
    if l2:
        steps.append(add_decayed_weights(l2))
    steps.append(scale_by_neg_lr(lr))
    return chain(*steps)


def adam(
    lr: ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    l2: float = 0.0,
) -> GradientTransformation:
    steps = []
    if l2:
        steps.append(add_decayed_weights(l2))
    steps.append(scale_by_adam(b1=b1, b2=b2, eps=eps))
    steps.append(scale_by_neg_lr(lr))
    return chain(*steps)


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    return jax.tree.map(
        lambda p, u: (p + u.astype(p.dtype)) if u is not None else p,
        params,
        updates,
        is_leaf=lambda x: x is None,
    )
