"""Plain reference of the CTR models the benchmark runs, and their training.

Written from the published descriptions, in float32 at the highest matmul
precision, with full ``[vocab, dim]`` tables and no dedup, cache, kernel or
lazy bookkeeping. It imports nothing of the program.

Models (``cfg["model"]``):

* ``deepfm`` (Guo et al., IJCAI 2017): logit = first-order sum + FM
  pairwise term + MLP tower;
* ``dcnv2`` (Wang et al., WWW 2021, stacked): ``x_{l+1} = x0 * (W_l x_l +
  b_l) + x_l`` for ``n_cross`` layers beside the MLP tower, then one linear
  combiner over both.

The tower input is the flattened field embeddings followed by the dense
features; the MLP is ReLU after every hidden layer.

Training follows the CowClip paper (Zheng et al., AAAI 2023, Alg. 1) with the
paper's coupled L2: for each embedding table and step, an id present in the
batch has its gradient row clipped to ``cnt * max(r * ||w||, zeta)`` (not for
1-wide first-order tables), gets ``l2 * w`` added, and takes an Adam step; an
absent id only decays, ``w *= 1 - lr * l2``, its moments held. The dense
tower takes plain Adam with a linear warm-up of its learning rate and no L2.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def tower_widths(cfg) -> int:
    """Width of the tower input: flattened embeddings + dense features."""
    return len(cfg["vocab_sizes"]) * cfg["emb_dim"] + cfg["n_dense"]


def param_shapes(cfg) -> dict:
    """``{group: {name: shape}}`` of every parameter."""
    d0, dim = tower_widths(cfg), cfg["emb_dim"]
    fields = {f"field_{i}": v for i, v in enumerate(cfg["vocab_sizes"])}
    embed = {"fm": {f: (v, dim) for f, v in fields.items()}}
    widths = (d0,) + tuple(cfg["mlp_dims"])
    mlp = {}
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        mlp[f"w{i}"], mlp[f"b{i}"] = (a, b), (b,)
    dense = {"mlp": mlp}
    if cfg["model"] == "deepfm":
        embed["lin"] = {f: (v, 1) for f, v in fields.items()}
        dense["lin_bias"] = ()
        dense["deep_out"] = {"w0": (widths[-1], 1), "b0": (1,)}
    elif cfg["model"] == "dcnv2":
        cross = {}
        for i in range(cfg["n_cross"]):
            cross[f"w{i}"], cross[f"b{i}"] = (d0, d0), (d0,)
        dense["cross"] = cross
        dense["combine"] = {"w0": (d0 + widths[-1], 1), "b0": (1,)}
    else:
        raise ValueError(f"no reference for model {cfg['model']!r}")
    return {"embed": embed, "dense": dense}


def _init_leaf(key, path: str, shape, cfg):
    """Tables N(0, emb_sigma); cross weights N(0, 1/d0); other weights
    Kaiming-normal N(0, 2/fan_in); biases 0."""
    name = path.rsplit("/", 1)[-1]
    if path.startswith("embed/"):
        return cfg["emb_sigma"] * jax.random.normal(key, shape, jnp.float32)
    if not name.startswith("w"):
        return jnp.zeros(shape, jnp.float32)
    scale = (1.0 / math.sqrt(shape[0]) if "/cross/" in f"/{path}"
             else math.sqrt(2.0 / shape[0]))
    return scale * jax.random.normal(key, shape, jnp.float32)


def _paths(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _paths(v, p + "/")
        else:
            yield p, v


def _build(tree, fn, prefix=""):
    return {k: (_build(v, fn, f"{prefix}{k}/") if isinstance(v, dict)
                else fn(f"{prefix}{k}", v)) for k, v in tree.items()}


def init_tree(cfg, seed):
    """Every parameter from one 32-bit seed (traceable). Leaf ``i`` (sorted
    paths) draws from ``fold_in(key, i)``, so a leaf does not depend on the
    others."""
    shapes = param_shapes(cfg)
    index = {p: i for i, (p, _) in enumerate(_paths(shapes))}
    key = jax.random.key(seed)
    return _build(shapes, lambda p, s: _init_leaf(
        jax.random.fold_in(key, index[p]), p, s, cfg))


def init_params(cfg, key_seed: int):
    """``init_tree`` in one jitted call on the default device."""
    return jax.jit(lambda s: init_tree(cfg, s))(jnp.uint32(key_seed))


# ---------------------------------------------------------------- forward


def _mlp(p, x, n, dot):
    for i in range(n):
        x = jax.nn.relu(dot(x, p[f"w{i}"]) + p[f"b{i}"])
    return x


def _split(x):
    """``x`` as a bfloat16 head and a bfloat16 tail, ``x ~ hi + lo``."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot3(a, b):
    """``a @ b`` in three bfloat16 passes accumulated in float32 (the
    tails' product dropped): JAX's ``high`` matmul precision on a TPU."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = partial(jnp.matmul, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.custom_vjp
def high_dot(a, b):
    """A matrix product in three bfloat16 passes, forward and backward: the
    control, one precision step below float32 at ``highest``."""
    return _dot3(a, b)


def _high_fwd(a, b):
    return _dot3(a, b), (a, b)


def _high_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


high_dot.defvjp(_high_fwd, _high_bwd)


def logits(params, cfg, ids, dense, dot=jnp.matmul):
    """Forward pass from full tables -> [batch] logits; ``dot`` computes
    every matrix product of the tower."""
    n_f = ids.shape[1]
    tables = params["embed"]["fm"]
    emb = jnp.stack([tables[f"field_{i}"][ids[:, i]] for i in range(n_f)],
                    axis=1)                                    # [B, F, D]
    d = params["dense"]
    x0 = jnp.concatenate([emb.reshape(emb.shape[0], -1), dense], axis=-1)
    deep = _mlp(d["mlp"], x0, len(cfg["mlp_dims"]), dot)
    if cfg["model"] == "deepfm":
        lin_t = params["embed"]["lin"]
        lin = sum(lin_t[f"field_{i}"][ids[:, i], 0] for i in range(n_f))
        s = emb.sum(axis=1)
        fm = 0.5 * (s * s - (emb * emb).sum(axis=1)).sum(axis=-1)
        out = (dot(deep, d["deep_out"]["w0"]) + d["deep_out"]["b0"])[:, 0]
        return lin + d["lin_bias"] + fm + out
    x = x0
    for i in range(cfg["n_cross"]):
        x = x0 * (dot(x, d["cross"][f"w{i}"]) + d["cross"][f"b{i}"]) + x
    both = jnp.concatenate([x, deep], axis=-1)
    return (dot(both, d["combine"]["w0"]) + d["combine"]["b0"])[:, 0]


def loss(params, cfg, batch, dot=jnp.matmul):
    """Mean binary cross-entropy of the batch."""
    z = logits(params, cfg, batch["ids"], batch["dense"], dot)
    y = batch["labels"]
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


# ---------------------------------------------------------------- training


def hyper(cfg, batch_size: int, warmup_steps: int) -> dict:
    """The CowClip rule from the base recipe to ``batch_size``: embedding lr
    kept, embedding L2 times s, dense lr times sqrt(s) (s = batch / base)."""
    s = batch_size / cfg["base_batch"]
    return {"emb_lr": cfg["base_lr"], "emb_l2": cfg["base_l2"] * s,
            "dense_lr": cfg["base_dense_lr"] * math.sqrt(s),
            "warmup": warmup_steps, "r": cfg["clip_r"],
            "zeta": cfg["clip_zeta"], "b1": cfg["adam_b1"],
            "b2": cfg["adam_b2"], "eps": cfg["adam_eps"]}


def init_opt(params):
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)  # noqa: E731
    return {"m": zeros(params), "v": zeros(params)}


def _adam(w, g, m, v, t, lr, hp):
    m = hp["b1"] * m + (1 - hp["b1"]) * g
    v = hp["b2"] * v + (1 - hp["b2"]) * g * g
    m_hat = m / (1 - hp["b1"] ** t)
    v_hat = v / (1 - hp["b2"] ** t)
    return w - lr * m_hat / (jnp.sqrt(v_hat) + hp["eps"]), m, v


def _table_step(w, g, cnt, m, v, t, hp):
    if w.shape[1] > 1:
        gn = jnp.sqrt(jnp.sum(g * g, axis=1))
        wn = jnp.sqrt(jnp.sum(w * w, axis=1))
        bound = cnt * jnp.maximum(hp["r"] * wn, hp["zeta"])
        g = g * jnp.where(gn > bound, bound / jnp.where(gn > 0, gn, 1), 1.0)[:, None]
    g = g + hp["emb_l2"] * w
    w_t, m_t, v_t = _adam(w, g, m, v, t, hp["emb_lr"], hp)
    hit = (cnt > 0)[:, None]
    decay = np.float32(1.0 - hp["emb_lr"] * hp["emb_l2"])
    return (jnp.where(hit, w_t, w * decay), jnp.where(hit, m_t, m),
            jnp.where(hit, v_t, v))


def make_step(cfg, hp, dot=jnp.matmul):
    """jitted ``(params, opt, batch, t) -> (params, opt, loss)``, donated;
    ``t`` is the 1-based step. ``dot``: the tower's matrix product."""

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, batch, t):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(loss)(params, cfg, batch, dot)
        tf = t.astype(jnp.float32)
        ids = batch["ids"]
        new_p, new_m, new_v = {"embed": {}}, {"embed": {}}, {"embed": {}}
        for group, tables in params["embed"].items():
            for g in (new_p, new_m, new_v):
                g["embed"][group] = {}
            for name, w in tables.items():
                col = ids[:, int(name.split("_")[1])]
                cnt = jnp.zeros(w.shape[0], jnp.float32).at[col].add(1.0)
                out = _table_step(w, grads["embed"][group][name], cnt,
                                  opt["m"]["embed"][group][name],
                                  opt["v"]["embed"][group][name], tf, hp)
                for g, o in zip((new_p, new_m, new_v), out):
                    g["embed"][group][name] = o
        lr = hp["dense_lr"] * jnp.minimum(1.0, tf / hp["warmup"])
        dense = jax.tree.map(
            lambda w, g, m, v: _adam(w, g, m, v, tf, lr, hp),
            params["dense"], grads["dense"], opt["m"]["dense"],
            opt["v"]["dense"])
        for i, g in enumerate((new_p, new_m, new_v)):
            g["dense"] = jax.tree.map(lambda o: o[i], dense,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return new_p, {"m": new_m, "v": new_v}, value

    return step
