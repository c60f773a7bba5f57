"""The paper's four CTR prediction models: W&D, DeepFM, DCN, DCN-v2.

Faithful to the paper's appendix setting: embedding dim 10, deep tower
3 x 400 ReLU, 3 cross layers, continuous fields feed only the DNN stream,
first-order (LR) tables are 1-dim embeddings exempt from CowClip.

Pure-functional: ``init(key, cfg) -> params``, ``apply(params, cfg, batch)``.
Params are split ``{"embed": ..., "dense": ...}`` for the two-group optimizer.
With emb dim 10 on Criteo-shape vocabs the dense tower is ~0.43M params
(DCN-v2 ~0.66M) vs ~10^8 embedding params — paper Table 1.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from . import embedding


@dataclasses.dataclass(frozen=True)
class CTRConfig:
    name: str                      # "wd" | "deepfm" | "dcn" | "dcnv2"
    vocab_sizes: tuple             # per categorical field
    n_dense: int = 13
    emb_dim: int = 10
    mlp_dims: tuple = (400, 400, 400)
    n_cross: int = 3
    emb_sigma: float = 1e-4        # 1e-2 for CowClip's large-init variant
    dtype: str = "float32"
    # Sparse unique-id update path: embedding forward/backward/optimizer run
    # on [n_unique, dim] gathered rows instead of the full [vocab, dim]
    # tables (update traffic O(batch) instead of O(vocab)). The dense path
    # stays available as the exactness oracle.
    sparse: bool = False
    # Padded capacity of the per-field unique-id set; <= 0 means the exact
    # default min(batch, vocab_f) (per shard under the sharded_sparse
    # placement: min(batch, rows_per_shard)). Smaller values bound memory
    # but overflow: the sparse placement drops gradient contributions
    # (see models/embedding.py), sharded_sparse falls back to the dense
    # per-shard update for the overflowing shard (exact, slower).
    unique_capacity: int = 0
    # Embedding placement (repro.embed.EmbeddingStore): one of
    # core.TRAIN_PATHS ("substrate" | "fused" | "sparse" | "sharded" |
    # "sharded_sparse" | "hotcold"). None defers to the legacy ``sparse``
    # knob above.
    placement: str | None = None
    # Mixed-precision compute dtype for the forward/backward ("float32" |
    # "bfloat16"), following the models/layers.py convention: tower
    # activations, looked-up embedding activations and dense-tower weights
    # are cast to this dtype at use; master embeddings, dense-tower
    # masters, CowClip norms/counts and Adam moments all stay float32
    # (logits are cast back to f32 before the loss, and gradients flow
    # through the casts back to f32 cotangents). bf16 halves activation
    # bandwidth on TPU-class chips; final AUC stays within 2e-3 of fp32
    # (tests/test_engine.py).
    compute_dtype: str = "float32"

    @property
    def n_fields(self) -> int:
        return len(self.vocab_sizes)

    @property
    def d0(self) -> int:
        """Cross/deep input width: flattened embeddings + dense feats."""
        return self.n_fields * self.emb_dim + self.n_dense


MODEL_NAMES = ("wd", "deepfm", "dcn", "dcnv2")

# The cross network of ``dcn`` and ``dcnv2`` runs under this
# ``jax.named_scope``, forward and backward, so a trace can tell it apart
# from the rest of the tower.
CROSS_SCOPE = "cross"


def _dense_init(key, fan_in, fan_out):
    """Kaiming-normal for ReLU towers (He et al. 2015, as in the paper)."""
    w = jax.random.normal(key, (fan_in, fan_out)) * jnp.sqrt(2.0 / fan_in)
    return w.astype(jnp.float32)


def _init_mlp(key, dims: Sequence[int]) -> dict:
    params = {}
    keys = jax.random.split(key, len(dims) - 1)
    for i, (k, din, dout) in enumerate(zip(keys, dims[:-1], dims[1:])):
        params[f"w{i}"] = _dense_init(k, din, dout)
        params[f"b{i}"] = jnp.zeros((dout,), jnp.float32)
    return params


def _apply_mlp(params: dict, x: jnp.ndarray, n_layers: int) -> jnp.ndarray:
    for i in range(n_layers):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


def init(key: jax.Array, cfg: CTRConfig) -> dict:
    if cfg.name not in MODEL_NAMES:
        raise ValueError(f"unknown CTR model {cfg.name!r}")
    k_emb, k_lin, k_mlp, k_cross, k_out = jax.random.split(key, 5)

    embed = {"fm": embedding.init_field_tables(
        k_emb, cfg.vocab_sizes, cfg.emb_dim, sigma=cfg.emb_sigma)}
    dense: dict = {}

    # Deep tower: input -> 3x400 -> 1 (last hidden feeds the combiner).
    mlp_dims = (cfg.d0,) + tuple(cfg.mlp_dims)
    dense["mlp"] = _init_mlp(k_mlp, mlp_dims)

    if cfg.name in ("wd", "deepfm"):
        # First-order LR stream: 1-dim embedding per field + global bias.
        embed["lin"] = embedding.init_field_tables(
            k_lin, cfg.vocab_sizes, 1, sigma=cfg.emb_sigma)
        dense["lin_bias"] = jnp.zeros((), jnp.float32)
        dense["deep_out"] = _init_mlp(k_out, (cfg.mlp_dims[-1], 1))
    elif cfg.name == "dcn":
        kc = jax.random.split(k_cross, cfg.n_cross)
        dense["cross"] = {
            f"w{i}": (jax.random.normal(kc[i], (cfg.d0,)) / jnp.sqrt(cfg.d0)).astype(jnp.float32)
            for i in range(cfg.n_cross)
        }
        dense["cross"].update(
            {f"b{i}": jnp.zeros((cfg.d0,), jnp.float32) for i in range(cfg.n_cross)}
        )
        dense["combine"] = _init_mlp(k_out, (cfg.d0 + cfg.mlp_dims[-1], 1))
    elif cfg.name == "dcnv2":
        kc = jax.random.split(k_cross, cfg.n_cross)
        dense["cross"] = {
            f"w{i}": (jax.random.normal(kc[i], (cfg.d0, cfg.d0)) / jnp.sqrt(cfg.d0)).astype(jnp.float32)
            for i in range(cfg.n_cross)
        }
        dense["cross"].update(
            {f"b{i}": jnp.zeros((cfg.d0,), jnp.float32) for i in range(cfg.n_cross)}
        )
        dense["combine"] = _init_mlp(k_out, (cfg.d0 + cfg.mlp_dims[-1], 1))

    return {"embed": embed, "dense": dense}


def _fm_second_order(emb: jnp.ndarray) -> jnp.ndarray:
    """Factorization-machine pairwise term 0.5*((sum e)^2 - sum e^2). [B]"""
    s = emb.sum(axis=1)                    # [B, D]
    s2 = jnp.square(emb).sum(axis=1)       # [B, D]
    return 0.5 * (jnp.square(s) - s2).sum(axis=-1)


def _forward_from_emb(
    dense_params: dict,
    cfg: CTRConfig,
    emb: jnp.ndarray,
    lin_emb: jnp.ndarray | None,
    dense_feats: jnp.ndarray,
) -> jnp.ndarray:
    """Model combiner from already-looked-up embeddings -> logits [B] f32.

    ``emb`` is [B, F, D]; ``lin_emb`` is the [B, F, 1] first-order stream for
    wd/deepfm (None otherwise). Shared by the dense (full-table lookup),
    sparse (unique-row gather) and sharded (masked psum assembly) paths so
    all stay one forward definition. Under ``cfg.compute_dtype="bfloat16"``
    every activation and dense weight is cast here and the logits cast back
    to f32, so the loss, its cotangents, and the whole optimizer stay f32.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    if dt != jnp.float32:
        emb = emb.astype(dt)
        lin_emb = None if lin_emb is None else lin_emb.astype(dt)
        dense_feats = dense_feats.astype(dt)
        dense_params = jax.tree.map(lambda w: w.astype(dt), dense_params)
    return _combine(dense_params, cfg, emb, lin_emb,
                    dense_feats).astype(jnp.float32)


def _combine(
    dense_params: dict,
    cfg: CTRConfig,
    emb: jnp.ndarray,
    lin_emb: jnp.ndarray | None,
    dense_feats: jnp.ndarray,
) -> jnp.ndarray:
    flat = emb.reshape(emb.shape[0], -1)
    x0 = jnp.concatenate([flat, dense_feats], axis=-1)        # [B, d0]
    n_mlp = len(cfg.mlp_dims)
    deep = jax.nn.relu(_apply_mlp(dense_params["mlp"], x0, n_mlp))

    if cfg.name == "wd":
        lin = lin_emb[..., 0].sum(axis=1) + dense_params["lin_bias"]
        out = _apply_mlp(dense_params["deep_out"], deep, 1)[:, 0]
        return lin + out
    if cfg.name == "deepfm":
        lin = lin_emb[..., 0].sum(axis=1) + dense_params["lin_bias"]
        fm = _fm_second_order(emb)
        out = _apply_mlp(dense_params["deep_out"], deep, 1)[:, 0]
        return lin + fm + out
    if cfg.name == "dcn":
        x = x0
        cp = dense_params["cross"]
        with jax.named_scope(CROSS_SCOPE):
            for i in range(cfg.n_cross):
                # x_{l+1} = x0 * (x_l . w_l) + b_l + x_l
                x = x0 * (x @ cp[f"w{i}"])[:, None] + cp[f"b{i}"] + x
        combined = jnp.concatenate([x, deep], axis=-1)
        return _apply_mlp(dense_params["combine"], combined, 1)[:, 0]
    if cfg.name == "dcnv2":
        x = x0
        cp = dense_params["cross"]
        with jax.named_scope(CROSS_SCOPE):
            for i in range(cfg.n_cross):
                # x_{l+1} = x0 ⊙ (W_l x_l + b_l) + x_l
                x = x0 * (x @ cp[f"w{i}"] + cp[f"b{i}"]) + x
        combined = jnp.concatenate([x, deep], axis=-1)
        return _apply_mlp(dense_params["combine"], combined, 1)[:, 0]
    raise ValueError(cfg.name)


def apply(
    params: dict,
    cfg: CTRConfig,
    ids: jnp.ndarray,
    dense_feats: jnp.ndarray,
) -> jnp.ndarray:
    """Forward pass -> logits [B] (sigmoid applied in the loss)."""
    dt = jnp.dtype(cfg.compute_dtype)
    emb = embedding.lookup(params["embed"]["fm"], ids, dtype=dt)  # [B, F, D]
    lin_emb = (
        embedding.lookup(params["embed"]["lin"], ids, dtype=dt)
        if "lin" in params["embed"] else None
    )
    return _forward_from_emb(params["dense"], cfg, emb, lin_emb, dense_feats)


def unique_batch(cfg: CTRConfig, ids: jnp.ndarray) -> dict:
    """Per-field unique-id dedup for the sparse path: {"field_i": UniqueField}.

    One dedup serves every embedding group (fm and lin tables of a field see
    the same ids).
    """
    return embedding.batch_unique(ids, cfg.vocab_sizes,
                                  capacity=cfg.unique_capacity)


def gather_embed_rows(params: dict, uniq: dict) -> dict:
    """Gather each embedding group's unique rows, tree-shaped like
    ``params["embed"]`` with [capacity_f, dim] leaves."""
    return {g: embedding.gather_rows(tables, uniq)
            for g, tables in params["embed"].items()}


def apply_rows(
    rows: dict,
    dense_params: dict,
    cfg: CTRConfig,
    uniq: dict,
    dense_feats: jnp.ndarray,
) -> jnp.ndarray:
    """Sparse forward: logits from gathered unique rows (same math as
    ``apply``; the gradient w.r.t. ``rows`` materializes as [n_unique, dim]
    per field instead of a full-table scatter-add)."""
    dt = jnp.dtype(cfg.compute_dtype)
    emb = embedding.lookup_rows(rows["fm"], uniq, dtype=dt)   # [B, F, D]
    lin_emb = (embedding.lookup_rows(rows["lin"], uniq, dtype=dt)
               if "lin" in rows else None)
    return _forward_from_emb(dense_params, cfg, emb, lin_emb, dense_feats)


def batch_counts(cfg: CTRConfig, ids: jnp.ndarray, params: dict) -> dict:
    """CowClip counts tree matching params['embed'] (fm and, if present, lin
    share the same per-field counts)."""
    c = embedding.field_counts(ids, cfg.vocab_sizes)
    tree = {"fm": c}
    if "lin" in params["embed"]:
        tree["lin"] = c
    return tree
