"""Model FLOPs and least bytes per step of DCN-V2 (Wang et al., 2021),
stacked: ``n_cross`` full-rank cross layers beside the MLP tower and one
linear combiner over both.

Counted as for DeepFM (``deepfm.py``): tower matrix products 2 FLOPs per
multiply-add forward and twice that backward, plus the cross layers'
elementwise product; touched rows of the one embedding group move ``w``,
``m``, ``v`` and ``last_step`` both ways; the batch input once; the dense
tower's ``w``, ``m`` and ``v`` both ways.
"""

F32 = 4


def _d0(cfg) -> int:
    return len(cfg["vocab_sizes"]) * cfg["emb_dim"] + cfg["n_dense"]


def tower_macs(cfg) -> int:
    d0 = _d0(cfg)
    widths = [d0, *cfg["mlp_dims"]]
    mlp = sum(a * b for a, b in zip(widths[:-1], widths[1:]))
    return mlp + cfg["n_cross"] * d0 * d0 + (d0 + cfg["mlp_dims"][-1])


def dense_params(cfg) -> int:
    d0 = _d0(cfg)
    widths = [d0, *cfg["mlp_dims"]]
    mlp = sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
    return mlp + cfg["n_cross"] * (d0 * d0 + d0) + (d0 + cfg["mlp_dims"][-1] + 1)


def flops_per_row(cfg) -> float:
    return 3.0 * (2 * tower_macs(cfg) + 2 * cfg["n_cross"] * _d0(cfg))


def least_bytes_per_step(cfg, uniques, batch: int) -> float:
    """``uniques``: the number of distinct ids of each field in the batch."""
    row = 2 * (3 * F32 * cfg["emb_dim"] + F32)
    inputs = batch * F32 * (len(cfg["vocab_sizes"]) + cfg["n_dense"] + 1)
    return sum(uniques) * row + inputs + 2 * 3 * F32 * dense_params(cfg)
