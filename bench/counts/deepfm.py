"""Model FLOPs and least bytes per step of DeepFM (Guo et al., 2017).

FLOPs count what the forward and backward passes require per row: each
matrix product of the tower (2 per multiply-add) forward, and twice that
backward (the input's and the weight's gradients, the input's because the
embeddings train), plus the FM pairwise term. Least bytes per step: every
touched row's ``w``, ``m`` and ``v`` read and written and its ``last_step``
read and written, in each embedding group; the batch's input read once; the
dense tower's ``w``, ``m`` and ``v`` read and written.
"""

F32 = 4


def tower_macs(cfg) -> int:
    """Multiply-adds of the tower's matrix products for one row."""
    widths = [len(cfg["vocab_sizes"]) * cfg["emb_dim"] + cfg["n_dense"],
              *cfg["mlp_dims"], 1]
    return sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def dense_params(cfg) -> int:
    widths = [len(cfg["vocab_sizes"]) * cfg["emb_dim"] + cfg["n_dense"],
              *cfg["mlp_dims"], 1]
    return sum(a * b + b for a, b in zip(widths[:-1], widths[1:])) + 1


def flops_per_row(cfg) -> float:
    fm = 4 * len(cfg["vocab_sizes"]) * cfg["emb_dim"]
    return 3.0 * (2 * tower_macs(cfg) + fm)


def row_bytes(dims) -> int:
    """Bytes one touched id moves: w, m, v of each group, and last_step,
    each read and written."""
    return sum(2 * (3 * F32 * d + F32) for d in dims)


def input_bytes(cfg, batch: int) -> int:
    return batch * F32 * (len(cfg["vocab_sizes"]) + cfg["n_dense"] + 1)


def least_bytes_per_step(cfg, uniques, batch: int) -> float:
    """``uniques``: the number of distinct ids of each field in the batch."""
    return (sum(uniques) * row_bytes((cfg["emb_dim"], 1))
            + input_bytes(cfg, batch) + 2 * 3 * F32 * dense_params(cfg))
