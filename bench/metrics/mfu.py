"""The whole step's share of the chip's peak bf16 FLOP/s: model FLOPs per
row (forward and backward, ``bench/counts``) times the rows trained in the
traced window over its seconds, over chips times the peak. Moves
``rows_per_s``."""

UNIT = "%"


def read(r):
    t = r["trace"]
    if not t or not r["rows"] or t["window_s"] <= 0:
        return None
    rate = r["flops_per_row"] * r["rows"] / t["window_s"]
    return 100.0 * rate / (r["chips"] * r["peaks"]["bf16_flops_per_s"])
