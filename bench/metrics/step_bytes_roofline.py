"""The whole step's share of its HBM-bandwidth roofline: the least bytes a
step must move (``bench/counts``: touched rows' w, m, v and last_step both
ways, the batch input, the dense tower's state both ways) over the peak
bandwidth, over the device's busy time per step. Moves ``rows_per_s``."""

UNIT = "%"


def read(r):
    t = r["trace"]
    if not t or not r["steps"] or t["busy_s"] <= 0:
        return None
    least_s = r["least_bytes_per_step"] / r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] / r["steps"])
