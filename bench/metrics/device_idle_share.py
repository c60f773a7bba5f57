"""Share of the traced window in which no op ran on the device: 1 minus the
union of op intervals over the window. Moves ``rows_per_s``."""

UNIT = "%"


def read(r):
    t = r["trace"]
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
