"""Device milliseconds per step in ops whose output leads with the batch,
which is also the unique-id capacity of the large fields: row gathers and
per-example work. Moves ``rows_per_s``."""

UNIT = "ms"


def read(r):
    t = r["trace"]
    if not t or not r["steps"] or t["class_s"]["batch"] <= 0:
        return None
    return 1e3 * t["class_s"]["batch"] / r["steps"]
