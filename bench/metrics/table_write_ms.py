"""Device milliseconds per step in ops whose output is a whole table of a
field with more ids than the batch (the trace's ``table`` class): work that
grows with the vocab, not the batch. Moves ``rows_per_s``."""

UNIT = "ms"


def read(r):
    t = r["trace"]
    if not t or not r["steps"] or t["class_s"]["table"] <= 0:
        return None
    return 1e3 * t["class_s"]["table"] / r["steps"]
