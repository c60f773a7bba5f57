"""Host milliseconds per step the window waited for the next chunk from the
prefetch feed (host clock around ``next``). Moves ``rows_per_s``."""

UNIT = "ms"


def read(r):
    steps = r["window_steps"]
    return 1e3 * r["input_wait_s"] / steps if steps else None
