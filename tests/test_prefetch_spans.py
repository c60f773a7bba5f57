"""The prefetch feed's host spans: under ``jax.profiler.trace`` each chunk
gets ``prefetch.stack`` on the worker and ``prefetch.wait`` and
``prefetch.put`` on the consumer, each carrying the chunk's number."""

import glob
import os

import jax
import numpy as np

from repro.data import prefetch as prefetch_lib

SPANS = ("prefetch.stack", "prefetch.wait", "prefetch.put")


def _spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        # one line per host thread (the lines' names need not differ)
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in SPANS:
                    out.append((e.name, dict(e.stats)["chunk"], e.start_ns,
                                e.start_ns + e.duration_ns,
                                (plane.name, thread)))
    return out


def test_prefetch_writes_three_spans_per_chunk_in_order(tmp_path):
    items = [{"x": np.full((4,), i, np.float32)} for i in range(3)]
    with jax.profiler.trace(str(tmp_path)):
        got = [int(c["x"][0]) for c in prefetch_lib.prefetch(iter(items))]
    assert got == [0, 1, 2]
    spans = _spans(str(tmp_path))
    by_name = {n: sorted((s for s in spans if s[0] == n),
                         key=lambda s: s[2]) for n in SPANS}
    # a chunk number per chunk, in the order they ran; the consumer's last
    # wait takes the end-of-feed marker, one past the last chunk
    assert [s[1] for s in by_name["prefetch.stack"]] == [0, 1, 2, 3]
    assert [s[1] for s in by_name["prefetch.wait"]] == [0, 1, 2, 3]
    assert [s[1] for s in by_name["prefetch.put"]] == [0, 1, 2]
    for n in range(3):
        stack, wait, put = (by_name[name][n] for name in SPANS)
        # stacked before it is taken off the queue, put after it is taken
        assert stack[2] <= wait[3] <= put[2]
    # the worker's spans on one thread, the consumer's on another
    assert {s[4] for s in by_name["prefetch.stack"]}.isdisjoint(
        {s[4] for s in by_name["prefetch.put"]})
    assert {s[4] for s in by_name["prefetch.wait"]} == {
        s[4] for s in by_name["prefetch.put"]}
