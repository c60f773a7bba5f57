"""Reduce a profiler trace of the timed window to the benchmark's numbers.

The op classification is copied from the program's ``scripts/trace_ctr.py``
(PR 11's reduction; two captures there agreed to 0.2 points): each device
op of the ``XLA Ops`` line is classed by the leading dimension of what it
writes:

* ``table``: the vocab of a field with more ids than the batch, an op that
  writes a whole ``[V, ...]`` table where the step touches at most
  ``batch`` of its rows;
* ``small_table``: the vocab of any other field;
* ``batch``: the batch, which is also the unique-id capacity of every field
  with more ids than the batch (row gathers and per-example work);
* ``sort``, and ``other``.

``while``/``conditional``/``call`` wrappers are left out and their bodies
counted. Busy time is the union of op intervals (not the sum of module
durations), clipped to the window; gaps in it are labelled by the
benchmark's own host span (``bench.*``) that covers the gap's middle.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Iterable, NamedTuple, Sequence

CONTAINERS = ("while", "conditional", "call")
CLASSES = ("table", "small_table", "batch", "sort", "other")
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


def parse_op(text: str):
    """``(opcode, leading dim of the (first) output)`` of one HLO line such
    as ``%fusion.3 = f32[7046547,10]{0,1:T(8,128)} fusion(...)``."""
    _, _, rest = text.partition(" = ")
    if rest.startswith("("):          # tuple output: skip to its ')'
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        out, tail = rest[1:i], rest[i + 1:]
    else:
        out, _, tail = rest.partition(" ")
    opcode = tail.strip().split("(", 1)[0]
    dims = out.split("[", 1)[1].split("]", 1)[0] if "[" in out else ""
    lead = dims.split(",", 1)[0]
    return opcode, int(lead) if lead.isdigit() else None


def classify(text: str, batch: int, vocabs: Iterable[int]) -> str | None:
    """The op's class, or None for a container op."""
    opcode, lead = parse_op(text)
    if opcode in CONTAINERS:
        return None
    vocabs = set(vocabs) - {batch}
    if opcode == "sort":
        return "sort"
    if lead in vocabs:
        return "table" if lead > batch else "small_table"
    return "batch" if lead == batch else "other"


def union(intervals: Sequence[tuple]) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def reduce(ops: Sequence[Event], spans: Sequence[Event], *, batch: int,
           vocabs: Iterable[int], window: tuple, top: int = 10) -> dict:
    """Numbers of one traced window ``(start_ns, end_ns)``.

    Returns ``busy_s``, ``window_s``, ``class_s`` (seconds per class),
    ``device_ops`` (top ``[name, seconds]``) and ``idle_gaps`` (longest
    ``[host span, seconds]``)."""
    w0, w1 = window
    vocabs = tuple(vocabs)
    class_s = defaultdict(float)
    per_op = defaultdict(float)
    live = []
    for e in ops:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if t <= s:
            continue
        cls = classify(e.name, batch, vocabs)
        if cls is None:
            continue
        sec = (t - s) / 1e9
        class_s[cls] += sec
        head = e.name.split(" = ", 1)
        short = head[0].strip()
        per_op[f"{short} [{cls}] {head[1][:60] if len(head) > 1 else ''}"] += sec
        live.append((s, t))
    busy = union(live)
    busy_s = sum(t - s for s, t in busy) / 1e9
    gaps = []
    prev = w0
    for s, t in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    labelled = defaultdict(float)
    host = [e for e in spans if e.name.startswith(SPAN_PREFIX)]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    for s, t in longest:
        mid = 0.5 * (s + t)
        covering = [e for e in host if e.start_ns <= mid <= e.start_ns + e.dur_ns]
        # the innermost span: the latest to start
        name = max(covering, key=lambda e: e.start_ns).name if covering \
            else "no bench span"
        labelled[name] += (t - s) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": (w1 - w0) / 1e9,
        "class_s": {c: class_s[c] for c in CLASSES},
        "device_ops": [[n, s] for n, s in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(labelled.items(), key=lambda kv: -kv[1])],
    }


def load(trace_dir: str, device: str = "/device:TPU:0"):
    """``(ops, spans)`` of the newest xplane under ``trace_dir``: the
    device's ``XLA Ops`` events and every host event named ``bench.*``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(device):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    if not ops:
        raise RuntimeError(f"{files[-1]} has no XLA Ops on {device}")
    return ops, spans
