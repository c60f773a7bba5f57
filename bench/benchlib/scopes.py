"""Put each device op of a trace down to the program phase that ran it.

The program names the phases of its step with ``jax.named_scope``
(``repro.train.loop.STEP_SCOPES``). Each HLO instruction of the compiled
program keeps the scope path it was traced under as ``op_name`` in its
metadata, for example ``jit(run)/while/body/closed_call/row_update_scatter/
jit(sparse_update_scatter)/scatter``. An op belongs to the innermost scope
in that path, and to ``unscoped`` where the path holds none (copies XLA
inserted, the scan's own bookkeeping).

The op's ``op_name`` comes from the compiled program's HLO text
(``op_names``: ``compiled.as_text()``), keyed by the instruction's name,
which is how a device trace names each op (``%fusion.12 = f32[...] ...``).
A fusion without metadata of its own takes its fused computation's root's.
Compile for this with JAX's persistent compilation cache off: the cache's
key leaves the metadata out, so a cached executable's text can carry the
names of another program that compiled to the same code.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Iterable, Mapping, Sequence

from .trace import CONTAINERS, parse_op

UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of every instruction of an HLO
    module's text that has one, or whose fused computation's root has."""
    names, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        name = ins.group(2)
        found = _OP_NAME.search(line)
        if found:
            names[name] = found.group(1)
        else:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
        if ins.group(1) and comp is not None:
            roots[comp] = name
    for name, comp in calls.items():
        root = roots.get(comp)
        if root in names:
            names[name] = names[root]
    return names


def scope_of(op_name: str, scopes: Iterable[str]) -> str:
    """The innermost of ``scopes`` in an ``op_name`` path (a part may wrap
    the name in transforms, ``transpose(jvp(name))``), else ``unscoped``."""
    scopes = set(scopes)
    for part in reversed(op_name.split("/")):
        core = part.rsplit("(", 1)[-1].rstrip(")")
        if core in scopes:
            return core
    return UNSCOPED


def instruction(event_name: str) -> str:
    """The instruction name of a trace op named by its HLO text,
    ``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scope_seconds(ops: Sequence, names: Mapping[str, str],
                  scopes: Sequence[str], window: tuple) -> dict:
    """Seconds of op time per scope over ``window`` ``(start_ns, end_ns)``,
    ``unscoped`` included, and ``matched_s`` / ``total_s``: op time whose
    instruction has an ``op_name``, and all op time. ``ops`` are
    ``benchlib.trace.Event``; containers (``while``, ...) are left out, as
    the trace reduction leaves them out."""
    w0, w1 = window
    out = defaultdict(float)
    matched = total = 0.0
    for e in ops:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if t <= s or parse_op(e.name)[0] in CONTAINERS:
            continue
        sec = (t - s) / 1e9
        op_name = names.get(instruction(e.name))
        total += sec
        matched += sec if op_name is not None else 0.0
        out[scope_of(op_name or "", scopes)] += sec
    return {"scope_s": {n: out[n] for n in (*scopes, UNSCOPED)},
            "matched_s": matched, "total_s": total}
