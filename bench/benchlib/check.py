"""The numbers that decide ``correct``: the program's first training steps
against the plain reference's.

* ``loss_gap``: the largest ``|loss - reference loss| / |reference loss|``
  over the steps of the first chunk;
* ``m_gap``: Adam's first moment after the first chunk, the gradients as the
  optimizer got them; per leaf the gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and of
  the median leaf, worst leaf;
* ``change_gap``: the same for the norm of each parameter's change over the
  first chunk, after the program's ``flush``.

Leaves whose reference moment is under a thousandth of the median leaf's
are left out of both leaf numbers: they move by round-off alone.
"""

from __future__ import annotations

import math
from typing import Mapping

NAMES = ("loss_gap", "m_gap", "change_gap")
NEGLIGIBLE = 1e-3


def leaf_gap(prog: Mapping[str, float], ref: Mapping[str, float],
             keep) -> tuple:
    """``(worst relative gap, its leaf)`` over the leaves in ``keep``."""
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))
        return math.inf, f"leaves differ: {missing[:4]}"
    scale_floor = _median([ref[k] for k in keep])
    worst, at = 0.0, ""
    for k in sorted(keep):
        p = prog[k]
        gap = (abs(p - ref[k]) / max(ref[k], scale_floor)
               if math.isfinite(p) else math.inf)
        if not gap <= worst:
            worst, at = gap, k
    return worst, at


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return 0.5 * (xs[(n - 1) // 2] + xs[n // 2]) if n else 0.0


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers from the two sides' ``losses``, ``m`` and
    ``change`` (per-leaf norms), each ``{"value", "at"}``."""
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        loss = math.inf
    else:
        loss = max((abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
                   for a, b in zip(lp, lr))
    med = _median(list(ref["m"].values()))
    keep = [k for k, v in ref["m"].items() if v >= NEGLIGIBLE * med]
    m, m_at = leaf_gap(prog["m"], ref["m"], keep)
    c, c_at = leaf_gap(prog["change"], ref["change"], keep)
    diag = {"loss_gap_step1": (abs(lp[0] - lr[0]) / abs(lr[0])
                               if lp and lr else math.inf),
            "smallest_leaf_share": min(ref["m"][k] for k in keep) / med}
    for part in ("embed", "dense"):
        sub = [k for k in keep if k.startswith(part + "/")]
        if sub:
            diag[f"m_gap_{part}"] = leaf_gap(prog["m"], ref["m"], sub)
            diag[f"change_gap_{part}"] = leaf_gap(prog["change"],
                                                  ref["change"], sub)
    return {"loss_gap": {"value": loss, "at": "first chunk"},
            "m_gap": {"value": m, "at": m_at},
            "change_gap": {"value": c, "at": c_at},
            "_left_out": sorted(set(ref["m"]) - set(keep)), "_diag": diag}


def verdict(read: dict, limits: Mapping[str, float]) -> tuple:
    """``{name: {"value", "limit"}}`` of the numbers the cell's limits name,
    and whether every value is within. A cell leaves out a number that
    nothing wrong reads above its sound runs."""
    if not limits or set(limits) - set(NAMES):
        raise ValueError(f"limits {sorted(limits)} are not some of {NAMES}")
    out = {n: {"value": read[n]["value"], "limit": limits[n]}
           for n in NAMES if n in limits}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return out, ok
