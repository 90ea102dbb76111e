"""Numbers compared with the plain reference, and their limits.

Every check is a named number and the limit it must not pass. A run is
``correct`` when every number is finite and within its limit. The limits
of a cell are data, in ``bench/limits/<cell>.json``; ``PERF.md`` gives the
readings each was set from.

Training: ``loss_gap`` is the widest relative gap of a step's loss;
``grad_gap`` and ``change_gap`` are, by the worst leaf, the gap between
the program's norm and the reference's, of the first step's gradient as
the optimizer got it and of the parameters' change over the steps, each
measured against the larger of that leaf's reference norm and the median
leaf's. Leaves whose reference gradient is under a thousandth of the
median leaf's move under Adam by round-off alone and are left out.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

from yardstick.spec import BENCH_DIR

NEGLIGIBLE = 1e-3


def limits(cell: str) -> dict:
    with open(os.path.join(BENCH_DIR, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def loss_gap(prog, ref) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.all(np.isfinite(prog)):
        return math.inf
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def counted_leaves(ref_grad_norms: dict) -> list[str]:
    """Leaves whose reference gradient is not nought to rounding."""
    med = float(np.median(list(ref_grad_norms.values())))
    return sorted(k for k, v in ref_grad_norms.items()
                  if v >= NEGLIGIBLE * med)


def norm_gap(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    """Worst leaf's |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖), and which
    leaf."""
    med = float(np.median([ref[k] for k in leaves]))
    worst, which = 0.0, ""
    for k in leaves:
        p = prog.get(k, math.nan)
        if not math.isfinite(p):
            return math.inf, k
        g = abs(p - ref[k]) / max(ref[k], med, 1e-30)
        if g >= worst:
            worst, which = g, k
    return worst, which


def verdict(checks: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every limit of the cell;
    a number that is missing or not finite fails."""
    out, ok = {}, True
    for name, limit in lim.items():
        v = checks.get(name, math.nan)
        v = float(v) if v is not None else math.nan
        good = math.isfinite(v) and v <= limit
        ok &= good
        out[name] = {"value": v if math.isfinite(v) else str(v),
                     "limit": limit}
    return bool(ok), out
