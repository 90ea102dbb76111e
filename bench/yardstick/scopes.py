"""Device time of a step program by part of the step, from a profiler trace
and the program's compiled HLO text.

The program names the parts of its training step with ``jax.named_scope``
(``embed_gather``, ``embed_quantize``, ``tower``, ``clip``, ``update``).
A scope lands in each HLO instruction's ``op_name`` metadata, under the
wrappers of the transformations that made the op: ``jvp(tower)`` for the
forward pass, ``transpose(jvp(tower))`` for its backward. A fusion carries
its root's ``op_name``. The trace names each device op by its instruction
(``yardstick.trace``), so the compiled text maps each op to its part:

    gather      embed_gather, forward: row, group and probability gathers
    table_grad  embed_gather under transpose(...): the table gradient's
                zero-fill and scatter-add
    quantize    embed_quantize, both ways: the expectation over the
                candidate widths (Eq. 9) and the lambda-regularizer
    tower       tower, both ways: MLP with batch norm, logit, loss
    clip        clip: global norm and scaling
    update      update: Adam moments, parameter update, NaN guard
    unscoped    an op under none of these, or not in the compiled text

Times are summed over the same runs of the program that
``trace.reduce_profile`` counts under ``modules``: whole runs whose
midpoint lies in the window.
"""
from __future__ import annotations

import bisect
import re
import statistics

from yardstick.trace import (MODULE_LINES, OPS_LINES, WINDOW, _events,
                             _op_name)

SCOPES = {"embed_gather": "gather", "embed_quantize": "quantize",
          "tower": "tower", "clip": "clip", "update": "update"}
PARTS = ("gather", "table_grad", "quantize", "tower", "clip", "update",
         "unscoped")
_WRAPPER = re.compile(r"^(jvp|transpose)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _components(path: str):
    """``a/f(b/c)/d`` -> ``a``, ``f(b/c)``, ``d``: split at the slashes
    outside parentheses."""
    depth, start = 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            yield path[start:i]
            start = i + 1
    yield path[start:]


def part_of(op_name: str) -> str:
    """The part of the step an op with this ``op_name`` belongs to: its
    innermost listed scope, its ``jvp(...)`` and ``transpose(...)``
    wrappers removed; ``embed_gather`` under a transpose is the table
    gradient. An ``op_name`` that joins several with ``;`` counts as its
    first."""
    return _part(op_name.split(";", 1)[0], False, "unscoped")


def _part(path: str, backward: bool, part: str) -> str:
    for comp in _components(path):
        back = backward
        while m := _WRAPPER.match(comp):
            back |= m.group(1) == "transpose"
            comp = m.group(2)
        if "/" in comp:
            part = _part(comp, back, part)
        elif comp in SCOPES:
            part = ("table_grad" if comp == "embed_gather" and back
                    else SCOPES[comp])
    return part


def op_parts(hlo_text: str) -> dict:
    """``{instruction: part}`` for every instruction of an HLO module's
    text; an instruction without ``op_name`` metadata is ``unscoped``.
    Instruction names are unique in a module, so the fused computations'
    instructions, which never run as ops of their own, do no harm."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = part_of(name.group(1)) if name else "unscoped"
    return out


def reduce_scopes(pd, hlo_text: str, module: str | None = None) -> dict:
    """Device seconds by part of the step in the runs of one program.

    ``pd`` is a ``jax.profiler.ProfileData`` with a host ``window``
    annotation; ``module`` names the program (the XLA module's name before
    its ``(...)``), by default the one with the most device time in the
    window. Returns ``module``, ``runs``, ``module_s`` (the runs' device
    time), ``ops_s`` (the time of their ops), ``mapped_s`` (of those, the
    ops found in ``hlo_text``) and ``parts`` (``{part: seconds}`` over
    ``PARTS``), every time averaged over the devices."""
    parts_of = op_parts(hlo_text)
    ws, we = _window(pd)
    runs_by_dev = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = [ev for n in OPS_LINES if n in lines
               for ev in _events(lines[n])]
        runs = [(m.split("(", 1)[0], s, e) for n in MODULE_LINES
                if n in lines for m, s, e in _events(lines[n])
                if ws <= (s + e) / 2 < we]
        if ops and runs:
            runs_by_dev.append((runs, ops))
    if not runs_by_dev:
        raise ValueError("the trace holds no program run in the window")
    if module is None:
        time_of = {}
        for runs, _ in runs_by_dev:
            for m, s, e in runs:
                time_of[m] = time_of.get(m, 0.0) + e - s
        module = max(time_of, key=time_of.get)

    parts = dict.fromkeys(PARTS, 0.0)
    n_runs = module_s = ops_s = mapped_s = 0.0
    for runs, ops in runs_by_dev:
        runs = sorted((s, e) for m, s, e in runs if m == module)
        starts = [s for s, _ in runs]
        n_runs += len(runs)
        module_s += sum(e - s for s, e in runs) * 1e-9
        for op, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue
            name, secs = _op_name(op), (e - s) * 1e-9
            ops_s += secs
            if name in parts_of:
                mapped_s += secs
            parts[parts_of.get(name, "unscoped")] += secs
    n = len(runs_by_dev)
    return {"module": module, "runs": n_runs / n, "module_s": module_s / n,
            "ops_s": ops_s / n, "mapped_s": mapped_s / n,
            "parts": {k: v / n for k, v in parts.items()}}


def clock_leads(pd, span: str, module: str) -> list[float]:
    """Seconds from the start of each host ``span`` to the start of the
    run of ``module`` it dispatched, paired by order over the whole trace
    (first device). The device's and the host's clocks agree to within the
    least of these where it is positive; a negative lead means they differ
    by at least that much."""
    spans = sorted(s for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for name, s, _ in _events(line)
                   if name == span)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        runs = sorted(s for n in MODULE_LINES if n in lines
                      for m, s, _ in _events(lines[n])
                      if m.split("(", 1)[0] == module)
        if runs:
            return [(r - h) * 1e-9 for h, r in zip(spans, runs)]
    return []


def lead_summary(leads: list[float]) -> str:
    if not leads:
        return "no dispatch paired with a run"
    return (f"{len(leads)} runs, min {min(leads) * 1e3:.3f} ms, median "
            f"{statistics.median(leads) * 1e3:.3f} ms")


def _window(pd):
    windows = [(s, e) for plane in pd.planes if plane.name.startswith("/host:")
               for line in plane.lines for name, s, e in _events(line)
               if name == WINDOW]
    if not windows:
        raise ValueError("the trace holds no 'window' annotation")
    return max(windows, key=lambda w: w[1] - w[0])
