"""Reduction of a profiler trace to device busy time, top device operations
and idle gaps named by what the host was doing.

The harness wraps its measured window in a host annotation ``window`` and
its calls into the program in annotations of their own
(``yardstick.annotate``). The profiler writes them on the host plane, on
the same clock as the device planes' operations.

- busy: the union of the intervals in which an operation ran on a device,
  clipped to the window, averaged over the devices traced;
- top operations: device seconds per operation, named
  ``<module>:<operation>`` by the program (XLA module) that ran it;
- programs: for each XLA module, its runs whose midpoint lies in the
  window and their device seconds, whole. The device's clock and the
  host's can differ by a millisecond or so, so a run is not cut at the
  window's edge: the seconds and the runs stay whole together.
- idle gaps: the window less the busy union, each gap given to the host
  annotation that overlaps it most (``untraced`` where none does), summed by
  annotation.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW = "window"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def profile_options():
    """No Python tracer (it would trace every line of the harness's loop);
    host annotations and runtime events only."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(
            e.duration_ns)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _op_name(event_name: str) -> str:
    """``fusion.49`` of an XLA op event named by its whole HLO line
    (``%fusion.49 = s32[...] fusion(...), ...``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _module_namer(modules):
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def name(op, start):
        op = _op_name(op)
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and modules[i][1] <= start < modules[i][2]:
            return f"{modules[i][0].split('(', 1)[0]}:{op}"
        return op
    return name


def reduce_profile(pd, *, annotations=(), top: int = 10) -> dict:
    """``pd`` is a ``jax.profiler.ProfileData``. Returns ``busy_s``,
    ``window_s``, ``device_ops`` and ``idle_gaps`` (each a list of
    ``[name, seconds]``, longest first, at most ``top``), ``modules``
    (``{module: [seconds, runs]}``) and ``n_devices``, every time averaged
    over the devices; raises ``ValueError`` when the trace holds no window or
    no device operation."""
    host = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW or name in annotations:
                    host.append((name, s, e))
    windows = [h for h in host if h[0] == WINDOW]
    if not windows:
        raise ValueError("the trace holds no 'window' annotation")
    _, ws, we = max(windows, key=lambda h: h[2] - h[1])
    spans = sorted((h for h in host
                    if h[0] != WINDOW and h[2] > ws and h[1] < we),
                   key=lambda h: h[1])

    busy, ops, gaps = [], defaultdict(float), defaultdict(float)
    mods = defaultdict(lambda: [0.0, 0])
    n_dev = 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        op_lines = [lines[n] for n in OPS_LINES if n in lines]
        if not op_lines:
            continue
        modules = [ev for n in MODULE_LINES if n in lines
                   for ev in _events(lines[n])]
        name_of = _module_namer(modules)
        for mod, s, e in modules:
            if ws <= (s + e) / 2 < we:
                mod = mod.split("(", 1)[0]
                mods[mod][0] += (e - s) * 1e-9
                mods[mod][1] += 1
        intervals = []
        for line in op_lines:
            for op, s, e in _events(line):
                s, e = max(s, ws), min(e, we)
                if e > s:
                    intervals.append((s, e))
                    ops[name_of(op, s)] += (e - s) * 1e-9
        if not intervals:
            continue
        n_dev += 1
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        # idle gaps of this device, named by the host span over them: one
        # sweep over gaps and spans, both in start order
        edges = [ws] + [x for iv in merged for x in iv] + [we]
        j, active = 0, []
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            while j < len(spans) and spans[j][1] < ge:
                active.append(spans[j])
                j += 1
            active = [sp for sp in active if sp[2] > gs]
            best, label = 0.0, "untraced"
            for name, s, e in active:
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, name
            gaps[label] += (ge - gs) * 1e-9
    if not n_dev:
        raise ValueError("the trace holds no device operation in the window")

    def ranked(acc):
        return [[k, v / n_dev] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / n_dev, "window_s": (we - ws) * 1e-9,
            "n_devices": n_dev, "device_ops": ranked(ops),
            "idle_gaps": ranked(gaps),
            "modules": {k: [v[0] / n_dev, v[1] / n_dev]
                        for k, v in mods.items()}}


def main_program(summary: dict):
    """``(name, seconds, runs)`` of the program (XLA module) that took the
    most device time in the window, or None where the trace holds none."""
    mods = (summary or {}).get("modules") or {}
    if not mods:
        return None
    name, (secs, runs) = max(mods.items(), key=lambda kv: kv[1][0])
    return name, secs, runs


def reduce_dir(log_dir: str, **kw) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(log_dir)), **kw)
