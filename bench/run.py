"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/yardstick/spec.py``). The run
builds the seeded program (for a training cell, the trainer), warms every shape the cell uses
(``setup_s``), measures for ``--seconds``, reads its metrics (with
``--trace 1`` the per-layer ones, from a profiler trace of the window),
frees the program's state and compares what the window produced with the
plain reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``; its last key, ``checks``, holds each number
compared beside its limit, which are also the last lines on standard
error.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. The compile cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from yardstick import spec  # noqa: E402


class Harness:
    """What a driver gets: the cell's configuration, mix, model module and
    seed, and the harness's hooks for set-up, the window and the readers."""

    def __init__(self, bench, cell, args, devices, *, log=None,
                 cfg=None, mix=None, peaks=None, limits=None):
        self.bench, self.cell, self.args = bench, cell, args
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        # the reference's precision: what the configuration states for the
        # chip (tests on the CPU, whose products are plain float32, pass
        # "f32")
        self.ref_mode = "stated"
        self.cfg = cfg if cfg is not None else spec.config(cell["config"])
        self.traffic = mix if mix is not None else spec.traffic(
            cell["traffic"])
        self.model = spec.load_module("models", self.cfg["model"])
        self.driver = spec.load_module("drivers", self.traffic["driver"])
        self.devices = devices
        self.limits = limits
        if peaks is None:
            from yardstick.peaks import peaks as table
            peaks = table(devices[0].device_kind)
        self.peaks = peaks
        self.log = log or (lambda msg: print(msg, file=sys.stderr,
                                             flush=True))
        self.t_start = T_START
        self.metrics: dict = {}
        self.trace_summary = None
        self.window_compiles = None
        from yardstick.compile_monitor import CompileMonitor
        self.monitor = CompileMonitor()

    # -- hooks the drivers call ---------------------------------------------
    def settle(self):
        from yardstick import device
        device.settle()

    def memory(self, key: str):
        from yardstick import device
        return device.memory(self.devices, key)

    def setup_done(self) -> float:
        s = time.perf_counter() - self.t_start
        self.log(f"[setup] {s:.3f} s, compile {self.monitor.snapshot()}")
        return s

    @contextlib.contextmanager
    def window(self):
        """The measured window: annotated, traced with ``--trace 1``, and
        watched for compilations."""
        import jax

        from yardstick import annotate, trace
        before = self.monitor.snapshot()
        tdir = None
        if self.trace:
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(
                tdir, profiler_options=trace.profile_options())
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                yield
        finally:
            if tdir is not None:
                jax.profiler.stop_trace()
        after = self.monitor.snapshot()
        self.window_compiles = {k: after[k] - before[k] for k in after}
        self.log(f"[window] compile events inside the window: "
                 f"{self.window_compiles}")
        if tdir is not None:
            try:
                self.trace_summary = trace.reduce_dir(
                    tdir, annotations=annotate.ANNOTATIONS)
            except ValueError as e:
                self.log(f"[trace] nothing to read: {e}")
            finally:
                shutil.rmtree(tdir, ignore_errors=True)

    def read_layers(self, values: dict):
        """Read this run's metrics while the program's state still lives."""
        ctx = dict(values, cfg=self.cfg, traffic=self.traffic,
                   peaks=self.peaks, trace=self.trace_summary)
        group = "per_layer" if self.trace else "end_to_end"
        for m in spec.cell_metrics(self.bench, self.cell["name"], group):
            v = spec.load_module("metrics", m["name"]).read(ctx)
            if v is not None and math.isfinite(v):
                self.metrics[m["name"]] = {"value": float(v),
                                           "unit": m["unit"]}


def run_cell(h: Harness) -> dict:
    """Run the cell; the result object (without printing it)."""
    from yardstick import compare, device
    out = h.driver.run(h)
    lim = h.limits if h.limits is not None else compare.limits(
        h.cell["name"])
    correct, checks = compare.verdict(out["checks"], lim)
    dev = dict(device.describe(h.devices),
               memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": h.metrics, "device": dev}
    if h.trace and h.trace_summary is not None:
        dev["busy_s"] = h.trace_summary["busy_s"]
        dev["window_s"] = h.trace_summary["window_s"]
        result["breakdown"] = {k: h.trace_summary[k]
                               for k in ("device_ops", "idle_gaps")}
    result["checks"] = checks
    return result


def print_checks(checks: dict, correct: bool):
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct {str(correct).lower()}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    from yardstick import device
    device.use_compile_cache()
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    h = Harness(bench, cell, args, devices)
    h.log(f"[device] {device.describe(devices)}; workload {cell['name']} "
          f"seed {args.seed} seconds {args.seconds} trace {args.trace}")
    result = run_cell(h)
    print_checks(result["checks"], result["correct"])
    print(json.dumps(result, default=_jsonable))
    return 0


def _jsonable(x):
    if hasattr(x, "item"):
        return x.item()
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    raise TypeError(f"not JSON serializable: {type(x)}")


if __name__ == "__main__":
    sys.exit(main())
