"""Read a training cell's step by part of the step, from one traced run.

    python3 bench/scope_profile.py --workload <cell> --seed <n> --seconds <s> [--out DIR]

Runs the cell once as ``run.py --trace 1`` does, through the same harness,
driver and window, and prints ``run.py``'s result line with one key more,
``scoped``:

- ``step_ms``: device milliseconds per run of the step's program for each
  part of the step (``yardstick.scopes``: gather, table_grad, quantize,
  tower, clip, update, unscoped), mapped through the step's compiled HLO
  text (``Trainer.compiled_step``), which is taken after set-up and before
  the window;
- ``mapped_share``: the share of the runs' op device time whose
  instruction the compiled text holds, and ``ops_share``: that op time over
  the runs' device time;
- ``clock_lead_ms``: the least and the median lead of each run's device
  start over the start of its ``trainer.dispatch`` span, paired by order
  (a negative least lead means the device's and the host's clocks differ
  by at least that much);
- ``idle_by_trainer_span``: the window's idle gaps given to the trainer's
  spans ``trainer.data``, ``trainer.stage`` and ``trainer.dispatch``
  (``untraced`` outside them);
- ``top_ops``: the ten longest device ops, each with its part.

With ``--out`` the trace and the compiled text are kept there. Without a
TPU it exits 3, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import statistics
import sys
import tempfile

import run

from yardstick import spec

TRAINER_SPANS = ("trainer.data", "trainer.stage", "trainer.dispatch")


class ScopedHarness(run.Harness):
    """The harness with a traced window that also reads the step by part.
    It keeps the trainer the model's ``build_trainer`` makes, so that the
    window can take the step's compiled text."""

    def __init__(self, *a, out=None, **kw):
        super().__init__(*a, **kw)
        self.trace, self.out = True, out
        self.trainer = self.hlo = self.scoped = None
        build = self.model.build_trainer

        def keep(*args, **kwargs):
            self.trainer = build(*args, **kwargs)
            return self.trainer
        self.model.build_trainer = keep

    @contextlib.contextmanager
    def window(self):
        import jax
        from jax.profiler import ProfileData

        from yardstick import annotate, trace
        batch = self.driver.ring(self.cfg, self.traffic, self.seed)[0]
        self.hlo = self.trainer.compiled_step(batch).as_text()
        before = self.monitor.snapshot()
        tdir = tempfile.mkdtemp(prefix="bench_scoped_")
        jax.profiler.start_trace(tdir, profiler_options=trace.profile_options())
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        after = self.monitor.snapshot()
        self.window_compiles = {k: after[k] - before[k] for k in after}
        self.log(f"[window] compile events inside the window: "
                 f"{self.window_compiles}")
        try:
            path = trace.find_xplane(tdir)
            if self.out:
                os.makedirs(self.out, exist_ok=True)
                shutil.copy(path, os.path.join(self.out, "step.xplane.pb"))
                with open(os.path.join(self.out, "step.hlo.txt"), "w") as f:
                    f.write(self.hlo)
            pd = ProfileData.from_file(path)
            self.trace_summary = trace.reduce_profile(
                pd, annotations=annotate.ANNOTATIONS)
            self.scoped = read_scoped(pd, self.hlo, self.trace_summary)
            self.log(f"[scoped] {json.dumps(self.scoped)}")
        except ValueError as e:
            self.log(f"[trace] nothing to read: {e}")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)


def read_scoped(pd, hlo: str, summary: dict) -> dict:
    from yardstick import scopes, trace
    module = trace.main_program(summary)[0]
    got = scopes.reduce_scopes(pd, hlo, module)
    runs = got["runs"]
    leads = scopes.clock_leads(pd, "trainer.dispatch", module)
    part_of = scopes.op_parts(hlo)
    top = [[name, secs / runs * 1e3,
            part_of.get(name.split(":", 1)[-1], "unscoped")]
           for name, secs in summary["device_ops"]]
    gaps = trace.reduce_profile(pd, annotations=TRAINER_SPANS)["idle_gaps"]
    return {
        "module": module, "runs": runs,
        "module_ms": got["module_s"] / runs * 1e3,
        "step_ms": {k: v / runs * 1e3 for k, v in got["parts"].items()},
        "mapped_share": got["mapped_s"] / got["ops_s"],
        "ops_share": got["ops_s"] / got["module_s"],
        "clock_lead_ms": ([min(leads) * 1e3, statistics.median(leads) * 1e3,
                           len(leads)] if leads else None),
        "idle_by_trainer_span": gaps,
        "top_ops": top,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    args.trace = 1

    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    from yardstick import device
    device.use_compile_cache()
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    h = ScopedHarness(bench, cell, args, devices, out=args.out)
    result = run.run_cell(h)
    run.print_checks(result["checks"], result["correct"])
    result["scoped"] = h.scoped
    print(json.dumps(result, default=run._jsonable))
    return 0


if __name__ == "__main__":
    sys.exit(main())
