"""Readings that set a training cell's limits.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        --control-seeds 3

For each seed, one run of the cell as ``run.py`` makes it (set-up, a short
window, the comparison), and, on the first ``--control-seeds`` seeds,
beside the program's readings those of the control and of the planted
faults on the same inputs:

- the control is the reference in bfloat16 put in the program's place;
- the fault ``half`` is the reference that takes the mean over half of
  each batch;
- a step that leaves the state unchanged reads 1 on ``change_gap`` by
  construction;
- ``f32_vs_stated`` is the reference at full float32 against the
  reference in the stated precision, for the record.

Each reading is one JSON line on standard output, with the verdict that
the cell's limits give it. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
from yardstick import compare, device, spec  # noqa: E402


def emit(**kw):
    print(json.dumps(kw, default=harness._jsonable), flush=True)


def train_readings(h, with_controls: bool):
    """Run the cell once; the gaps of the program and, with
    ``with_controls``, of the control and the faults."""
    drv = h.driver
    got = {}
    real_gaps = drv.gaps

    def gaps(ref, losses, grad_norms, change_norms, log=None):
        out = real_gaps(ref, losses, grad_norms, change_norms, log)
        got["program"] = out
        got["ref_losses"], got["losses"] = ref["losses"], losses
        if not with_controls:
            return out
        batches = drv.ring(h.cfg, h.traffic, h.seed)[
            :int(h.traffic["checked_steps"])]
        exp = float(h.traffic["zipf_exponent"])
        for name, kw in (("control", {"mode": "bf16"}),
                         ("fault_half", {"mode": "stated", "half": True}),
                         ("f32_vs_stated", {"mode": "f32"})):
            alt = h.model.reference_train(h.cfg, h.seed, batches,
                                          exponent=exp, **kw)
            got[name] = real_gaps(ref, alt["losses"], alt["grad_norms"],
                                  alt["change_norms"])
        got["fault_unchanged"] = real_gaps(
            ref, ref["losses"], ref["grad_norms"],
            {k: 0.0 for k in ref["change_norms"]})
        return out
    drv.gaps = gaps
    res = harness.run_cell(h)
    return res, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    device.use_compile_cache()
    devices = device.require_chips(int(cell["chips"]))
    limits = compare.limits(cell["name"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for k, seed in enumerate(seeds):
        ns = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        h = harness.Harness(bench, cell, ns, devices)
        h.t_start = time.perf_counter()
        res, got = train_readings(h, k < args.control_seeds)
        verdicts = {name: compare.verdict(r, limits)[0]
                    for name, r in got.items() if isinstance(r, dict)}
        emit(workload=cell["name"], seed=seed, correct=res["correct"],
             metrics={k: v["value"] for k, v in res["metrics"].items()},
             peak=res["device"]["memory_peak_bytes"], readings=got,
             correct_under_limits=verdicts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
