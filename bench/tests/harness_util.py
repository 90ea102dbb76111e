"""Shared set-up of the benchmark's CPU tests: the benchmark's directory
and the program's ``src`` on the path, the harness module loaded under a
name of its own, and reduced copies of the configurations and mixes."""
from __future__ import annotations

import importlib.util
import os
import sys
import types

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(BENCH)
for p in (os.path.join(CHECKOUT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from yardstick import spec  # noqa: E402


def harness_module():
    """``bench/run.py``, imported once as ``bench_run``."""
    if "bench_run" not in sys.modules:
        s = importlib.util.spec_from_file_location(
            "bench_run", os.path.join(BENCH, "run.py"))
        mod = importlib.util.module_from_spec(s)
        sys.modules["bench_run"] = mod
        s.loader.exec_module(mod)
    return sys.modules["bench_run"]


def reduced_config(name: str) -> dict:
    """The configuration at a size a CPU test can hold: every key as run,
    four small fields and a two-layer tower."""
    cfg = spec.config(name)
    cfg.update(field_vocabs=[1000, 700, 300, 50], mlp_hidden=[32, 16])
    return cfg


def reduced_mix(name: str) -> dict:
    mix = spec.traffic(name)
    mix.update(batch=64, ring_batches=4)
    return mix


def cpu_harness(cell_name: str, *, seed: int = 2**31 + 77,
                seconds: float = 0.5):
    """A harness for the cell ``cell_name`` of ``BENCHMARK.json`` on the
    CPU at the reduced size, under the cell's own limits, its reference at
    plain float32 (the CPU's products are float32)."""
    import jax

    from yardstick import peaks
    run = harness_module()
    bench = spec.benchmark()
    cell = spec.workload(bench, cell_name)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    h = run.Harness(bench, cell, args, jax.devices()[:1],
                    cfg=reduced_config(cell["config"]),
                    mix=reduced_mix(cell["traffic"]), log=lambda m: None,
                    peaks=peaks.PEAKS["TPU v5 lite"])
    h.ref_mode = "f32"
    return run, h
