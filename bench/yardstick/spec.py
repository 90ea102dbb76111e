"""Name → file lookup for everything ``BENCHMARK.json`` names.

A cell names a configuration and a traffic mix; each lives in a file of its
own that is found by that name, and so does the reader of each metric and
the code of each model and driver:

    bench/configs/<config>.json     sizes of one configuration, as run
    bench/models/<model>.py         builder + plain reference, named by the
                                    configuration's ``"model"`` key
    bench/traffic/<traffic>.json    parameters of one traffic mix
    bench/drivers/<driver>.py       the load generator or job driver, named
                                    by the traffic file's ``"driver"`` key
    bench/metrics/<metric>.py       the reader of one metric

Adding a cell, a configuration, a mix or a metric is adding files and
entries; no file that exists needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _checked(name: str, what: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {what} name {name!r}")
    return name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(checkout: str = CHECKOUT) -> dict:
    return load_json(os.path.join(checkout, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def config(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "configs",
                                  _checked(name, "config") + ".json"))


def traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "traffic",
                                  _checked(name, "traffic") + ".json"))


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, _checked(name, kind) + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports: those whose ``workloads`` list it; without the key, an
    end-to-end metric is everyone's and a per-layer metric belongs to every
    cell that reports the end-to-end metric it ``moves``."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out
