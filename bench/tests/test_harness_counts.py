"""The byte and FLOP counters against values worked out by hand, and the
step readers' arithmetic on a trace summary."""
from __future__ import annotations

import harness_util
import pytest

from yardstick import counts, spec

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_mlp_flops():
    # 8 -> 6 -> 3 -> 1: 2 * (48 + 18 + 3) = 138
    assert counts.mlp_flops(8, [6, 3]) == 138


def test_model_tower_and_train_flops():
    model = spec.load_module("models", "dlrm_dnn")
    cfg = {"field_vocabs": [10, 10], "d": 4, "mlp_hidden": [6, 3]}
    assert model.tower_flops(cfg) == 138
    assert model.train_flops(cfg) == 3 * 138


def test_criteo_tower_flops():
    model = spec.load_module("models", "dlrm_dnn")
    cfg = spec.config("dlrm-criteo")
    per_row = 2 * (624 * 1024 + 1024 * 512 + 512 * 256 + 256)
    assert model.tower_flops(cfg) == per_row == 2_589_184


def test_dense_adam_bytes():
    # two leaves of 10 and 6 float32 elements, 8 passes of 4 bytes each
    assert counts.dense_adam_bytes([10, 6]) == 16 * 4 * 8


def _summary(modules):
    return {"busy_s": 1.0, "window_s": 2.0, "modules": modules}


@pytest.mark.parametrize("metric,key,peak", [
    ("train_hbm_share", "step_bytes", "hbm_bytes_per_s"),
    ("train_mfu", "step_flops", "bf16_flops")])
def test_step_share_reads_the_step_program(metric, key, peak):
    # the step program ran 10 times in 0.5 device seconds; a copy program
    # that took less device time is not the step
    reader = spec.load_module("metrics", metric)
    ctx = {"peaks": V5E, key: 1e9,
           "trace": _summary({"jit_train_step": [0.5, 10],
                              "jit_copy": [0.01, 10]})}
    assert reader.read(ctx) == pytest.approx(100.0 * 1e9 * 10 / V5E[peak]
                                             / 0.5)


@pytest.mark.parametrize("metric", ["train_hbm_share", "train_mfu",
                                    "device_idle.train"])
def test_reader_without_a_trace_reads_nothing(metric):
    reader = spec.load_module("metrics", metric)
    ctx = {"peaks": V5E, "step_bytes": 1e9, "step_flops": 1e9, "trace": None}
    assert reader.read(ctx) is None


def test_device_idle_share():
    reader = spec.load_module("metrics", "device_idle.train")
    assert reader.read({"trace": _summary({})}) == pytest.approx(50.0)


def test_benchmark_names_resolve_to_files():
    """Every configuration, mix, driver, model, reader and limits file that
    BENCHMARK.json names is there, and every per-layer metric's layer
    reports its end-to-end metric in each of its cells."""
    import os
    bench = spec.benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    for c in cells.values():
        cfg, mix = spec.config(c["config"]), spec.traffic(c["traffic"])
        spec.load_module("models", cfg["model"])
        spec.load_module("drivers", mix["driver"])
        assert os.path.exists(os.path.join(
            harness_util.BENCH, "limits", c["name"] + ".json"))
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert hasattr(spec.load_module("metrics", m["name"]), "read")
    for m in bench["per_layer"]:
        for cell in m.get("workloads", cells):
            e2e = [e["name"] for e in spec.cell_metrics(bench, cell,
                                                        "end_to_end")]
            assert m["moves"] in e2e
