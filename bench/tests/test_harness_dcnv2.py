"""The ``dcnv2-multihot-train-search`` cell's whole training run at a
reduced size on the CPU, past the harness's look for a chip, under the
cell's own limits: sound, it is correct; with a step that leaves the
parameters unchanged, a loss over half of each batch, or the bfloat16
reference (the control) in the program's place, it is not. The loss
compared over the first two steps, the bag's HBM share read from a fixed
context, and the step's new named scopes (the bag's
per-field sum, the bottom MLP, the cross layers) given to their parts."""
from __future__ import annotations

import types

import harness_util
import pytest

from yardstick import spec

CELL = "dcnv2-multihot-train-search"


def reduced_config() -> dict:
    """The configuration at a size a CPU test can hold: every key as run,
    four small fields of bag sizes 3, 1, 7 and 12, and small towers."""
    cfg = spec.config("dlrm-dcnv2-mlperf")
    cfg.update(field_vocabs=[1000, 700, 300, 50], multi_hot_sizes=[3, 1, 7, 12],
               d=16, bottom_mlp=[32, 16], top_mlp=[32, 16], cross_rank=8)
    return cfg


def cpu_harness(seed: int = 2**31 + 77):
    """A harness for the cell on the CPU at the reduced size, under the
    cell's own limits, its reference at plain float32 (the CPU's products
    are float32)."""
    import jax

    from yardstick import peaks
    run = harness_util.harness_module()
    bench = spec.benchmark()
    cell = spec.workload(bench, CELL)
    mix = spec.traffic(cell["traffic"])
    mix.update(batch=64, ring_batches=4)
    args = types.SimpleNamespace(seed=seed, seconds=0.5, trace=0)
    h = run.Harness(bench, cell, args, jax.devices()[:1],
                    cfg=reduced_config(), mix=mix, log=lambda m: None,
                    peaks=peaks.PEAKS["TPU v5 lite"])
    h.ref_mode = "f32"
    return run, h


def test_sound_run_is_correct():
    run, h = cpu_harness()
    res = run.run_cell(h)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "train_samples_per_s"}


def test_unchanged_state_is_caught(monkeypatch):
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "apply_updates", lambda params, updates: params)
    run, h = cpu_harness()
    res = run.run_cell(h)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > 0.99


def test_half_batch_is_caught(monkeypatch):
    from repro.models.dlrm import DLRM
    real = DLRM.loss_fn

    def half(params, buffers, state, batch, cfg, **kw):
        n = batch["label"].shape[0] // 2
        return real(params, buffers, state,
                    {k: v[:n] for k, v in batch.items()}, cfg, **kw)
    monkeypatch.setattr(DLRM, "loss_fn", staticmethod(half))
    run, h = cpu_harness()
    res = run.run_cell(h)
    assert not res["correct"]


def test_bf16_control_is_caught():
    run, h = cpu_harness()
    drv, real = h.driver, h.driver.gaps

    def control(ref, losses, grad_norms, change_norms, log=None):
        batches = drv.ring(h.cfg, h.traffic, h.seed)[
            :int(h.traffic["checked_steps"])]
        ctl = h.model.reference_train(
            h.cfg, h.seed, batches, "bf16",
            exponent=float(h.traffic["zipf_exponent"]))
        return real(ref, ctl["losses"], ctl["grad_norms"],
                    ctl["change_norms"], log)
    drv.gaps = control
    res = run.run_cell(h)
    assert not res["correct"], res["checks"]


V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(trace, config="dlrm-dcnv2-mlperf"):
    return {"trace": trace, "peaks": V5E, "cfg": spec.config(config),
            "traffic": spec.traffic("mpe-search-multihot")}


STEP_TRACE = {"busy_s": 1.0, "window_s": 2.0,
              "modules": {"jit_train_step": [0.5, 10], "jit_copy": [0.01, 10]}}


@pytest.mark.parametrize("ctx", [
    _ctx(None),
    _ctx({"busy_s": 1.0, "window_s": 2.0, "modules": {}}),
    _ctx(STEP_TRACE, config="dlrm-criteo"),
], ids=["no_trace", "no_program", "no_bytes"])
def test_bag_hbm_share_reads_nothing_without_its_inputs(ctx):
    # no_bytes: dlrm-criteo's model counts no bag
    reader = spec.load_module("metrics", "bag_hbm_share")
    assert reader.read(ctx) is None


def test_bag_hbm_share_reads_the_step_program():
    # the step program ran 10 times in 0.5 device seconds; a copy program
    # that took less device time is not the step
    reader = spec.load_module("metrics", "bag_hbm_share")
    model = spec.load_module("models", "dlrm_dcnv2")
    ctx = _ctx(STEP_TRACE)
    nbytes = model.bag_bytes(ctx["cfg"], ctx["traffic"])
    # 2048 samples x 214 ids, each 3 x 512 B of row, 4 B of group id and
    # 7 x 4 B of width probabilities
    assert nbytes == 2048 * 214 * (3 * 128 * 4 + 4 + 7 * 4) == 687_210_496
    assert reader.read(ctx) == pytest.approx(100.0 * nbytes * 10
                                             / 819e9 / 0.5)


@pytest.mark.parametrize("step,read", [(0, 0.01), (1, 0.01), (2, 0.0)],
                         ids=["step1", "step2", "step3"])
def test_loss_gap_reads_the_first_two_steps(step, read):
    """A loss off by 1% is read on the first two steps, and not on the
    third, which rounding moves (the driver's docstring)."""
    drv = spec.load_module("drivers", "train_bags")
    norms = {"a": 1.0, "b": 2.0}
    ref = {"losses": [3.0, 2.5, 2.0], "grad_norms": norms,
           "change_norms": norms}
    losses = list(ref["losses"])
    losses[step] *= 1.01
    out = drv.gaps(ref, losses, norms, norms)
    assert out["loss_gap"] == pytest.approx(read)
    assert out["grad_gap"] == out["change_gap"] == 0.0


@pytest.fixture(scope="module")
def op_names():
    import re
    model = spec.load_module("models", "dlrm_dcnv2")
    drv = spec.load_module("drivers", "train_bags")
    cfg = reduced_config()
    mix = spec.traffic("mpe-search-multihot")
    mix.update(batch=16, ring_batches=1)
    tr = model.build_trainer(cfg, 3, 1.1)
    text = tr.compiled_step(drv.ring(cfg, mix, 3)[0]).as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope,parts", [
    ("jvp(embed_gather)/bag_pool/", {"gather"}),
    ("transpose(jvp(embed_gather))/bag_pool/", {"table_grad"}),
    ("jvp(tower)/bottom/", {"tower"}),
    ("transpose(jvp(tower))/bottom/", {"tower"}),
    ("jvp(tower)/cross/", {"tower"}),
    ("transpose(jvp(tower))/cross/", {"tower"})])
def test_new_scopes_fall_in_their_parts(op_names, scope, parts):
    """The bag's per-field sum, the bottom MLP and the cross layers are
    named inside the step's existing scopes, so ``yardstick.scopes``
    gives them to the bag's parts and to the tower as it stands."""
    from yardstick import scopes
    names = [n for n in op_names if f"jit(train_step)/{scope}" in n]
    assert names
    assert {scopes.part_of(n) for n in names} == parts
