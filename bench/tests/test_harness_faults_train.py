"""A whole training run at the reduced size on the CPU, past the harness's
look for a chip, under the cell's own limits: sound, it is correct; with a
step that leaves the parameters unchanged, a loss over half of each batch,
or the bfloat16 reference (the control) in the program's place, it is
not."""
from __future__ import annotations

import harness_util


def test_sound_run_is_correct():
    run, h = harness_util.cpu_harness("criteo-train-search")
    res = run.run_cell(h)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def test_unchanged_state_is_caught(monkeypatch):
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "apply_updates", lambda params, updates: params)
    run, h = harness_util.cpu_harness("criteo-train-search")
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
    run, h = harness_util.cpu_harness("criteo-train-search")
    res = run.run_cell(h)
    assert not res["correct"]


def test_bf16_control_is_caught():
    run, h = harness_util.cpu_harness("criteo-train-search")
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
