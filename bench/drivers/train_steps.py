"""Training steps through the program's ``Trainer`` loop.

Traffic keys: ``batch`` (samples per step), ``ring_batches`` (distinct
seeded batches made in set-up, cycled by step and staged to the device by
the loop each step), ``zipf_exponent``, ``positive_rate`` (labels are
seeded Bernoulli draws), ``checked_steps`` (steps that set-up runs and the
reference follows).

Set-up builds one trainer and drives it through the checked steps with the
same call and feed that the window uses; the window goes on with that same
trainer. At most two steps are in flight: after each step the loop waits
for the one before it, as a loop that logs its losses does. The rate is
samples stepped in the window over the window, the last step blocked on.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import compare, counts, traffic
from yardstick.annotate import span


def ring(cfg: dict, mix: dict, seed: int) -> list[dict]:
    n, bsz = int(mix["ring_batches"]), int(mix["batch"])
    pool = traffic.id_pool(cfg["field_vocabs"], mix["zipf_exponent"],
                           n * bsz, seed)
    gen = traffic.rng(seed, 4)
    labels = (gen.random(n * bsz) < mix["positive_rate"]).astype(np.int32)
    return [{"ids": pool[k * bsz:(k + 1) * bsz],
             "label": labels[k * bsz:(k + 1) * bsz]} for k in range(n)]


def run(h) -> dict:
    cfg, mix, model = h.cfg, h.traffic, h.model
    t = cfg["train"]
    batches = ring(cfg, mix, h.seed)
    exponent = float(mix["zipf_exponent"])
    trainer = model.build_trainer(cfg, h.seed, exponent)
    n_groups = trainer.buffers["embedding"]["freq_sum"].shape[0]
    sizes = [int(x.size) for x in jax.tree.leaves(trainer.carry["params"])]

    def data_fn(step):
        with span("make_batch"):
            return batches[step % len(batches)]

    checked = int(mix["checked_steps"])
    trainer.run(data_fn, 1, log_every=1, log_fn=lambda *_: None)
    grad_norms = model.program_grad_norms(trainer, float(t["b1"]))
    trainer.run(data_fn, checked, log_every=1, log_fn=lambda *_: None)
    change_norms = model.program_change_norms(trainer, cfg, h.seed, n_groups)
    losses = [x["loss"] for x in trainer.history[:checked]]
    jax.block_until_ready(jnp.copy(trainer.carry["opt"]["step"]))
    h.settle()
    values = {"setup_s": h.setup_done()}

    clock = time.perf_counter
    steps, marks = 0, []
    with h.window():
        t0 = clock()
        while clock() - t0 < h.seconds:
            with span("train_step"):
                trainer.run(data_fn, trainer.step + 1, log_every=0)
            marks.append(jnp.copy(trainer.carry["opt"]["step"]))
            if len(marks) > 1:
                jax.block_until_ready(marks.pop(0))
            steps += 1
        jax.block_until_ready(trainer.carry)
        t_end = clock()
    window_s = t_end - t0
    samples = steps * int(mix["batch"])
    h.log(f"[train] {steps} steps of {mix['batch']} in {window_s:.3f} s "
          f"({samples / window_s:.1f} samples/s)")
    values.update({"samples": samples, "window_s": window_s,
                   "samples_per_s": samples / window_s,
                   "step_bytes": counts.dense_adam_bytes(sizes),
                   "step_flops": model.train_flops(cfg) * int(mix["batch"])})
    h.read_layers(values)

    peak = h.memory("peak_bytes_in_use")
    del trainer
    h.settle()
    ref = model.reference_train(cfg, h.seed, batches[:checked], h.ref_mode,
                                exponent=exponent)
    checks = gaps(ref, losses, grad_norms, change_norms, h.log)
    return {"attempted": steps, "failed": 0, "values": values,
            "checks": checks, "memory_peak_bytes": peak}


def gaps(ref, losses, grad_norms, change_norms, log=None) -> dict:
    leaves = compare.counted_leaves(ref["grad_norms"])
    g_gap, g_leaf = compare.norm_gap(grad_norms, ref["grad_norms"], leaves)
    c_gap, c_leaf = compare.norm_gap(change_norms, ref["change_norms"],
                                     leaves)
    if log is not None:
        left_out = sorted(set(ref["grad_norms"]) - set(leaves))
        log(f"[train] losses {losses} reference {ref['losses']}; worst grad "
            f"leaf {g_leaf}, worst change leaf {c_leaf}; left out as nought "
            f"to rounding: {left_out}")
    return {"loss_gap": compare.loss_gap(losses, ref["losses"]),
            "grad_gap": g_gap, "change_gap": c_gap}
