"""Generic fault-tolerant training loop.

Works with every model in the zoo through a uniform loss signature:

    loss_fn(params, buffers, state, batch, *, step) -> (loss, (new_state, metric))

Features (DESIGN.md §5):
  - jitted train step with grad clipping;
  - named scopes ``clip`` and ``update`` over the step's device ops, host
    spans ``trainer.step`` (a ``StepTraceAnnotation``), ``trainer.data``,
    ``trainer.stage`` and ``trainer.dispatch`` on the profiler's clock;
  - NaN/inf guard: non-finite grads skip the update (params/opt state
    kept). The guard is the optimizer's ``ok`` argument, folded into its
    per-step scalars (zero step size, moment decays (1, 0), gradient read
    as zero): selecting per element between the new and the old params
    and moments split a table's update into three passes (θ, μ, ν each
    streaming the gradient), where the folded guard lets XLA write all
    three in one;
  - checkpoint every N steps (atomic, keep-k, async), restore-on-start;
  - optional compressor post-update hook (ALPT grid projection);
  - optional int8 error-feedback gradient compression (cross-pod simulation);
  - deterministic restart: the data function is keyed by step.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.train import checkpoint as ckpt
from repro.train.compression import make_error_feedback_transform
from repro.train.optimizer import apply_updates, clip_by_global_norm


class Trainer:
    def __init__(self, loss_fn: Callable, params, buffers, state, optimizer, *,
                 ckpt_dir: str | None = None, ckpt_every: int = 200,
                 ckpt_keep: int = 3, clip_norm: float = 10.0,
                 post_update: Callable | None = None,
                 grad_compression: bool = False, donate: bool = True,
                 mesh=None, table_rows_axes=("model",)):
        self.loss_fn = loss_fn
        self.buffers = buffers
        self.optimizer = optimizer
        self.ckpt_dir, self.ckpt_every, self.ckpt_keep = ckpt_dir, ckpt_every, ckpt_keep
        self.post_update = post_update
        self.step = 0
        # one entry per log point: {"step", "loss", "metric", "grad_norm",
        # "skipped", "wall_s"} — wall_s is the host time since the previous
        # log point (or the start of ``run``), compile included
        self.history: list[dict] = []
        opt_state = optimizer.init(params)
        ef_init, ef_apply = make_error_feedback_transform()
        self.grad_compression = grad_compression
        ef_state = ef_init(params) if grad_compression else None
        self.carry = {"params": params, "state": state, "opt": opt_state,
                      "ef": ef_state}

        # loss+grad: plain on one device; on a multi-device mesh the whole
        # thing runs inside shard_map — batch data-parallel over the mesh,
        # embedding-table rows sharded over `table_rows_axes` with
        # row-shard-local grads, replicated params pmean'd (repro.dist.shard)
        self.mesh = mesh
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            from repro.dist.shard import sharded_value_and_grad
            value_and_grad = sharded_value_and_grad(
                self.loss_fn, mesh, rows_axes=table_rows_axes)
        else:
            def value_and_grad(params, buffers, state, batch, *, step):
                return jax.value_and_grad(self.loss_fn, has_aux=True)(
                    params, buffers, state, batch, step=step)

        # buffers enter as an argument, not a closure: closed-over arrays
        # would be embedded in the program as constants (at Criteo width the
        # per-feature group map alone is 137 MB)
        def train_step(carry, buffers, batch, step):
            params, state, opt_state = carry["params"], carry["state"], carry["opt"]
            (loss, (new_state, metric)), grads = value_and_grad(
                params, buffers, state, batch, step=step)
            with jax.named_scope("clip"):
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
            with jax.named_scope("update"):
                ef_state = carry["ef"]
                if self.grad_compression:
                    grads, ef_state = ef_apply(grads, ef_state)
                # NaN guard: non-finite grads or loss skip the whole update
                ok = jnp.isfinite(gnorm) & jnp.isfinite(loss)
                updates, new_opt = self.optimizer.update(grads, opt_state,
                                                         params, ok=ok)
                new_params = apply_updates(params, updates)
            new_carry = {"params": new_params, "state": new_state,
                         "opt": new_opt, "ef": ef_state}
            return new_carry, {"loss": loss, "metric": metric,
                               "grad_norm": gnorm, "skipped": ~ok}

        self._train_step = jax.jit(train_step, donate_argnums=(0,) if donate else ())

    def compiled_step(self, batch):
        """The executable ``run`` calls for a batch shaped like ``batch``.
        Its ``as_text()`` names every device op with the scope it came from
        (``op_name`` metadata)."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return self._train_step.lower(self.carry, self.buffers, batch,
                                      jnp.asarray(self.step)).compile()

    # -- fault tolerance ----------------------------------------------------
    def restore(self) -> bool:
        if self.ckpt_dir is None:
            return False
        tree, step = ckpt.restore(self.ckpt_dir, {"carry": _restorable(self.carry),
                                                  "step": 0})
        if tree is None:
            return False
        restored = tree["carry"]
        if self.carry.get("ef") is None:
            restored["ef"] = None
        self.carry = restored
        self.step = int(tree["step"])
        return True

    def save(self, blocking: bool = False):
        if self.ckpt_dir is None:
            return
        payload = {"carry": _restorable(self.carry), "step": self.step}
        if blocking:
            ckpt.save(self.ckpt_dir, self.step, payload, keep=self.ckpt_keep)
        else:
            ckpt.save_async(self.ckpt_dir, self.step, payload, keep=self.ckpt_keep)

    # -- main loop ------------------------------------------------------------
    def run(self, data_fn: Callable, n_steps: int, *, log_every: int = 100,
            log_fn=print, prefetch=False) -> dict:
        """Run up to ``n_steps``. ``prefetch`` stages each batch on device one
        step ahead of compute (``repro.cache.PrefetchPipeline`` — pass True
        for a default pipeline or a pre-built one), overlapping the
        host→device copy with the in-flight step's compute. Same bytes, same
        order: losses are step-identical to the synchronous loop."""
        if prefetch:
            from repro.cache.prefetch import PrefetchPipeline
            data_fn = (prefetch if isinstance(prefetch, PrefetchPipeline)
                       else PrefetchPipeline(data_fn))
        t0 = t_last = time.perf_counter()
        start_step = self.step
        last = {}
        while self.step < n_steps:
            with jax.profiler.StepTraceAnnotation("trainer.step",
                                                  step_num=self.step):
                with jax.profiler.TraceAnnotation("trainer.data"):
                    batch = data_fn(self.step)
                with jax.profiler.TraceAnnotation("trainer.stage"):
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                with jax.profiler.TraceAnnotation("trainer.dispatch"):
                    self.carry, out = self._train_step(
                        self.carry, self.buffers, batch, jnp.asarray(self.step))
                if self.post_update is not None:
                    self.carry["params"] = self.post_update(self.carry["params"])
                self.step += 1
                if log_every and self.step % log_every == 0:
                    last = {k: float(v) for k, v in out.items()}
                    now = time.perf_counter()
                    self.history.append(dict(last, step=self.step,
                                             wall_s=now - t_last))
                    t_last = now
                    ms = (now - t0) / (self.step - start_step) * 1e3
                    log_fn(f"step {self.step} loss {last['loss']:.5f} "
                           f"gnorm {last['grad_norm']:.3f} ({ms:.1f} ms/step)")
                if self.ckpt_dir and self.step % self.ckpt_every == 0:
                    self.save()
        if self.ckpt_dir:
            self.save(blocking=True)
        return last

    @property
    def params(self):
        return self.carry["params"]

    @property
    def state(self):
        return self.carry["state"]


def _restorable(carry):
    """Drop None leaves (npz can't store them)."""
    return {k: v for k, v in carry.items() if v is not None}
