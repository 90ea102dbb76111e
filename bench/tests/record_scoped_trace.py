"""Record the scoped device trace that ``test_harness_scopes.py`` reduces.

    python3 bench/tests/record_scoped_trace.py [out_dir]

On a chip: the program's own ``Trainer`` steps a tiny model whose loss
carries the program's scope names (a row gather under ``embed_gather``, a
logit summed from the rows and its cross-entropy under ``tower``; the
trainer adds ``clip`` and ``update``). It warms up, then runs three steps
inside a ``window`` annotation, one ``Trainer.run`` call and one wait for
the device each, so the trace holds every part of a step but the
quantizer (the gather's forward and its transpose, the table gradient,
among them) and the trainer's host spans. The host tracer records user
annotations only, which keeps the file small. Writes
``trace_scoped.xplane.pb`` and the step's compiled HLO text,
``trace_scoped.hlo.txt.gz``, and prints the reduction by part and the lead
of each run's device start over its ``trainer.dispatch`` span.
"""
from __future__ import annotations

import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import jax  # noqa: E402

from yardstick import scopes, trace  # noqa: E402


def tiny_trainer():
    """A 512-row table of width 8 looked up by two fields, the logit the
    sum of the rows, Adam; batches of 256 seeded ids and labels."""
    import jax.numpy as jnp
    import numpy as np

    from repro.train.loop import Trainer
    from repro.train.optimizer import adam
    rows, fields, d, batch = 512, 2, 8, 256

    def loss_fn(params, buffers, state, b, *, step=None):
        with jax.named_scope("embed_gather"):
            emb = jnp.take(params["emb"], b["ids"], axis=0)
        with jax.named_scope("tower"):
            logit = jnp.sum(emb, axis=(1, 2))
            y = b["label"].astype(jnp.float32)
            loss = jnp.mean(jnp.maximum(logit, 0) - logit * y
                            + jnp.log1p(jnp.exp(-jnp.abs(logit))))
        return loss, (state, loss)

    params = {"emb": 0.1 * jax.random.normal(jax.random.PRNGKey(0),
                                             (rows, d))}

    def data(step):
        gen = np.random.default_rng(step)
        return {"ids": gen.integers(0, rows, (batch, fields), np.int32),
                "label": gen.integers(0, 2, batch, np.int32)}
    return Trainer(loss_fn, params, {}, {}, adam(1e-3)), data


def main(out_dir: str) -> int:
    trainer, batch = tiny_trainer()
    trainer.run(batch, 2, log_every=0)
    jax.block_until_ready(trainer.carry)
    hlo = trainer.compiled_step(batch(0)).as_text()
    tdir = tempfile.mkdtemp(prefix="record_scoped_")
    opts = trace.profile_options()
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            trainer.run(batch, trainer.step + 1, log_every=0)
            jax.block_until_ready(trainer.carry)
    jax.profiler.stop_trace()
    xplane = os.path.join(out_dir, "trace_scoped.xplane.pb")
    shutil.copy(trace.find_xplane(tdir), xplane)
    shutil.rmtree(tdir, ignore_errors=True)
    with gzip.open(os.path.join(out_dir, "trace_scoped.hlo.txt.gz"),
                   "wt") as f:
        f.write(hlo)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane)
    got = scopes.reduce_scopes(pd, hlo)
    print(got)
    print("clock lead:", scopes.lead_summary(
        scopes.clock_leads(pd, "trainer.dispatch", got["module"])))
    print("size", os.path.getsize(xplane))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(HERE, "data")))
