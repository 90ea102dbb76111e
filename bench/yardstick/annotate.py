"""Host spans around the harness's calls into the program, on the
profiler's clock: ``window`` (the measured window), ``make_batch`` and
``train_step``. They cost a few microseconds each when no trace is being
taken."""
from __future__ import annotations

import jax

ANNOTATIONS = ("make_batch", "train_step")


def span(name: str):
    return jax.profiler.TraceAnnotation(name)
