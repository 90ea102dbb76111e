"""Pallas execution mode, derived from the backend rather than a flag."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` when given, else True exactly when the default backend
    is not a TPU: the kernels compile with Mosaic on the chip and run in the
    Pallas interpreter everywhere else. Only a compile against a described
    (unattached) TPU topology passes ``False`` explicitly."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
