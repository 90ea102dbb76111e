"""Operations and bytes that the work needs, from shapes alone.

These count what the algorithm must do, not what an implementation happens
to do, so a share of a peak computed from them reads the same work whatever
computes it, and a faster implementation reads higher. A model's own count
of operations lives in its module under ``bench/models/``; what is here is
shared by any model.
"""
from __future__ import annotations

F32 = 4


def mlp_flops(d_in: int, hidden, d_out: int = 1) -> int:
    """Multiply-adds ×2 of one row through a dense tower."""
    dims = [d_in, *hidden, d_out]
    return int(sum(2 * a * b for a, b in zip(dims[:-1], dims[1:])))


def dense_adam_bytes(param_sizes) -> int:
    """HBM bytes of one dense Adam step over parameters of these element
    counts (float32): the gradient written once, then gradient, parameter
    and both moments read and parameter and moments written."""
    return int(sum(param_sizes) * F32 * 8)
