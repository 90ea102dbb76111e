"""jit'd public wrapper with custom_vjp: forward and backward both run the
fused Pallas kernels, so QAT training takes one HBM round-trip per direction
instead of m=7."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mpe_qat.kernel import (mixed_expectation_bwd,
                                          mixed_expectation_fwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mixed_expectation_kernel(rows, probs, alpha, beta, bits):
    return mixed_expectation_fwd(rows, probs, alpha, beta, bits=bits)


def _fwd(rows, probs, alpha, beta, bits):
    out = mixed_expectation_fwd(rows, probs, alpha, beta, bits=bits)
    return out, (rows, probs, alpha, beta)


def _bwd(bits, res, g):
    rows, probs, alpha, beta = res
    drows, dprobs, dalpha, dbeta = mixed_expectation_bwd(
        rows, probs, alpha, beta, g, bits=bits)
    return drows, dprobs, dalpha, dbeta


mixed_expectation_kernel.defvjp(_fwd, _bwd)


def mixed_expectation_kernel_sharded(rows, probs, alpha, beta, bits, *,
                                     mesh=None):
    """Forward Eq. (9) under ``shard_map``: rows split over every mesh axis
    (row-parallel, collective-free, bit-exact), padded up to the device
    count and unpadded after. Falls back to the fused kernel when no
    multi-device mesh is active (see ``repro.dist.shard``)."""
    from repro.dist.shard import sharded_mixed_expectation
    return sharded_mixed_expectation(rows, probs, alpha, beta, bits,
                                     mesh=mesh)
