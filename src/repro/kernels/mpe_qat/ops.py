"""jit'd public wrapper with custom_vjp: forward and backward both run the
fused Pallas kernels, so QAT training takes one HBM round-trip per direction
instead of one per width. Both kernels are emitted under the
``embed_quantize`` scope, the backward too, so that a trace reads them as
the step's ``quantize`` part."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mpe_qat.kernel import (mixed_expectation_bwd,
                                          mixed_expectation_fwd)

SCOPE = "embed_quantize"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mixed_expectation_kernel(rows, probs, alpha, beta, bits):
    with jax.named_scope(SCOPE):
        return mixed_expectation_fwd(rows, probs, alpha, beta, bits=bits)


def _fwd(rows, probs, alpha, beta, bits):
    with jax.named_scope(SCOPE):
        out = mixed_expectation_fwd(rows, probs, alpha, beta, bits=bits)
    return out, (rows, probs, alpha, beta)


def _bwd(bits, res, g):
    with jax.named_scope(SCOPE):
        return mixed_expectation_bwd(*res, g, bits=bits)


mixed_expectation_kernel.defvjp(_fwd, _bwd)

