"""Fused expectation-over-bit-widths QAT kernel (paper Eq. 9) + its backward.

The plain formulation runs the LSQ+ quantizer once per non-zero width over
the gathered rows, and its backward reduces ∂α, ∂β and ∂p in passes of
their own: many HBM round-trips on a memory-bound op. These kernels read a
row block into VMEM once per direction and unroll the (static) width list
over it.

Backward fuses all four gradient terms of Eq. (9) — ∂rows (Eq. 4 per width,
p-weighted), ∂probs (= Q_i(e)·g reduced over d), ∂α (Eq. 5 reduced over the
whole tile grid) and ∂β (Eq. 6, likewise) — in a single pass over the same
block, accumulating the shared-parameter grads across grid steps in a
revisited output block. The arithmetic is the float32 LSQ+ quantizer's
(``repro.core.quantizer``); only the summation order of ∂α, ∂β and ∂p
differs from it.

Tile geometry, from a sweep on one v5e chip at 438,272 rows of d=128:
whole blocks of ``TILE_B`` rows each way (fewer rows take one block of the
row count rounded up to 8). A row count that is not a multiple of the tile
leaves a partial last block: its reads past the end are masked out of ∂α
and ∂β, and its writes past the end are dropped, so nothing is padded or
copied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantizer import int_bounds
from repro.kernels.backend import resolve_interpret

TILE_B = 1024


def _tile(n: int) -> int:
    return min(TILE_B, -(-n // 8) * 8)


def _fwd_kernel(rows_ref, probs_ref, alpha_ref, beta_ref, out_ref, *, bits):
    probs = probs_ref[...]                     # (T, m)
    beta = beta_ref[...]                       # (1, d)
    x = rows_ref[...] - beta                   # (T, d)
    acc = jnp.zeros_like(x)
    for i, b in enumerate(bits):
        if b == 0:
            continue
        n_b, p_b = int_bounds(b)
        alpha = alpha_ref[0, i]
        v = x / alpha
        codes = jnp.clip(jnp.round(v), n_b, p_b)
        acc = acc + probs[:, i:i + 1] * (alpha * codes + beta)
    out_ref[...] = acc


def _bwd_kernel(rows_ref, probs_ref, alpha_ref, beta_ref, g_ref,
                drows_ref, dprobs_ref, dalpha_ref, dbeta_ref, *, bits, n):
    tile = rows_ref.shape[0]
    rows, probs, beta, g = (rows_ref[...], probs_ref[...], beta_ref[...],
                            g_ref[...])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dalpha_ref[...] = jnp.zeros_like(dalpha_ref)
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    if n % tile:               # rows past the end hold whatever was read
        row = (pl.program_id(0) * tile
               + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0))
        valid = row < n
        rows = jnp.where(valid, rows, 0.0)
        probs = jnp.where(valid, probs, 0.0)
        g = jnp.where(valid, g, 0.0)

    x = rows - beta
    drows = jnp.zeros_like(rows)
    dbeta = jnp.zeros_like(rows)
    dprobs_cols, dalpha = [], []
    for i, b in enumerate(bits):
        if b == 0:
            dprobs_cols.append(jnp.zeros_like(probs[:, :1]))
            dalpha.append(jnp.zeros((1, 1), jnp.float32))
            continue
        n_b, p_b = int_bounds(b)
        alpha = alpha_ref[0, i]
        v = x / alpha
        codes = jnp.clip(jnp.round(v), n_b, p_b)
        q = alpha * codes + beta
        inside = (v > n_b) & (v < p_b)
        pg = probs[:, i:i + 1] * g
        # ∂probs_i = <g, Q_i> per row
        dprobs_cols.append(jnp.sum(g * q, axis=1, keepdims=True))
        # ∂rows += p_i · 1[inside] · g                      (Eq. 4)
        drows = drows + jnp.where(inside, pg, 0.0)
        # ∂α_i = Σ p_i · g · (N_b | codes - v | P_b)        (Eq. 5)
        dq_da = jnp.where(v <= n_b, float(n_b),
                          jnp.where(v >= p_b, float(p_b), codes - v))
        dalpha.append(jnp.sum(pg * dq_da).reshape(1, 1))
        # ∂β += p_i · g · 1[outside]                        (Eq. 6)
        dbeta = dbeta + jnp.where(inside, 0.0, pg)
    drows_ref[...] = drows
    dprobs_ref[...] = jnp.concatenate(dprobs_cols, axis=1)
    dalpha_ref[...] += jnp.concatenate(dalpha, axis=1)       # (1, m) revisited
    dbeta_ref[...] += jnp.sum(dbeta, axis=0, keepdims=True)  # (1, d) revisited


def _row_spec(tile, width):
    return pl.BlockSpec((tile, width), lambda i: (i, 0))


def _whole_spec(width):
    return pl.BlockSpec((1, width), lambda i: (0, 0))


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def mixed_expectation_fwd(rows, probs, alpha, beta, *, bits,
                          interpret: bool | None = None):
    n, d = rows.shape
    m = len(bits)
    tile = _tile(n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bits=bits),
        grid=(pl.cdiv(n, tile),),
        in_specs=[_row_spec(tile, d), _row_spec(tile, m), _whole_spec(m),
                  _whole_spec(d)],
        out_specs=_row_spec(tile, d),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        name="mpe_qat_fwd",
        interpret=resolve_interpret(interpret),
    )(rows, probs, alpha.reshape(1, m), beta.reshape(1, d))


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def mixed_expectation_bwd(rows, probs, alpha, beta, g, *, bits,
                          interpret: bool | None = None):
    n, d = rows.shape
    m = len(bits)
    tile = _tile(n)
    drows, dprobs, dalpha, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, bits=bits, n=n),
        grid=(pl.cdiv(n, tile),),
        in_specs=[_row_spec(tile, d), _row_spec(tile, m), _whole_spec(m),
                  _whole_spec(d), _row_spec(tile, d)],
        out_specs=[_row_spec(tile, d), _row_spec(tile, m),
                   _whole_spec(m),    # revisited: accumulates
                   _whole_spec(d)],   # revisited: accumulates
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, m), jnp.float32),
                   jax.ShapeDtypeStruct((1, m), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        name="mpe_qat_bwd",
        interpret=resolve_interpret(interpret),
    )(rows, probs, alpha.reshape(1, m), beta.reshape(1, d), g)
    return drows, dprobs, dalpha.reshape(m), dbeta.reshape(d)
