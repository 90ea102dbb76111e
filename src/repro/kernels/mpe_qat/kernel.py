"""Fused expectation-over-bit-widths QAT kernel (paper Eq. 9) + its backward.

The naive formulation runs the LSQ+ quantizer m=7 times over the gathered
rows — 7 HBM round-trips on a memory-bound op. This kernel keeps a
(TILE_B, d) row block resident in VMEM and unrolls the (static) width list in
registers: one HBM read, one write, regardless of m.

Backward fuses all four gradient terms of Eq. (9) — ∂rows (Eq. 4 per width,
p-weighted), ∂probs (= Q_i(e)·g reduced over d), ∂α (Eq. 5 reduced over the
whole tile grid) and ∂β (Eq. 6, likewise) — in a single pass over the same
block, accumulating the shared-parameter grads across grid steps in a
revisited output block.

Tile geometry: TILE_B = 256 rows keeps (rows + g + out + per-width temps)
≈ 256·d·4·4 B ≤ 1 MiB for d ≤ 256 — well inside the ~16 MiB v5e VMEM, and
d is lane-aligned (pad d to 128 upstream for peak VPU utilization; correctness
does not require it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantizer import int_bounds
from repro.kernels.backend import resolve_interpret

TILE_B = 256


def _fwd_kernel(rows_ref, probs_ref, alpha_ref, beta_ref, out_ref, *, bits):
    rows = rows_ref[...]                       # (T, d)
    probs = probs_ref[...]                     # (T, m)
    beta = beta_ref[...]                       # (1, d)
    acc = jnp.zeros_like(rows)
    for i, b in enumerate(bits):
        if b == 0:
            continue
        n_b, p_b = int_bounds(b)
        alpha = alpha_ref[0, i]
        v = (rows - beta) / alpha
        codes = jnp.clip(jnp.round(v), n_b, p_b)
        acc = acc + probs[:, i:i + 1] * (alpha * codes + beta)
    out_ref[...] = acc


def _bwd_kernel(rows_ref, probs_ref, alpha_ref, beta_ref, g_ref,
                drows_ref, dprobs_ref, dalpha_ref, dbeta_ref, *, bits):
    rows = rows_ref[...]
    probs = probs_ref[...]
    beta = beta_ref[...]
    g = g_ref[...]

    first = pl.program_id(0) == 0

    @pl.when(first)
    def _init():
        dalpha_ref[...] = jnp.zeros_like(dalpha_ref)
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    drows = jnp.zeros_like(rows)
    dprobs_cols = []
    dalpha_acc = []
    dbeta_acc = jnp.zeros_like(beta)
    for i, b in enumerate(bits):
        if b == 0:
            dprobs_cols.append(jnp.zeros_like(probs[:, :1]))
            dalpha_acc.append(jnp.zeros((1, 1), jnp.float32))
            continue
        n_b, p_b = int_bounds(b)
        alpha = alpha_ref[0, i]
        p_i = probs[:, i:i + 1]
        v = (rows - beta) / alpha
        codes = jnp.clip(jnp.round(v), n_b, p_b)
        q = alpha * codes + beta
        inside = (v > n_b) & (v < p_b)
        # ∂probs_i = <g, Q_i> per row
        dprobs_cols.append(jnp.sum(g * q, axis=1, keepdims=True))
        # ∂rows += p_i · 1[inside] · g                      (Eq. 4)
        drows = drows + p_i * jnp.where(inside, g, 0.0)
        # ∂α_i = Σ p_i · g · (N_b | codes - v | P_b)        (Eq. 5)
        dq_da = jnp.where(v <= n_b, float(n_b),
                          jnp.where(v >= p_b, float(p_b), codes - v))
        dalpha_acc.append(jnp.sum(p_i * g * dq_da).reshape(1, 1))
        # ∂β += p_i · g · 1[outside]                        (Eq. 6)
        dbeta_acc = dbeta_acc + jnp.sum(p_i * jnp.where(inside, 0.0, g),
                                        axis=0, keepdims=True)
    drows_ref[...] = drows
    dprobs_ref[...] = jnp.concatenate(dprobs_cols, axis=1)
    dalpha_ref[...] += jnp.concatenate(dalpha_acc, axis=1)   # (1, m) revisited
    dbeta_ref[...] += dbeta_acc                              # (1, d) revisited


def _pad(x, tile):
    b = x.shape[0]
    rem = (-b) % tile
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem, *x.shape[1:]), x.dtype)], axis=0)
    return x


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def mixed_expectation_fwd(rows, probs, alpha, beta, *, bits,
                          interpret: bool | None = None):
    b0, d = rows.shape
    m = len(bits)
    rows_p, probs_p = _pad(rows, TILE_B), _pad(probs, TILE_B)
    n_tiles = rows_p.shape[0] // TILE_B
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, bits=bits),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, m), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(rows_p.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(rows_p, probs_p, alpha.reshape(1, m), beta.reshape(1, d))
    return out[:b0]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def mixed_expectation_bwd(rows, probs, alpha, beta, g, *, bits,
                          interpret: bool | None = None):
    b0, d = rows.shape
    m = len(bits)
    rows_p, probs_p, g_p = _pad(rows, TILE_B), _pad(probs, TILE_B), _pad(g, TILE_B)
    n_tiles = rows_p.shape[0] // TILE_B
    drows, dprobs, dalpha, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, bits=bits),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, m), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_B, d), lambda i: (i, 0)),
            pl.BlockSpec((TILE_B, m), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),   # revisited: accumulates
            pl.BlockSpec((1, d), lambda i: (0, 0)),   # revisited: accumulates
        ],
        out_shape=[
            jax.ShapeDtypeStruct(rows_p.shape, jnp.float32),
            jax.ShapeDtypeStruct(probs_p.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(rows_p, probs_p, alpha.reshape(1, m), beta.reshape(1, d), g_p)
    return drows[:b0], dprobs[:b0], dalpha.reshape(m), dbeta.reshape(d)
