"""Fused packed-embedding gather → bit-unpack → dequantize (paper §4).

One pallas_call per width bucket (the bit-width ``b`` is a compile-time
constant — buckets are static after sampling). The row index for each grid
step comes from scalar-prefetched ids, so the packed row's DMA is issued ahead
of compute (Pallas double-buffers the row blocks automatically); unpack is
shift/mask arithmetic on 32-bit lanes, dequant an FMA with the per-width step
size and per-dimension offset, all in VMEM.

Mosaic only accepts a block whose last two dims are (8, 128)-divisible or
equal to the array's, so the packed words enter as ``(N, 1, W)`` and the
output leaves as ``(B, 1, d)``: each grid step moves one ``(1, W)`` /
``(1, d)`` slab whose trailing dims equal the array's.

The unpack avoids in-kernel gathers (TPU lanes dislike them): each of the ≤12
packed words is broadcast against a (1, d) iota of bit offsets and the right
word is chosen with a select — a (W, d) mask-reduce that vectorizes on the
8×128 VPU. Captured constants are avoided (Pallas requirement); everything is
built from broadcasted_iota.

HBM traffic per row is ceil(d·b/32)·4 bytes instead of d·4 — the packed table
is the roofline win (memory-bound lookup: 32/b× fewer bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantizer import int_bounds
from repro.kernels.backend import resolve_interpret


def _unpack_block(words, *, b: int, d: int, w: int):
    """words: (1, W) uint32 -> (1, d) int32 signed codes. No gathers.

    The words are reinterpreted as int32 (Mosaic reduces signed integers
    only) and shifted logically, so every bit pattern is the uint32 one; the
    masked sums add exactly one non-zero word each, so they cannot wrap."""
    bitpos = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1) * b      # (1, d)
    w0 = bitpos // 32                                                # (1, d)
    off = bitpos % 32
    straddle = off + b > 32
    shift_hi = jnp.clip(32 - off, 0, 31)
    w1 = jnp.minimum(w0 + 1, w - 1)

    word_ids = jax.lax.broadcasted_iota(jnp.int32, (w, d), 0)        # (W, d)
    signed = jax.lax.bitcast_convert_type(words, jnp.int32)
    wcol = jnp.broadcast_to(signed.reshape(w, 1), (w, d))            # (W, d)
    lo_all = jax.lax.shift_right_logical(wcol, jnp.broadcast_to(off, (w, d)))
    hi_all = jax.lax.shift_left(wcol, jnp.broadcast_to(shift_hi, (w, d)))
    zero = jnp.zeros((w, d), jnp.int32)
    lo = jnp.sum(jnp.where(word_ids == jnp.broadcast_to(w0, (w, d)),
                           lo_all, zero), axis=0, keepdims=True)     # (1, d)
    hi = jnp.sum(jnp.where(word_ids == jnp.broadcast_to(w1, (w, d)),
                           hi_all, zero), axis=0, keepdims=True)
    n_b, _ = int_bounds(b)
    u = jnp.where(straddle, lo | hi, lo) & ((1 << b) - 1)
    return u + n_b


def _lookup_kernel(idx_ref, words_ref, alpha_ref, beta_ref, out_ref, *,
                   b: int, d: int, w: int):
    del idx_ref  # consumed by the BlockSpec index_map
    codes = _unpack_block(words_ref[...], b=b, d=d, w=w)
    out_ref[...] = alpha_ref[0, 0] * codes.astype(jnp.float32) + beta_ref[...]


@functools.partial(jax.jit, static_argnames=("b", "d", "interpret"))
def packed_lookup_pallas(ids: jnp.ndarray, words: jnp.ndarray,
                         alpha: jnp.ndarray, beta: jnp.ndarray, *,
                         b: int, d: int,
                         interpret: bool | None = None) -> jnp.ndarray:
    """ids: (B,) rows into the packed subtable ``words`` (N, W) -> (B, d).
    ``interpret`` defaults to the backend's mode (``resolve_interpret``)."""
    n_rows, w = words.shape
    bsz = ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((None, 1, w), lambda i, idx_ref: (idx_ref[i], 0, 0)),
            pl.BlockSpec((1, 1), lambda i, idx_ref: (0, 0)),
            pl.BlockSpec((1, d), lambda i, idx_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, idx_ref: (i, 0, 0)),
    )
    kern = functools.partial(_lookup_kernel, b=b, d=d, w=w)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 1, d), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(ids.astype(jnp.int32), words.reshape(n_rows, 1, w), alpha.reshape(1, 1),
      beta.reshape(1, d))
    return out.reshape(bsz, d)
