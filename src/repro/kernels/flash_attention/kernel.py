"""Flash-style fused causal attention forward (TPU Pallas).

The §Perf log (EXPERIMENTS.md, qwen cell) showed the remaining LM-train memory
term is the chunked-softmax score blocks crossing HBM between XLA fusions.
This kernel keeps them in VMEM: grid = (batch·heads, q_blocks, kv_blocks) with
the kv dimension innermost; the running (max, denom, accumulator) live in VMEM
scratch across the kv sweep and only the final normalized (BQ, hd) output
block is written — one HBM write per q block, zero score-block traffic.

BlockSpec geometry (v5e): q/o blocks (BQ=128, hd) and kv blocks (BK=128, hd)
are MXU-aligned for hd ∈ {64, 128}; VMEM per step ≈
(2·BQ·hd + 2·BK·hd + BQ·BK)·4 B ≤ 0.4 MiB — far under the ~16 MiB budget, so
the automatic double-buffering pipeline overlaps the next KV DMA with compute.

Causality is block-granular: fully-masked blocks contribute nothing (compute
skipped via pl.when), the diagonal block applies the element mask — the
causal-block-skipping optimization the chunked jnp path can't express.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, d_ref, *,
                  bq: int, bk: int, causal: bool, scale: float, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    run = ((ki * bk) <= (qi * bq + bq - 1)) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0]                      # (BQ, hd)
        k = k_ref[0]                      # (BK, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(d_ref[...], 1e-30)).astype(o_ref.dtype)


def _flash_fwd_stats_kernel(q_ref, k_ref, v_ref, o_ref, l_ref,
                            acc_ref, m_ref, d_ref, *,
                            bq: int, bk: int, causal: bool, scale: float,
                            n_kv: int):
    """Forward that also emits the logsumexp rows (for the backward)."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, d_ref,
                  bq=bq, bk=bk, causal=causal, scale=scale, n_kv=n_kv)
    ki = pl.program_id(2)

    @pl.when(ki == n_kv - 1)
    def _emit_lse():
        l_ref[0] = (m_ref[...] +
                    jnp.log(jnp.maximum(d_ref[...], 1e-30)))[:, 0]


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, bq: int, bk: int,
                      causal: bool, scale: float, n_q: int):
    """Backward over the same tiling: grid (BH, kv_blocks, q_blocks).

    Recomputes p from (q, k, lse) blockwise — no stored score tensors.
    q_blocks is the inner sweep, so each dk/dv block stays VMEM-resident and
    accumulates consecutively; dq blocks are revisited once per kv block
    (re-fetched, read-modify-write) and initialized on the first kv block.
    """
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    @pl.when(ki == 0)
    def _init_dq():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    run = ((ki * bk) <= (qi * bq + bq - 1)) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, None]                 # (BQ, 1)
        delta = delta_ref[0][:, None]             # (BQ, 1) = rowsum(do*o)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                      # exact softmax via stored lse
        dv_ref[0] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32
                                         ).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_ref[0] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32
                                         ).astype(dk_ref.dtype)
        dq_ref[0] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32
                                         ).astype(dq_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_fwd_stats(q, k, v, *, causal: bool = True, bq: int = 128,
                              bk: int = 128, interpret: bool | None = None):
    """Forward returning (o, lse) — the residuals the backward needs."""
    bh, s, hd = q.shape
    bq, bk = min(bq, s), min(bk, s)
    n_q, n_kv = s // bq, s // bk
    kern = functools.partial(_flash_fwd_stats_kernel, bq=bq, bk=bk,
                             causal=causal, scale=hd ** -0.5, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        bq: int = 128, bk: int = 128, interpret: bool | None = None):
    """-> (dq, dk, dv). delta = rowsum(do ⊙ o) computed outside (cheap)."""
    bh, s, hd = q.shape
    bq, bk = min(bq, s), min(bk, s)
    n_q, n_kv = s // bq, s // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    kern = functools.partial(_flash_bwd_kernel, bq=bq, bk=bk, causal=causal,
                             scale=hd ** -0.5, n_q=n_q)
    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(bh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            # dq revisited across the kv sweep (j) — accumulates
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, bq: int = 128,
                           bk: int = 128, interpret: bool | None = None):
    """q,k,v: (BH, S, hd) flattened batch·heads -> (BH, S, hd)."""
    bh, s, hd = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_q, n_kv = s // bq, s // bk
    scale = hd ** -0.5
    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                             scale=scale, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # running accumulator
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
