"""Public wrapper: multi-head causal attention through the flash kernels.

Accepts (B, S, H, hd) (GQA handled by pre-expanding KV, as the §Perf-tuned
chunked path does) and flattens to the kernels' (B·H, S, hd) layout. Fully
differentiable: custom_vjp runs the fused backward kernel (blockwise p
recomputation from the stored logsumexp — no score tensors in HBM in either
direction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd_stats,
                                                  flash_attention_pallas)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_flat(q, k, v, causal, bq, bk):
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk)


def _flash_flat_fwd(q, k, v, causal, bq, bk):
    o, lse = flash_attention_fwd_stats(q, k, v, causal=causal, bq=bq, bk=bk)
    return o, (q, k, v, o, lse)


def _flash_flat_bwd(causal, bq, bk, res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal, bq=bq,
                               bk=bk)


_flash_flat.defvjp(_flash_flat_fwd, _flash_flat_bwd)


def flash_attention_kernel(q, k, v, *, n_kv_heads: int | None = None,
                           causal: bool = True, bq: int = 128, bk: int = 128):
    """q: (B, S, Hq, hd); k,v: (B, S, Hkv, hd) -> (B, S, Hq, hd)."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    if hkv != hq:  # GQA: expand KV to query heads
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * hq, s, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * hq, s, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * hq, s, hd)
    of = _flash_flat(qf, kf, vf, causal, min(bq, s), min(bk, s))
    return jnp.moveaxis(of.reshape(b, hq, s, hd), 1, 2)

