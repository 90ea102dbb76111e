"""EmbeddingBag — JAX has no native nn.EmbeddingBag; this IS the system.

Multi-hot bags are represented padded: ids (B, L) with a validity mask
(B, L). ``embedding_bag`` gathers rows and segment-reduces per bag. For
mixed-precision tables the gather is replaced by the compressor's lookup —
the reduce stays identical, so the bag composes with every compression method.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray, mask: jnp.ndarray | None = None,
                  *, combine: str = "sum") -> jnp.ndarray:
    """table: (n, d); ids: (B, L); mask: (B, L) bool -> (B, d)."""
    rows = jnp.take(table, ids, axis=0)                    # (B, L, d)
    return reduce_bag(rows, mask, combine=combine)


def reduce_bag(rows: jnp.ndarray, mask: jnp.ndarray | None, *, combine: str = "sum"):
    """rows: (B, L, d) already-gathered (possibly dequantized) embeddings."""
    if mask is not None:
        rows = rows * mask[..., None].astype(rows.dtype)
    if combine == "sum":
        return jnp.sum(rows, axis=-2)
    if combine == "mean":
        denom = (jnp.sum(mask, axis=-1, keepdims=True).astype(rows.dtype)
                 if mask is not None else rows.shape[-2])
        return jnp.sum(rows, axis=-2) / jnp.maximum(denom, 1.0)
    if combine == "max":
        neg = jnp.finfo(rows.dtype).min
        if mask is not None:
            rows = jnp.where(mask[..., None], rows, neg)
        return jnp.max(rows, axis=-2)
    raise ValueError(f"unknown combine {combine}")


def pool_fields(rows: jnp.ndarray, sizes) -> jnp.ndarray:
    """Sum pooling of fixed-size bags laid side by side: rows (B, ΣL_f, d),
    field f's ``sizes[f]`` slots in order -> (B, F, d). Each field is summed
    over its own slots, with no padding to the longest bag."""
    out, s = [], 0
    for n in sizes:
        out.append(rows[:, s] if n == 1 else jnp.sum(rows[:, s:s + n], axis=1))
        s += n
    return jnp.stack(out, axis=1)


def ragged_embedding_bag(table: jnp.ndarray, flat_ids: jnp.ndarray,
                         segment_ids: jnp.ndarray, num_bags: int,
                         *, combine: str = "sum") -> jnp.ndarray:
    """True ragged form: flat_ids (N,), segment_ids (N,) -> (num_bags, d).

    Used by the GNN message-passing path and by the data loader when bags are
    CSR-encoded; segment_sum is the TPU-native scatter-reduce.
    """
    rows = jnp.take(table, flat_ids, axis=0)               # (N, d)
    if combine == "sum":
        return jax.ops.segment_sum(rows, segment_ids, num_segments=num_bags)
    if combine == "mean":
        s = jax.ops.segment_sum(rows, segment_ids, num_segments=num_bags)
        c = jax.ops.segment_sum(jnp.ones((rows.shape[0], 1), rows.dtype),
                                segment_ids, num_segments=num_bags)
        return s / jnp.maximum(c, 1.0)
    if combine == "max":
        return jax.ops.segment_max(rows, segment_ids, num_segments=num_bags)
    raise ValueError(f"unknown combine {combine}")


def segment_mean(data: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int):
    s = jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)
    c = jax.ops.segment_sum(jnp.ones_like(data[..., :1]), segment_ids,
                            num_segments=num_segments)
    return s / jnp.maximum(c, 1.0)
