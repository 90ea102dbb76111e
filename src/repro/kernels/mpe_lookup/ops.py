"""Public wrapper: full mixed-precision table lookup through the Pallas path.

Composes the per-width bucket kernels exactly like
``repro.core.inference.packed_lookup`` composes the jnp reference: gather each
bucket's rows with the static-width kernel, then select by the row's width.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.mpe_lookup.kernel import packed_lookup_pallas


def packed_lookup_kernel_sharded(table, meta, ids: jnp.ndarray, *,
                                 rows_axes=("model",), mesh=None,
                                 lookup_comms: str = "psum",
                                 bucket_capacity: int | None = None
                                 ) -> jnp.ndarray:
    """The fused lookup under ``shard_map``: subtables row-sharded over
    ``rows_axes`` of the active mesh, the per-bucket Pallas kernel gathering
    device-locally, one psum merging buckets — or, with
    ``lookup_comms="a2a"``, the capacity-bucketed all-to-all id shuffle that
    ships packed words instead of dequantized partials (bit-exact either
    way). Falls back to the single-device kernel path when no multi-device
    mesh is active (see ``repro.dist.shard``)."""
    from repro.dist.shard import sharded_packed_lookup
    return sharded_packed_lookup(table, meta, ids, rows_axes=rows_axes,
                                 mesh=mesh, use_kernel=True,
                                 lookup_comms=lookup_comms,
                                 bucket_capacity=bucket_capacity)


def packed_lookup_kernel(table, meta, ids: jnp.ndarray) -> jnp.ndarray:
    bits = meta["bits"]
    d = meta["d"]
    flat = ids.reshape(-1)
    widx = jnp.take(table["width_idx"], flat, axis=0)
    lidx = jnp.take(table["local_idx"], flat, axis=0)
    out = jnp.zeros((flat.shape[0], d), jnp.float32)
    for i, b in enumerate(bits):
        if b == 0:
            continue
        sub = table["subtables"][f"b{b}"]
        deq = packed_lookup_pallas(jnp.clip(lidx, 0, sub.shape[0] - 1), sub,
                                   table["alpha"][i], table["beta"],
                                   b=b, d=d)
        out = jnp.where((widx == i)[:, None], deq, out)
    return out.reshape(*ids.shape, d)
