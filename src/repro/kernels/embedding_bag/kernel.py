"""Fused multi-hot embedding bag: gather + masked segment-sum in one pass.

JAX has no nn.EmbeddingBag; the jnp formulation materializes the (B, L, d)
gathered tensor in HBM before reducing. This kernel never does: the grid is
(B, L) with L innermost, each step DMAs one table row (scalar-prefetched id)
into VMEM and accumulates into the bag's (1, d) output block, which Pallas
keeps resident across the L revisits. HBM traffic drops from
B·L·d·(read+write) + B·d to B·L·d reads + B·d writes — and the row DMA for
(i, j+1) overlaps the accumulate of (i, j) via the automatic pipeline.

Mosaic only accepts a block whose last two dims are (8, 128)-divisible or
equal to the array's, so the table enters as ``(N, 1, d)`` and the bags leave
as ``(B, 1, d)``; the per-slot mask rides in SMEM next to the ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _bag_kernel(idx_ref, mask_ref, row_ref, out_ref, *, l: int):
    del idx_ref
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = row_ref[...]
    out_ref[...] += jnp.where(mask_ref[i * l + j] != 0, row,
                              jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_pallas(table: jnp.ndarray, ids: jnp.ndarray,
                         mask: jnp.ndarray, *,
                         interpret: bool | None = None):
    """table: (N, d); ids, mask: (B, L) -> (B, d) masked sum per bag.
    ``interpret`` defaults to the backend's mode (``resolve_interpret``)."""
    bsz, l = ids.shape
    n, d = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, l),
        in_specs=[
            pl.BlockSpec((None, 1, d),
                         lambda i, j, idx_ref, m_ref: (idx_ref[i * l + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d),
                               lambda i, j, idx_ref, m_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_bag_kernel, l=l), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 1, d), table.dtype),
        interpret=resolve_interpret(interpret),
    )(ids.reshape(-1).astype(jnp.int32), mask.reshape(-1).astype(jnp.int32),
      table.reshape(n, 1, d))
    return out.reshape(bsz, d)
