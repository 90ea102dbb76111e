"""jit'd wrapper with custom_vjp: backward is the (sparse) scatter of the bag
cotangent into the touched rows — expressed with segment_sum (itself the
TPU-native scatter) since the kernel's forward never materializes (B, L, d)."""
from __future__ import annotations

import jax

from repro.kernels.embedding_bag.kernel import embedding_bag_pallas


@jax.custom_vjp
def embedding_bag_kernel(table, ids, mask):
    return embedding_bag_pallas(table, ids, mask)


def _fwd(table, ids, mask):
    out = embedding_bag_pallas(table, ids, mask)
    return out, (table.shape, ids, mask)


def _bwd(res, g):
    table_shape, ids, mask = res
    b, l = ids.shape
    # d_table[row] += mask * g[bag] for every (bag, slot) pointing at row
    flat_ids = ids.reshape(-1)
    contrib = (g[:, None, :] * mask[..., None].astype(g.dtype)).reshape(b * l, -1)
    d_table = jax.ops.segment_sum(contrib, flat_ids,
                                  num_segments=table_shape[0])
    return d_table.astype(g.dtype), None, None


embedding_bag_kernel.defvjp(_fwd, _bwd)


def embedding_bag_kernel_sharded(table, ids, mask, *, rows_axes=("model",),
                                 mesh=None):
    """Differentiable bag under ``shard_map``: table rows over ``rows_axes``,
    bags over the data axes, partial sums psum-merged; the backward pass is
    a ``custom_vjp`` that segment-sums each device's owned cotangent rows
    locally (no dense-gradient collective over the row axis). Tolerance
    ~1e-6 vs the single-device kernel when the rows really split (the psum
    reassociates the bag sum — pinned by
    ``tests/test_shard_a2a.py::test_embedding_bag_psum_tolerance``); falls
    back to the kernel when no multi-device mesh is active (see
    ``repro.dist.shard``)."""
    from repro.dist.shard import sharded_embedding_bag
    return sharded_embedding_bag(table, ids, mask, rows_axes=rows_axes,
                                 mesh=mesh)
