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

