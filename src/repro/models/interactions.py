"""Feature-interaction operators for the DLRM backbones (paper §5.1.2).

DNN = MLP only; DCN adds a cross network [arXiv:1708.05123]; DeepFM adds a
factorization machine [Rendle ICDM'10]; IPNN adds an inner-product layer
[arXiv:1611.00144]; DCN-v2 stacks low-rank cross layers [arXiv:2008.13535]
under the MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn import init as initializers


def fm_second_order(emb: jnp.ndarray) -> jnp.ndarray:
    """emb: (B, F, d) -> (B,) FM 2nd-order term: ½Σ_d[(Σ_f v)² − Σ_f v²]."""
    sum_sq = jnp.square(jnp.sum(emb, axis=1))
    sq_sum = jnp.sum(jnp.square(emb), axis=1)
    return 0.5 * jnp.sum(sum_sq - sq_sum, axis=-1)


def inner_products(emb: jnp.ndarray) -> jnp.ndarray:
    """emb: (B, F, d) -> (B, F(F-1)/2) pairwise inner products (IPNN)."""
    f = emb.shape[1]
    gram = jnp.einsum("bfd,bgd->bfg", emb, emb)
    iu, ju = jnp.triu_indices(f, k=1)
    return gram[:, iu, ju]


class CrossNetwork:
    """DCN-v1 cross layers: x_{l+1} = x0 ⊙ (x_l·w_l) + b_l + x_l."""

    @staticmethod
    def init(key, dim: int, n_layers: int = 3, dtype=jnp.float32):
        keys = jax.random.split(key, n_layers)
        return {
            "w": [initializers.normal(keys[i], (dim,), std=0.01, dtype=dtype)
                  for i in range(n_layers)],
            "b": [jnp.zeros((dim,), dtype) for _ in range(n_layers)],
        }

    @staticmethod
    def apply(params, x0: jnp.ndarray) -> jnp.ndarray:
        x = x0
        for w, b in zip(params["w"], params["b"]):
            x = x0 * (x @ w)[:, None] + b + x
        return x


class LowRankCrossNet:
    """DCN-v2 cross layers in low-rank form (arXiv:2008.13535, Eq. 1 with
    the factorization of §5), as MLPerf's DLRM-DCNv2 runs them:
    x_{l+1} = x0 ⊙ (W_l (V_l x_l) + b_l) + x_l, with V_l: r×d (no bias) and
    W_l: d×r. Kernels are stored for ``x @ kernel``: ``v`` (d, r), ``w``
    (r, d)."""

    @staticmethod
    def init(key, dim: int, rank: int, n_layers: int = 3,
             dtype=jnp.float32):
        keys = jax.random.split(key, 2 * n_layers)
        return {
            "v": [initializers.glorot_uniform(keys[2 * i], (dim, rank), dtype)
                  for i in range(n_layers)],
            "w": [initializers.glorot_uniform(keys[2 * i + 1], (rank, dim),
                                              dtype)
                  for i in range(n_layers)],
            "b": [jnp.zeros((dim,), dtype) for _ in range(n_layers)],
        }

    @staticmethod
    def apply(params, x0: jnp.ndarray) -> jnp.ndarray:
        x = x0
        for v, w, b in zip(params["v"], params["w"], params["b"]):
            x = x0 * ((x @ v) @ w + b) + x
        return x
