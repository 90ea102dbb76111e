"""MPE search-phase embedding layer (paper §3.2–§3.3).

Holds the full-precision table, per-group bit-width logits γ, per-width step
sizes α and per-dimension offsets β. Lookup returns the expectation over
candidate quantizers (Eq. 9); ``reg_loss`` is the frequency-weighted expected
bit-width (Eq. 10, second term, without λ).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantizer
from repro.nn import init as initializers

#: a TPU vector register's lane count: rows this wide or a multiple of it
#: fill the fused Eq. 9 kernel's lanes
LANES = 128


class MPEConfig(NamedTuple):
    bits: tuple = (0, 1, 2, 3, 4, 5, 6)  # paper §5.1.5
    group_size: int = 128                # paper §5.1.5
    tau: float = 3e-3                    # paper §5.1.5
    lam: float = 1e-5                    # swept in {1e-6 .. 3e-4} (paper)
    embed_std: float = initializers.EMBED_STD


def make_groups(freqs: np.ndarray, group_size: int):
    """Frequency-aware grouping (§3.2).

    Sort features by frequency (desc), split into groups of ``group_size``.
    Returns (group_of_feature (n,) int32, freq_sum_per_group (g,) float32).
    """
    freqs = np.asarray(freqs, np.float64)
    n = freqs.shape[0]
    order = np.argsort(-freqs, kind="stable")
    g = -(-n // group_size)
    group_of_rank = np.arange(n) // group_size
    group_of_feature = np.empty((n,), np.int32)
    group_of_feature[order] = group_of_rank.astype(np.int32)
    sums = np.zeros((g,), np.float64)
    np.add.at(sums, group_of_feature, freqs)
    return jnp.asarray(group_of_feature), jnp.asarray(np.maximum(sums, 1.0), dtype=jnp.float32)


class MPESearchEmbedding:
    """Functional module. ``buffers`` are non-trained constants."""

    @staticmethod
    def init(key, n: int, d: int, freqs, cfg: MPEConfig):
        m = len(cfg.bits)
        group_of_feature, freq_sum = make_groups(np.asarray(freqs), cfg.group_size)
        g = int(freq_sum.shape[0])
        emb = initializers.normal(key, (n, d), std=cfg.embed_std)
        params = {
            "emb": emb,
            # all-zero init => uniform distribution over candidate widths (§3.3)
            "gamma": jnp.zeros((g, m), jnp.float32),
            "alpha": jnp.asarray([quantizer.init_alpha(cfg.embed_std, b) for b in cfg.bits],
                                 jnp.float32),
            "beta": jnp.zeros((d,), jnp.float32),
        }
        buffers = {"group_of_feature": group_of_feature, "freq_sum": freq_sum}
        return params, buffers

    @staticmethod
    def probabilities(params, cfg: MPEConfig) -> jnp.ndarray:
        """(g, m) softmax(γ/τ) — Eq. (8)."""
        return jax.nn.softmax(params["gamma"] / cfg.tau, axis=-1)

    @staticmethod
    def lookup(params, buffers, ids: jnp.ndarray, cfg: MPEConfig) -> jnp.ndarray:
        """ids: int32 of any shape -> (*ids.shape, d) mixed-precision embeddings.

        Named scopes tag the device ops: ``embed_gather`` (the row, group and
        probability gathers; under ``transpose(...)`` the table gradient's
        zero-fill and scatter-add) and ``embed_quantize`` (Eq. 9 and its
        straight-through backward).

        Eq. 9 runs as the fused ``kernels.mpe_qat`` pair (one pass each
        way, row-parallel over an active mesh) when the rows are lane-dense
        (d a multiple of 128); narrower rows, which would fill a fraction of
        the kernel's lanes, keep ``quantizer.mixed_expectation``."""
        from repro.dist.sharding import shard_batch_dim
        with jax.named_scope("embed_gather"):
            rows = jnp.take(params["emb"], ids, axis=0)
            # §Perf: keep gathered rows batch-sharded — without the pin, GSPMD
            # may replicate the (B, F, d) gather output to every device
            # (EXPERIMENTS.md §Perf wide-deep it1). No-op outside a mesh.
            rows = shard_batch_dim(rows)
            p = MPESearchEmbedding.probabilities(params, cfg)    # (g, m)
            gid = jnp.take(buffers["group_of_feature"], ids, axis=0)
            probs = jnp.take(p, gid, axis=0)                      # (*ids, m)
            probs = shard_batch_dim(probs)
        with jax.named_scope("embed_quantize"):
            d = rows.shape[-1]
            if d % LANES:
                return quantizer.mixed_expectation(
                    rows, probs, params["alpha"], params["beta"], cfg.bits)
            from repro.dist.shard import sharded_mixed_expectation
            out = sharded_mixed_expectation(
                rows.reshape(-1, d), probs.reshape(-1, probs.shape[-1]),
                params["alpha"], params["beta"], tuple(cfg.bits))
            return out.reshape(rows.shape)

    @staticmethod
    def reg_loss(params, buffers, cfg: MPEConfig) -> jnp.ndarray:
        """Eq. (10): Σ_j (1/s_j) Σ_i b_i p_i^j  (caller multiplies by λ)."""
        with jax.named_scope("embed_quantize"):
            p = MPESearchEmbedding.probabilities(params, cfg)     # (g, m)
            bits = jnp.asarray(cfg.bits, jnp.float32)
            per_group = p @ bits                                  # (g,)
            return jnp.sum(per_group / buffers["freq_sum"])

    @staticmethod
    def expected_bits(params, buffers, cfg: MPEConfig) -> jnp.ndarray:
        """Average expected bit-width over features (monitoring/compression)."""
        p = MPESearchEmbedding.probabilities(params, cfg)
        bits = jnp.asarray(cfg.bits, jnp.float32)
        per_group = p @ bits                                      # (g,)
        return jnp.mean(jnp.take(per_group, buffers["group_of_feature"]))
