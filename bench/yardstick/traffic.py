"""Seeded traffic: per-field Zipf id pools.

One seed gives one pool. The id sampler is the exact truncated discrete
Zipf of the program's ``data/synthetic.SyntheticCTR`` (inverse CDF over
each field's popularity ranks), copied here so that the yardstick does not
move with the program.
"""
from __future__ import annotations

import functools

import numpy as np


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


@functools.lru_cache(maxsize=64)
def _zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    """The popularity CDF of one vocabulary size (read-only)."""
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** (-exponent))
    cdf /= cdf[-1]
    cdf.flags.writeable = False
    return cdf


def zipf_ids(vocab: int, exponent: float, n: int,
             gen: np.random.Generator) -> np.ndarray:
    """``n`` iid popularity ranks in ``[0, vocab)``, P(k) ∝ (k+1)^-s.
    The uniforms are searched in sorted order (cache-friendly) and the
    draws put back in a random order."""
    u = np.sort(gen.random(n))
    ranks = np.searchsorted(_zipf_cdf(int(vocab), float(exponent)), u,
                            side="right")
    ranks = np.minimum(ranks, vocab - 1).astype(np.int32)
    return ranks[gen.permutation(n)]


def id_pool(field_vocabs, exponent: float, n_rows: int, seed: int,
            ) -> np.ndarray:
    """(n_rows, F) int32 per-field local ids."""
    out = np.empty((n_rows, len(field_vocabs)), np.int32)
    for f, v in enumerate(field_vocabs):
        out[:, f] = zipf_ids(v, exponent, n_rows, rng(seed, 1, f))
    return out
