"""Keys from the seed: a seed of any size and a salt give a 32-bit key word
(the lowbias32 integer finalizer, twice), and from it a ``jax.random`` key,
so one seed gives the same weights on every machine.
"""
from __future__ import annotations

import jax

_M1, _M2 = 0x7FEB352D, 0x846CA68B
_G1, _G2 = 0x9E3779B1, 0x85EBCA77
_MASK = 0xFFFFFFFF


def _mix32_int(x: int) -> int:
    x &= _MASK
    x ^= x >> 16
    x = (x * _M1) & _MASK
    x ^= x >> 15
    x = (x * _M2) & _MASK
    x ^= x >> 16
    return x


def key_word(seed: int, salt: int) -> int:
    """A 32-bit key word from a seed of any size and a salt."""
    seed = int(seed)
    lo, hi = seed & _MASK, (seed >> 32) & _MASK
    return _mix32_int(_mix32_int(lo ^ (salt * _G2)) ^ (hi + _G1 + salt))


def prng_key(seed: int, salt: int):
    """A ``jax.random`` key for seeded arrays (tables, towers)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(key_word(seed, salt) & 0x7FFFFFFF), salt)
