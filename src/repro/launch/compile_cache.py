"""Persistent XLA compilation cache at a fixed place.

The path is part of what the cache is keyed on, so a directory that moves
between runs never hits. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax
reads it itself and nothing is configured in code. Otherwise the cache lives
in ``<checkout>/.jax_cache`` — never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
