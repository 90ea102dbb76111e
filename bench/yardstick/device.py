"""The chip: refusal without one, the compile cache, memory readings.

``use_compile_cache`` must run before JAX compiles anything. The cache
lives at ``<checkout>/.jax_cache``, a fixed path inside the checkout (the
path is part of the cache key, so a directory that moves never hits). The
benchmark sets it over any ``JAX_COMPILATION_CACHE_DIR`` it inherits, so
two checkouts never share a cache.
"""
from __future__ import annotations

import gc
import os

from yardstick.spec import CHECKOUT

CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def use_compile_cache(path: str = CACHE_DIR) -> str:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    # every program of a cell is cached, however small or quick to compile,
    # so that the second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n: int, platform: str = "tpu"):
    """The first ``n`` devices, or ``NoChip``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoChip(f"needs a {platform.upper()}; JAX found platform "
                     f"{devices[0].platform!r} ({devices[0].device_kind}, "
                     f"{len(devices)} device(s))")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory(devices, key: str) -> int | None:
    """``memory_stats()[key]`` on the fullest chip, or None where the
    backend reports none."""
    vals = [(dev.memory_stats() or {}).get(key) for dev in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def settle():
    """Drop what Python still holds of freed device buffers."""
    gc.collect()
