"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces 512).
"""
from __future__ import annotations

from repro.dist.mesh import make_device_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_device_mesh(shape, axes)


# v5e hardware constants for the roofline terms (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM; ICI per-link bandwidth)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # B/s per chip
ICI_BW = 50e9                   # B/s per link
