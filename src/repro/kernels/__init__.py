"""Pallas TPU kernels for the paper's embedding hot paths.

Each kernel package ships:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (+ custom_vjp where trainable); the
              packed lookup's wrapper is ``core.inference`` itself
  ref.py    — pure-jnp oracle; tests assert_allclose against it

Interpret mode follows the backend (``backend.resolve_interpret``): Mosaic
on a TPU, the Pallas interpreter elsewhere. Every block keeps its last two
dims (8, 128)-divisible or equal to the array's, as Mosaic requires;
``tests/test_tpu_compile.py`` compiles each kernel for a described v5e.
"""
from repro.kernels.mpe_qat.ops import mixed_expectation_kernel
from repro.kernels.embedding_bag.ops import embedding_bag_kernel
from repro.kernels.flash_attention.ops import flash_attention_kernel

__all__ = ["mixed_expectation_kernel", "embedding_bag_kernel",
           "flash_attention_kernel"]
