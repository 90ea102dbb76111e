"""Pallas TPU kernels for the paper's embedding hot paths.

Each kernel package ships:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (+ custom_vjp where trainable)
  ref.py    — pure-jnp oracle; tests assert_allclose against it

Interpret mode follows the backend (``backend.resolve_interpret``): Mosaic
on a TPU, the Pallas interpreter elsewhere. Every block keeps its last two
dims (8, 128)-divisible or equal to the array's, as Mosaic requires;
``tests/test_tpu_compile.py`` compiles each kernel for a described v5e.
"""
from repro.kernels.mpe_lookup.ops import (packed_lookup_kernel,
                                           packed_lookup_kernel_sharded)
from repro.kernels.mpe_qat.ops import (mixed_expectation_kernel,
                                        mixed_expectation_kernel_sharded)
from repro.kernels.embedding_bag.ops import (embedding_bag_kernel,
                                             embedding_bag_kernel_sharded)
from repro.kernels.flash_attention.ops import (flash_attention_kernel,
                                               flash_attention_kernel_sharded)

__all__ = ["packed_lookup_kernel", "mixed_expectation_kernel",
           "embedding_bag_kernel", "flash_attention_kernel",
           "packed_lookup_kernel_sharded", "mixed_expectation_kernel_sharded",
           "embedding_bag_kernel_sharded", "flash_attention_kernel_sharded"]
