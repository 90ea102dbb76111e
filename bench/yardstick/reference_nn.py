"""Plain jax.numpy towers for the references, in three precisions.

- ``f32``: float32 throughout, products at ``Precision.HIGHEST``.
- ``stated``: what the configurations state. Activations, weights and
  every elementwise step in float32; each matrix product rounds its two
  operands to bfloat16 and accumulates the exact products in float32. That
  is the TPU's default precision for a float32 product (one bfloat16 pass
  of the matrix unit), written out: the operands are rounded here and
  multiplied at ``HIGHEST``, which is exact for bfloat16 values. Backward
  products round their operands the same way. The logit head, a product
  into one column, stays float32: XLA lowers it on the TPU to a multiply
  and a reduction on the vector unit, not to the matrix unit.
- ``bf16``: the control, one step below: every array and every operation
  in bfloat16.

A tower is ``{"layers": [(w, b), ...], "bn": [(scale, bias), ...],
"head": (w, b)}``; each layer is dense → batch norm → ReLU, the head is
dense. Batch norm matches the configurations' recipe: epsilon 1e-5, in
training mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5


def _r(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _mm_stated(x, w):
    return jnp.dot(_r(x), _r(w), precision=HI)


def _mm_fwd(x, w):
    return _mm_stated(x, w), (x, w)


def _mm_bwd(res, g):
    x, w = res
    return (jnp.dot(_r(g), _r(w).T, precision=HI),
            jnp.dot(_r(x).T, _r(g), precision=HI))


_mm_stated.defvjp(_mm_fwd, _mm_bwd)


def mm(x, w, mode: str):
    if mode == "stated":
        return _mm_stated(x, w)
    if mode == "bf16":
        return jnp.dot(x, w, preferred_element_type=jnp.bfloat16)
    return jnp.dot(x, w, precision=HI)


def dtype_of(mode: str):
    return jnp.bfloat16 if mode == "bf16" else jnp.float32


def cast(tree, mode: str):
    dt = dtype_of(mode)
    return jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def tower(params, x, mode: str):
    """Logits (B,) of rows ``x`` (B, K), batch norm on the batch's own
    statistics (training mode)."""
    for (w, b), (scale, bias) in zip(params["layers"], params["bn"]):
        x = mm(x, w, mode) + b
        mean, var = jnp.mean(x, axis=0), jnp.var(x, axis=0)
        x = (x - mean) / jnp.sqrt(var + BN_EPS) * scale + bias
        x = jnp.maximum(x, 0)
    w, b = params["head"]
    return (mm(x, w, "f32" if mode == "stated" else mode) + b)[:, 0]


def init_tower(key, d_in: int, hidden):
    """The training recipe's start: Glorot-uniform kernels, zero biases,
    unit batch-norm scale; and the running statistics the program's state
    starts from (mean 0, variance 1)."""
    dims = [d_in, *hidden, 1]
    keys = jax.random.split(key, len(dims))
    layers, bn, running = [], [], []
    for i, (a, c) in enumerate(zip(dims[:-1], dims[1:])):
        lim = jnp.sqrt(6.0 / (a + c))
        w = jax.random.uniform(keys[i], (a, c), minval=-lim, maxval=lim)
        b = jnp.zeros((c,))
        if i == len(dims) - 2:
            head = (w, b)
            break
        layers.append((w, b))
        bn.append((jnp.ones((c,)), jnp.zeros((c,))))
        running.append((jnp.zeros((c,)), jnp.ones((c,))))
    return {"layers": layers, "bn": bn, "head": head}, running
