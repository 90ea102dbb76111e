"""Optimizer semantics: Adam trajectory, bf16 moments, clipping, schedules,
the ``ok`` guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.optimizer import (adam, apply_updates, clip_by_global_norm,
                                   sgd)


def _run(opt, steps=60, dim=8):
    """Minimize ||x - t||² from a fixed start; returns final distance."""
    t = jnp.arange(1.0, dim + 1)
    params = {"x": jnp.zeros((dim,))}
    state = opt.init(params)
    for _ in range(steps):
        grads = {"x": 2 * (params["x"] - t)}
        updates, state = opt.update(grads, state, params)
        params = apply_updates(params, updates)
    return float(jnp.max(jnp.abs(params["x"] - t)))


def test_adam_converges():
    assert _run(adam(0.3), steps=200) < 0.05


def test_adam_bf16_moments_converges():
    """Quantized moments track fp32 closely on a quadratic."""
    d32 = _run(adam(0.3), steps=120)
    d16 = _run(adam(0.3, moment_dtype=jnp.bfloat16), steps=120)
    assert abs(d32 - d16) < 0.3


def test_adam_bf16_moment_state_dtype():
    opt = adam(1e-3, moment_dtype=jnp.bfloat16)
    state = opt.init({"w": jnp.zeros((4, 4))})
    assert state["mu"]["w"].dtype == jnp.bfloat16
    assert state["nu"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.ones((4, 4))}
    _, state = opt.update(g, state, {"w": jnp.zeros((4, 4))})
    assert state["mu"]["w"].dtype == jnp.bfloat16


def test_sgd_momentum_converges():
    assert _run(sgd(0.05, momentum=0.9), steps=200) < 0.05


def test_clip_by_global_norm():
    grads = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    np.testing.assert_allclose(float(norm), 20.0)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5)
    # small grads untouched
    grads = {"a": jnp.full((4,), 0.01)}
    clipped, _ = clip_by_global_norm(grads, 1.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]), 0.01, rtol=1e-6)


def test_adamw_decay_skips_vectors():
    opt = adam(1e-2, weight_decay=0.1)
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = opt.init(params)
    grads = jax.tree.map(jnp.zeros_like, params)
    updates, _ = opt.update(grads, state, params)
    assert float(jnp.max(jnp.abs(updates["w"]))) > 0  # decayed
    assert float(jnp.max(jnp.abs(updates["b"]))) == 0  # bias skipped


_GUARDED = {
    "adam": lambda: adam(1e-2),
    "adam_bf16_moments": lambda: adam(1e-2, moment_dtype=jnp.bfloat16),
    "adamw": lambda: adam(1e-2, weight_decay=0.1),
    "sgd": lambda: sgd(1e-2),
    "sgd_momentum": lambda: sgd(1e-2, momentum=0.9),
}


def _bits(tree):
    return [np.atleast_1d(np.asarray(x)).view(np.uint8)
            for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("name", list(_GUARDED))
def test_ok_guard_skips_or_matches_unguarded(name):
    """``ok=False`` leaves params and state bit-identical whatever the
    gradient holds; a traced ``ok=True`` is the default, unguarded step."""
    opt = _GUARDED[name]()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"w": jax.random.normal(keys[0], (8, 4)), "b": jnp.zeros((4,))}
    grads = {"w": jax.random.normal(keys[1], (8, 4)),
             "b": jax.random.normal(keys[2], (4,))}
    update = jax.jit(opt.update)
    bad = {"w": grads["w"].at[0, 0].set(jnp.inf).at[1, 2].set(jnp.nan),
           "b": grads["b"].at[3].set(-jnp.inf)}
    state = opt.init(params)
    # skipped at the first step (no bias correction may divide by zero),
    # then a few steps in, once the moments are not zero
    for n_steps in (0, 3):
        for _ in range(n_steps):
            updates, state = opt.update(grads, state, params)
            params = apply_updates(params, updates)
        updates, kept = update(bad, state, params, jnp.asarray(False))
        for a, b in zip(_bits((apply_updates(params, updates), kept)),
                        _bits((params, state))):
            np.testing.assert_array_equal(a, b)

    want_u, want_s = update(grads, state, params)
    got_u, got_s = update(grads, state, params, jnp.asarray(True))
    assert jax.tree.structure(got_s) == jax.tree.structure(want_s)
    for got, want in zip(jax.tree.leaves((got_u, got_s)),
                         jax.tree.leaves((want_u, want_s))):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=1e-6)
