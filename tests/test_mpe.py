"""MPE core invariants: grouping, distribution, sampling, packed export."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev-only dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st
from conftest import hyp_examples

from repro.core import (MPEConfig, MPESearchEmbedding, MPERetrainEmbedding,
                        build_packed_table, feature_bits, make_groups,
                        packed_lookup, sample_group_bits)


def test_groups_are_frequency_sorted(rng):
    freqs = rng.zipf(1.2, 1000).astype(np.float64)
    gof, fsum = make_groups(freqs, 128)
    gof = np.asarray(gof)
    # every feature in group k must be at least as frequent as any in group k+1
    g = gof.max() + 1
    mins = [freqs[gof == k].min() for k in range(g)]
    maxs = [freqs[gof == k].max() for k in range(g)]
    for k in range(g - 1):
        assert mins[k] >= maxs[k + 1]


def test_group_sizes(rng):
    freqs = rng.random(1000)
    gof, fsum = make_groups(freqs, 128)
    counts = collections.Counter(np.asarray(gof).tolist())
    sizes = sorted(counts.values(), reverse=True)
    assert sizes[0] == 128 and sizes[-1] == 1000 - 7 * 128
    assert fsum.shape == (8,)


def test_initial_distribution_uniform(rng):
    cfg = MPEConfig()
    params, bufs = MPESearchEmbedding.init(jax.random.PRNGKey(0), 300, 8,
                                           rng.random(300), cfg)
    p = MPESearchEmbedding.probabilities(params, cfg)
    np.testing.assert_allclose(np.asarray(p), 1.0 / len(cfg.bits), rtol=1e-5)
    # expected bits at uniform init = mean of candidates = 3.0
    eb = MPESearchEmbedding.expected_bits(params, bufs, cfg)
    np.testing.assert_allclose(float(eb), 3.0, rtol=1e-5)


def test_eq11_sampling_picks_highest_eligible():
    """b* = max{b_i | p_i > 1/(2m)} — not the argmax."""
    cfg = MPEConfig()
    m = len(cfg.bits)
    gamma = np.zeros((2, m), np.float32)
    # group 0: argmax at b=1, but b=5 has p>1/2m  -> must sample 5
    probs0 = np.array([.05, .5, .05, .05, .05, .25, .05])
    probs1 = np.array([.9, .02, .02, .02, .02, .01, .01])  # -> 0
    gamma[0] = np.log(probs0) * cfg.tau
    gamma[1] = np.log(probs1) * cfg.tau
    params = {"gamma": jnp.asarray(gamma)}
    out = np.asarray(sample_group_bits(params, cfg))
    assert cfg.bits[out[0]] == 5
    assert cfg.bits[out[1]] == 0


def test_sampling_always_nonempty(rng):
    """max p >= 1/m > 1/(2m), so some width is always eligible."""
    cfg = MPEConfig()
    gamma = jnp.asarray(rng.normal(0, 5 * cfg.tau, (50, len(cfg.bits))),
                        jnp.float32)
    out = np.asarray(sample_group_bits({"gamma": gamma}, cfg))
    assert (out >= 0).all()


def test_packed_table_matches_fakequant(rng):
    """Packed inference (§4) must equal the retrain layer's fake quant."""
    cfg = MPEConfig()
    key = jax.random.PRNGKey(1)
    params, bufs = MPESearchEmbedding.init(key, 700, 16, rng.zipf(1.3, 700),
                                           cfg)
    params = dict(params, gamma=jnp.asarray(
        rng.normal(0, 0.01, params["gamma"].shape), jnp.float32))
    gb = sample_group_bits(params, cfg)
    fb = feature_bits(gb, bufs["group_of_feature"])
    table, meta = build_packed_table(params["emb"], fb, params["alpha"],
                                     params["beta"], cfg)
    rp, rb = MPERetrainEmbedding.init(params["emb"], params["alpha"],
                                      params["beta"], fb)
    ids = jnp.asarray(rng.integers(0, 700, (256,)))
    np.testing.assert_allclose(
        np.asarray(packed_lookup(table, meta, ids)),
        np.asarray(MPERetrainEmbedding.lookup(rp, rb, ids, cfg)),
        rtol=0, atol=1e-6)


def test_regularizer_weights_infrequent_groups_harder(rng):
    """Eq. 10: 1/s_j weighting — a rare group's bit-probability shift moves
    the regularizer more than the same shift on a frequent group."""
    cfg = MPEConfig()
    n = 256
    freqs = np.concatenate([np.full(128, 1000.0), np.full(128, 1.0)])
    params, bufs = MPESearchEmbedding.init(jax.random.PRNGKey(0), n, 8,
                                           freqs, cfg)

    def reg_with_boost(group):
        gamma = np.zeros((2, len(cfg.bits)), np.float32)
        gamma[group, -1] = 10 * cfg.tau  # push highest bit-width
        p = dict(params, gamma=jnp.asarray(gamma))
        return float(MPESearchEmbedding.reg_loss(p, bufs, cfg))

    assert reg_with_boost(1) > reg_with_boost(0)


@settings(max_examples=hyp_examples(10), deadline=None)
@given(seed=st.integers(0, 1000), lam=st.sampled_from([0.0, 1e-5, 1e-4]))
def test_lookup_differentiable(seed, lam):
    cfg = MPEConfig(lam=lam)
    rng = np.random.default_rng(seed)
    params, bufs = MPESearchEmbedding.init(jax.random.PRNGKey(seed), 300, 8,
                                           rng.zipf(1.3, 300), cfg)
    ids = jnp.asarray(rng.integers(0, 300, (64,)))

    def loss(p):
        e = MPESearchEmbedding.lookup(p, bufs, ids, cfg)
        return jnp.sum(e ** 2) + lam * MPESearchEmbedding.reg_loss(p, bufs, cfg)

    g = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.parametrize("d", [16, 128])
def test_lookup_eq9_path_follows_the_row_width(d, rng):
    """Lane-dense rows (d % 128 == 0) run Eq. 9 through the fused
    ``kernels.mpe_qat`` pair and match ``quantizer.mixed_expectation``, the
    output and the gradients of every parameter, to float32 rounding (only
    the summation order of ∂α, ∂β and ∂p differs), over a row count that
    is not a multiple of the kernel's tile. Narrower rows lower with no
    kernel, bit-identical to it."""
    from repro.core import quantizer
    cfg = MPEConfig()
    n = 500
    params, bufs = MPESearchEmbedding.init(jax.random.PRNGKey(2), n, d,
                                           rng.zipf(1.3, n), cfg)
    params = dict(params, gamma=jnp.asarray(
        rng.normal(0, 0.01, params["gamma"].shape), jnp.float32),
        beta=jnp.asarray(rng.normal(0, 1e-3, (d,)), jnp.float32))
    ids = jnp.asarray(rng.integers(0, n, (5, 430)), jnp.int32)  # 2150 rows
    w = jnp.asarray(rng.normal(0, 1, (5, 430, d)), jnp.float32)

    def plain(p):
        rows = jnp.take(p["emb"], ids, axis=0)
        probs = jnp.take(MPESearchEmbedding.probabilities(p, cfg),
                         jnp.take(bufs["group_of_feature"], ids), axis=0)
        return quantizer.mixed_expectation(rows, probs, p["alpha"],
                                           p["beta"], cfg.bits)

    def lookup(p):
        return MPESearchEmbedding.lookup(p, bufs, ids, cfg)

    def loss(fn):
        return lambda p: jnp.sum(jnp.sin(fn(p)) * w)

    kernel = "pallas_call" in str(jax.make_jaxpr(jax.grad(loss(lookup)))(
        params))
    assert kernel == (d % 128 == 0)
    got, want = jax.jit(lookup)(params), jax.jit(plain)(params)
    g_got = jax.jit(jax.grad(loss(lookup)))(params)
    g_want = jax.jit(jax.grad(loss(plain)))(params)
    if not kernel:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for k in g_want:
            np.testing.assert_array_equal(np.asarray(g_got[k]),
                                          np.asarray(g_want[k]))
        return
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-9)
    for k in ("emb", "gamma", "alpha", "beta"):
        scale = float(jnp.max(jnp.abs(g_want[k])))
        assert scale > 0, k
        np.testing.assert_allclose(np.asarray(g_got[k]),
                                   np.asarray(g_want[k]), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=k)
