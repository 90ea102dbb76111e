"""DLRM-DCNv2 on the CPU in float32: the low-rank cross layers against
their equation written out, the multi-hot MPE bag against per-slot lookups
summed by field, and the whole search step's loss and gradients against
the benchmark's plain reference (``bench/models/dlrm_dcnv2.py``) on seeded
weights, at a small size: 4 fields of bag sizes 3, 1, 7 and 12, d=8, cross
rank 4."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mpe import MPEConfig, MPESearchEmbedding
from repro.embeddings.table import FieldSpec, field_offsets
from repro.models.dlrm import DLRM, DLRMConfig
from repro.models.interactions import LowRankCrossNet

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "bench")
SIZES = (3, 1, 7, 12)
VOCABS = (50, 30, 200, 20)


def _bench_model():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from yardstick import spec
    return spec, spec.load_module("models", "dlrm_dcnv2")


@pytest.mark.parametrize("dim,rank,layers", [(12, 4, 3), (7, 7, 1)])
def test_low_rank_cross_net_is_its_equation(dim, rank, layers):
    """x_{l+1} = x0 ⊙ (W_l (V_l x_l) + b_l) + x_l, sample by sample in
    float64 with V_l (r×d) and W_l (d×r) as matrices."""
    params = LowRankCrossNet.init(jax.random.PRNGKey(1), dim, rank, layers)
    params["b"] = [jax.random.normal(jax.random.PRNGKey(2 + i), (dim,))
                   for i in range(layers)]
    x0 = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (5, dim)))
    got = np.asarray(LowRankCrossNet.apply(params, jnp.asarray(x0)))
    for n in range(x0.shape[0]):
        x = x0[n].astype(np.float64)
        for v, w, b in zip(params["v"], params["w"], params["b"]):
            V, W = np.asarray(v, np.float64).T, np.asarray(w, np.float64).T
            assert V.shape == (rank, dim) and W.shape == (dim, rank)
            x = x0[n] * (W @ (V @ x) + np.asarray(b)) + x
        # float32 products of a few terms against float64: a few ulps
        np.testing.assert_allclose(got[n], x, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sizes", [SIZES, (1, 1, 1, 1)],
                         ids=["multi_hot", "one_hot"])
def test_mpe_bag_is_per_slot_lookup_summed_by_field(sizes):
    """``DLRM.apply`` over a multi-hot batch gives the logits of its tower
    over each field's slots looked up one by one and summed; with one slot
    a field, the embeddings are today's (B, F, d) lookup of (B, F) ids."""
    fields = tuple(FieldSpec(f"f{i}", v, k)
                   for i, (v, k) in enumerate(zip(VOCABS, sizes)))
    cfg = DLRMConfig(fields=fields, d_embed=8, mlp_hidden=(16,),
                     compressor="mpe_search", comp_cfg={},
                     use_batchnorm=False)
    params, buffers, state = DLRM.init(jax.random.PRNGKey(0), cfg)
    params["embedding"]["gamma"] = jax.random.normal(
        jax.random.PRNGKey(3), params["embedding"]["gamma"].shape) * 1e-2
    gen = np.random.default_rng(0)
    ids = np.concatenate([gen.integers(0, v, (6, k)) for v, k in
                          zip(VOCABS, sizes)], axis=1).astype(np.int32)
    logits, _, _ = DLRM.apply(params, buffers, state, {"ids": ids}, cfg)

    mpe = MPEConfig()
    offs, per_field, s = field_offsets(fields), [], 0
    for f, k in enumerate(sizes):
        rows = [MPESearchEmbedding.lookup(
            params["embedding"], buffers["embedding"],
            jnp.asarray(ids[:, s + j] + offs[f]), mpe) for j in range(k)]
        per_field.append(sum(rows[1:], rows[0]))
        s += k
    emb = jnp.stack(per_field, axis=1)
    assert emb.shape == (6, len(fields), 8)
    if sizes == (1,) * len(fields):
        np.testing.assert_array_equal(emb, MPESearchEmbedding.lookup(
            params["embedding"], buffers["embedding"],
            jnp.asarray(ids + offs[None, :]), mpe))
    want, _ = DLRM.interact(params, state, emb, None, cfg)
    # the same float32 sums, in another order for the bags: a few ulps
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-6)


def _small_cfg(spec):
    cfg = spec.config("dlrm-dcnv2-mlperf")
    cfg.update(field_vocabs=list(VOCABS), multi_hot_sizes=list(SIZES), d=8,
               bottom_mlp=[16, 8], top_mlp=[16, 8], cross_rank=4)
    return cfg


def _batches(cfg, seed=2**31 + 11):
    spec, _ = _bench_model()
    drv = spec.load_module("drivers", "train_bags")
    mix = spec.traffic("mpe-search-multihot")
    mix.update(batch=32, ring_batches=3)
    return drv.ring(cfg, mix, seed)


def test_dcnv2_gradients_match_the_reference():
    """One step's loss and every gradient leaf of the program's
    ``DLRM.loss_fn`` (``dcnv2``, multi-hot, dense features) against the
    reference's loss written out, from the same seeded start."""
    spec, model = _bench_model()
    cfg = _small_cfg(spec)
    batch = _batches(cfg)[0]
    tr = model.build_trainer(cfg, 7, 1.1)
    params = tr.carry["params"]
    (loss, _), grads = jax.value_and_grad(tr.loss_fn, has_aux=True)(
        params, tr.buffers, tr.carry["state"], batch, step=0)

    gof, freq_sum = model.train_buffers(cfg, 1.1)
    init = model.train_init(cfg, 7, freq_sum.shape[0])
    t = cfg["train"]
    static = (tuple(cfg["bits"]), t["tau"], t["lam"], SIZES)
    idx = jnp.asarray(batch["ids"] + model.slot_offsets(cfg)[None, :])
    trainable = {k: init[k] for k in model.TRAINABLE}
    ref_loss, ref_g = jax.value_and_grad(
        lambda p: model._ref_loss(p, gof, freq_sum, idx,
                                  jnp.asarray(batch["dense"]),
                                  jnp.asarray(batch["label"]), static,
                                  "f32", False))(trainable)
    # both float32 on the CPU; sums over a batch in another order
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    want = model.program_tree(ref_g)
    got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(got) == len(jax.tree.leaves(want)) == 23
    for path, g in got:
        w = want
        for p in path:
            w = w[getattr(p, "key", getattr(p, "idx", None))]
        # float32 against float32: rounding of sums over the batch and
        # the slots, relative to the leaf's largest entry
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_dcnv2_search_steps_match_the_reference():
    """Three steps through ``Trainer`` (clip, Adam) against the reference's
    three steps: losses, the first gradient's norms and the change's norms
    by leaf."""
    spec, model = _bench_model()
    cfg = _small_cfg(spec)
    batches = _batches(cfg)
    tr = model.build_trainer(cfg, 7, 1.1)
    tr.run(lambda s: batches[s], 1, log_every=1, log_fn=lambda *_: None)
    grad_norms = model.program_grad_norms(tr, cfg["train"]["b1"])
    tr.run(lambda s: batches[s], 3, log_every=1, log_fn=lambda *_: None)
    n_groups = tr.buffers["embedding"]["freq_sum"].shape[0]
    change = model.program_change_norms(tr, cfg, 7, n_groups)
    ref = model.reference_train(cfg, 7, batches, "f32", exponent=1.1)
    # float32 both ways: a step's loss within a few ulps of its sums
    np.testing.assert_allclose([h["loss"] for h in tr.history],
                               ref["losses"], rtol=1e-5)
    assert set(grad_norms) == set(ref["grad_norms"]) == set(change)
    for k in grad_norms:
        # a norm of float32 sums; Adam's first moment divided by 1 - b1
        # adds a rounding of its own
        np.testing.assert_allclose(grad_norms[k], ref["grad_norms"][k],
                                   rtol=1e-4, err_msg=k)
        # Adam's step is lr-sized whatever the gradient's size, so a leaf
        # with a near-zero gradient entry may move by a rounding's sign:
        # relative to the largest leaf change
        np.testing.assert_allclose(change[k], ref["change_norms"][k],
                                   rtol=1e-3,
                                   atol=1e-4 * max(change.values()),
                                   err_msg=k)
