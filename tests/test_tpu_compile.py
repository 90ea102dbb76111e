"""The main-path Pallas kernels and the ``dlrm-criteo`` packed score step
compile for a TPU v5e chip that is described, not attached
(``jax.experimental.topologies``): Mosaic refuses misaligned blocks and
unsupported ops here, at no chip time. Nothing runs, so nothing is timed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file while only the one given it loads the
library. Keep every described-topology compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.core.inference import packed_specs
from repro.core.mpe import MPEConfig

BITS = MPEConfig().bits
D = 16
V5E_HBM_BYTES = 16e9          # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable cannot be read back without a chip
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b", [1, 2, 4, 6])
def test_mpe_lookup_compiles(one_chip, b):
    from repro.kernels.mpe_lookup.kernel import packed_lookup_pallas
    w = packing.words_per_row(D, b)
    compiled = jax.jit(lambda i, words, a, be: packed_lookup_pallas(
        i, words, a, be, b=b, d=D, interpret=False)).lower(
        _sds(one_chip, (4096,), jnp.int32),
        _sds(one_chip, (1 << 16, w), jnp.uint32),
        _sds(one_chip, (), jnp.float32),
        _sds(one_chip, (D,), jnp.float32)).compile()
    _assert_kernel(compiled)


# the criteo cell's rows (batch 2048 × 39 fields, d=16) and the dcnv2 cell's
# (batch 2048 × 214 multi-hot slots, d=128, the width the search step runs
# through the kernels)
QAT_SHAPES = [(2048 * 39, D), (2048 * 214, 128)]


def _qat_args(one_chip, rows, d):
    return (_sds(one_chip, (rows, d), jnp.float32),
            _sds(one_chip, (rows, len(BITS)), jnp.float32),
            _sds(one_chip, (len(BITS),), jnp.float32),
            _sds(one_chip, (d,), jnp.float32))


@pytest.mark.parametrize("rows,d", QAT_SHAPES)
def test_mpe_qat_fwd_compiles(one_chip, rows, d):
    from repro.kernels.mpe_qat.kernel import mixed_expectation_fwd
    compiled = jax.jit(lambda r, p, a, be: mixed_expectation_fwd(
        r, p, a, be, bits=BITS, interpret=False)).lower(
        *_qat_args(one_chip, rows, d)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("rows,d", QAT_SHAPES)
def test_mpe_qat_bwd_compiles(one_chip, rows, d):
    from repro.kernels.mpe_qat.kernel import mixed_expectation_bwd
    args = _qat_args(one_chip, rows, d)
    compiled = jax.jit(lambda r, p, a, be, g: mixed_expectation_bwd(
        r, p, a, be, g, bits=BITS, interpret=False)).lower(
        *args, args[0]).compile()
    _assert_kernel(compiled)


def test_embedding_bag_compiles(one_chip):
    from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
    compiled = jax.jit(lambda t, i, m: embedding_bag_pallas(
        t, i, m, interpret=False)).lower(
        _sds(one_chip, (1 << 16, D), jnp.float32),
        _sds(one_chip, (512, 8), jnp.int32),
        _sds(one_chip, (512, 8), jnp.bool_)).compile()
    _assert_kernel(compiled)


def test_dlrm_criteo_packed_score_step_fits_one_chip(one_chip):
    """The serve_p99 step of the full-width table (~34M rows, MLP
    1024-512-256) at 512 rows, on ``packed_specs`` shapes."""
    from repro.configs.dlrm_criteo import make_config
    from repro.models.dlrm import DLRM
    from repro.nn.mlp import MLP
    from repro.serve.cells import packed_score_step

    cfg = make_config(reduced=False)
    n = sum(f.vocab for f in cfg.fields)
    n_fields = len(cfg.fields)
    serve_cfg = cfg._replace(compressor="packed",
                             comp_cfg={"bits": BITS, "d": D, "n": n})
    hist = (0.1, 0.1, 0.15, 0.15, 0.2, 0.15, 0.15)
    mlp = jax.eval_shape(lambda k: MLP.init(k, n_fields * D, cfg.mlp_hidden,
                                            d_out=1, use_batchnorm=True),
                         jax.random.PRNGKey(0))
    params = {"embedding": packed_specs(n, D, MPEConfig(), hist), "mlp": mlp}
    state = {"mlp": jax.eval_shape(lambda: MLP.init_state(
        cfg.mlp_hidden, use_batchnorm=True))}
    buffers = {"embedding": {},
               "offsets": jax.ShapeDtypeStruct((n_fields,), jnp.int32)}

    def place(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    compiled = jax.jit(packed_score_step(DLRM, serve_cfg)).lower(
        place(params), place(state), place(buffers),
        _sds(one_chip, (512, n_fields), jnp.int32)).compile()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    table_bytes = sum(np.prod(s.shape) * 4 for s in
                      jax.tree.leaves(params["embedding"]))
    assert m.argument_size_in_bytes >= table_bytes
    assert peak < V5E_HBM_BYTES, f"{peak / 1e9:.2f} GB"


def test_trainer_updates_the_criteo_table_in_one_fusion(one_chip):
    """The NaN guard rides on the optimizer's per-step scalars, so Adam
    writes the full ``dlrm-criteo`` table's θ, μ and ν in one fusion. An
    element-wise select between new and old values splits it into three
    (θ, μ and ν each streaming the gradient), but only at this size: at
    1M rows XLA fuses either way."""
    from repro.train.loop import Trainer
    from repro.train.optimizer import adam

    rows, batch, n_fields = 33_775_889, 2048, 39

    def loss_fn(params, buffers, state, b, *, step=None):
        logit = jnp.sum(params["table"][b["ids"]], axis=(1, 2)) * params["w"][0]
        return jnp.mean(jnp.square(logit)), (state, jnp.mean(logit))

    small = {"table": jnp.zeros((64, D)), "w": jnp.ones((D,))}
    tr = Trainer(loss_fn, small, {}, {}, adam(1e-3))

    def place(x):
        shape = (rows, D) if x.shape == (64, D) else x.shape
        return _sds(one_chip, shape, x.dtype)

    compiled = tr._train_step.lower(
        jax.tree.map(place, tr.carry), {},
        {"ids": _sds(one_chip, (batch, n_fields), jnp.int32)},
        _sds(one_chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    table = f"f32[{rows},{D}]"
    outputs = [line.split(" fusion(")[0].count(table)
               for line in text[text.index("\nENTRY"):].splitlines()
               if " fusion(" in line and 'op_name="jit(train_step)/update/'
               in line and table in line.split(" fusion(")[0]]
    assert outputs == [3], outputs


@pytest.fixture(scope="module")
def dcnv2_step(one_chip):
    """The MPE search step of the benchmark's ``dlrm-dcnv2-mlperf``
    configuration (one chip's share of MLPerf's DLRM-DCNv2: 4.29M rows of
    d=128, 214 ids a sample in 26 multi-hot fields, 13 dense features, low
    rank cross layers and the 1024-1024-512-256 MLP) at batch 2048 through
    ``Trainer``, compiled on shapes alone, with its Pallas kernels built
    for the chip (the backend here is the CPU, which would interpret
    them). Returns the executable and the configuration."""
    import json

    from repro.core.mpe import MPEConfig
    from repro.embeddings.table import FieldSpec
    from repro.kernels.mpe_qat import kernel as qat_kernel
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.train.loop import Trainer
    from repro.train.optimizer import adam

    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                        "configs", "dlrm-dcnv2-mlperf.json")
    with open(path) as f:
        c = json.load(f)
    batch, n_ids = 2048, sum(c["multi_hot_sizes"])
    fields = tuple(FieldSpec(f"f{i}", v, k) for i, (v, k) in enumerate(
        zip(c["field_vocabs"], c["multi_hot_sizes"])))
    cfg = DLRMConfig(fields=fields, d_embed=c["d"],
                     mlp_hidden=tuple(c["top_mlp"]), backbone="dcnv2",
                     n_cross_layers=c["cross_layers"],
                     cross_rank=c["cross_rank"], dense_in=c["dense_in"],
                     bottom_hidden=tuple(c["bottom_mlp"]),
                     compressor="mpe_search",
                     comp_cfg=MPEConfig(bits=tuple(c["bits"]))._asdict(),
                     use_batchnorm=False)
    params, buffers, state = jax.eval_shape(
        lambda k: DLRM.init(k, cfg), jax.random.PRNGKey(0))

    def loss_fn(p, bu, st, b, *, step=None):
        return DLRM.loss_fn(p, bu, st, b, cfg, lam=3e-5, train=True,
                            step=step)

    def tiny(s):
        return jnp.zeros((1,) * len(s.shape), s.dtype)

    tr = Trainer(loss_fn, jax.tree.map(tiny, params),
                 jax.tree.map(tiny, buffers), state, adam(1e-3))
    carry = jax.eval_shape(lambda p: {"params": p, "state": state,
                                      "opt": adam(1e-3).init(p), "ef": None},
                           params)

    def place(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(qat_kernel, "resolve_interpret", lambda interpret: False)
        compiled = tr._train_step.lower(
            place(carry), place(buffers),
            {"ids": _sds(one_chip, (batch, n_ids), jnp.int32),
             "dense": _sds(one_chip, (batch, c["dense_in"]), jnp.float32),
             "label": _sds(one_chip, (batch,), jnp.int32)},
            _sds(one_chip, (), jnp.int32)).compile()
    return compiled, c


def test_dlrm_dcnv2_mlperf_train_step_fits_one_chip(dcnv2_step):
    """The step fits the chip's 16 GB."""
    compiled, c = dcnv2_step
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    table_bytes = sum(c["field_vocabs"]) * c["d"] * 4
    assert m.argument_size_in_bytes >= 3 * table_bytes    # θ, μ, ν
    assert peak < V5E_HBM_BYTES, f"{peak / 1e9:.2f} GB"


def test_dlrm_dcnv2_mlperf_train_step_runs_eq9_fused(dcnv2_step):
    """At d=128 the step runs Eq. 9 as the two named kernels, once each,
    both under ``embed_quantize``, so that a trace reads them by name and
    as the ``quantize`` part of the step."""
    compiled, _ = dcnv2_step
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    for name in ("mpe_qat_fwd", "mpe_qat_bwd"):
        calls = [line for line in entry.splitlines()
                 if f"%{name}" in line.split(" = ", 1)[0]
                 and "tpu_custom_call" in line]
        assert len(calls) == 1, (name, calls)
        op_name = calls[0].split('op_name="', 1)[1].split('"', 1)[0]
        assert "embed_quantize" in op_name, op_name
