"""shard_map parity suite (ISSUE 4): all four Pallas kernels, the packed and
tiered serve cells, and the shard_map train step on real multi-device meshes.

Everything here but the one-device ownership test of the lookup routine is
marked ``multidevice`` and runs in-process in the blocking CI job of the
same name (``XLA_FLAGS`` virtualizes 4 CPU devices —
see tests/conftest.py). On a single-device session the marked tests skip and
``test_shard_suite_subprocess_fallback`` re-runs the whole suite in a
4-virtual-device child pytest, so tier-1 keeps the coverage.

Parity contract (docs/ARCHITECTURE.md §shard_map layer):
  - packed lookup / tiered hot lookup / flash attention / QAT expectation:
    bit-identical to the jitted single-device path on 1x1, 1x4 and 2x2
    meshes (the masked-gather+psum adds one non-zero term to zeros).
  - embedding bag: documented tolerance — the psum over row shards
    reassociates the bag sum (exact when the row axes don't really split).
  - train step: documented tolerance — mean-of-shard-means reassociates the
    batch reduction.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantizer
from repro.core.inference import build_packed_table, packed_lookup
from repro.core.mpe import MPEConfig
from repro.dist import shard
from repro.dist.mesh import make_device_mesh, use_mesh

multidevice = pytest.mark.multidevice

MESH_SHAPES = [(1, 1), (1, 4), (2, 2)]
BITS = MPEConfig().bits


def _mesh(shape):
    return make_device_mesh(shape, ("data", "model"))


def _random_packed_table(n=160, d=12, seed=0, row_pad_multiple=None):
    rng = np.random.default_rng(seed)
    cfg = MPEConfig()
    emb = rng.normal(size=(n, d)).astype(np.float32)
    fbits = rng.integers(0, len(cfg.bits), size=n).astype(np.int32)
    alpha = (np.abs(rng.normal(size=len(cfg.bits))) * 0.1 + 0.01).astype(np.float32)
    beta = (rng.normal(size=d) * 0.01).astype(np.float32)
    return build_packed_table(emb, fbits, alpha, beta, cfg,
                              row_pad_multiple=row_pad_multiple)


# ---------------------------------------------------------------------------
# row-shard ownership of the one lookup routine, on one device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hot_fraction", [None, 0.0, 0.3, 1.0])
@pytest.mark.parametrize("k", [2, 4])
def test_lookup_rows_row_shards_sum_to_lookup(k, hot_fraction, rng):
    """``core.inference._lookup_rows`` on each of ``k`` hand-cut row shards
    of every width's subtable, told which shard it holds: the k partial
    lookups sum to ``packed_lookup`` (no keep mask) or, with the hot bit as
    ``keep``, to ``tiered_hot_lookup`` — zeros at cold ids. Runs without a
    mesh, so the ownership mask the psum bodies rely on is checked in every
    session."""
    from repro.cache import TieredTableStore
    from repro.cache.tiers import tiered_hot_lookup
    from repro.core.inference import _lookup_rows
    from repro.embeddings.frequency import zipf_frequencies
    table, meta = _random_packed_table(n=150, row_pad_multiple=1)
    bits, d = meta["bits"], meta["d"]
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(53,)), jnp.int32)
    if hot_fraction is None:
        tree, local, keep_vec = table, "local_idx", None
        ref_fn = jax.jit(lambda t, i: packed_lookup(t, meta, i))
    else:
        tree = TieredTableStore(table, meta,
                                zipf_frequencies(meta["n"], seed=1),
                                hot_fraction).hot
        local, keep_vec = "tier_local", tree["is_hot"]
        ref_fn = jax.jit(lambda t, i: tiered_hot_lookup(t, bits, d, i))
    ref = np.asarray(ref_fn(tree, ids))

    def cut(sub, s):
        rows = -(-sub.shape[0] // k)
        padded = jnp.pad(sub, ((0, rows * k - sub.shape[0]), (0, 0)))
        return padded[s * rows:(s + 1) * rows]

    @jax.jit
    def shard_sum(t, i):
        keep = None if keep_vec is None else jnp.take(t["is_hot"], i, axis=0)
        out = jnp.zeros((i.shape[0], d), jnp.float32)
        for s in range(k):
            out = out + _lookup_rows(
                {key: cut(v, s) for key, v in t["subtables"].items()},
                t["alpha"], t["beta"], bits, d,
                jnp.take(t["width_idx"], i, axis=0),
                jnp.take(t[local], i, axis=0), keep=keep, shard=s)
        return out

    got = np.asarray(shard_sum(tree, ids))
    np.testing.assert_array_equal(got, ref)
    if keep_vec is not None:
        cold = ~np.asarray(keep_vec)[np.asarray(ids)]
        assert not got[cold].any()


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
@pytest.mark.parametrize("use_kernel", [False, True])
@multidevice
def test_packed_lookup_parity(mesh_shape, use_kernel, rng):
    table, meta = _random_packed_table()
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(24, 3)), jnp.int32)
    ref = np.asarray(jax.jit(lambda t, i: packed_lookup(t, meta, i))(table, ids))
    with use_mesh(_mesh(mesh_shape)):
        got = jax.jit(lambda t, i: shard.sharded_packed_lookup(
            t, meta, i, use_kernel=use_kernel))(table, ids)
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_packed_lookup_pad_to_shard_edge(mesh_shape, rng):
    """Non-divisible edge: row_pad_multiple=1 leaves odd subtable row counts
    (23, 31, ... rows on a 2/4-way model axis) — the wrapper's
    pad_rows_to_shard must keep the result bit-exact."""
    table, meta = _random_packed_table(n=150, row_pad_multiple=1)
    mp = mesh_shape[1]
    assert any(v.shape[0] % mp for v in table["subtables"].values()), \
        "edge case degenerated: all subtables divide the model axis"
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(37,)), jnp.int32)
    ref = np.asarray(jax.jit(lambda t, i: packed_lookup(t, meta, i))(table, ids))
    with use_mesh(_mesh(mesh_shape)):
        got = jax.jit(lambda t, i: shard.sharded_packed_lookup(
            t, meta, i))(table, ids)
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_embedding_bag_parity(mesh_shape, rng):
    from repro.kernels.embedding_bag.ref import embedding_bag_ref
    tab = jnp.asarray(rng.normal(0, 1, (101, 16)), jnp.float32)  # odd rows
    ids = jnp.asarray(rng.integers(0, 101, (8, 5)))
    mask = jnp.asarray(rng.random((8, 5)) < 0.8)
    ref = np.asarray(jax.jit(embedding_bag_ref)(tab, ids, mask))
    with use_mesh(_mesh(mesh_shape)):
        got = jax.jit(lambda t, i, m: shard.sharded_embedding_bag(
            t, i, m))(tab, ids, mask)
    # documented tolerance: the psum reassociates each bag's sum
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
@multidevice
def test_flash_attention_parity(mesh_shape, rng):
    from repro.kernels.flash_attention.ops import flash_attention_kernel
    q = jnp.asarray(rng.normal(0, 1, (4, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (4, 32, 2, 16)), jnp.float32)  # GQA
    v = jnp.asarray(rng.normal(0, 1, (4, 32, 2, 16)), jnp.float32)
    ref = np.asarray(jax.jit(lambda a, b, c: flash_attention_kernel(
        a, b, c, causal=True))(q, k, v))
    with use_mesh(_mesh(mesh_shape)):
        got = jax.jit(lambda a, b, c: shard.sharded_flash_attention(
            a, b, c, causal=True))(q, k, v)
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES)
@multidevice
def test_mixed_expectation_parity(mesh_shape, rng):
    from repro.kernels.mpe_qat.ops import mixed_expectation_kernel
    m = len(BITS)
    rows = jnp.asarray(rng.normal(0, 3e-3, (101, 16)), jnp.float32)  # odd rows
    probs = jax.nn.softmax(jnp.asarray(rng.normal(0, 1, (101, m)),
                                       jnp.float32), -1)
    alpha = jnp.asarray([quantizer.init_alpha(3e-3, b) for b in BITS])
    beta = jnp.asarray(rng.normal(0, 1e-4, (16,)), jnp.float32)
    ref = np.asarray(jax.jit(lambda r, p, a, b: mixed_expectation_kernel(
        r, p, a, b, BITS))(rows, probs, alpha, beta))
    with use_mesh(_mesh(mesh_shape)):
        got = jax.jit(lambda r, p, a, b: shard.sharded_mixed_expectation(
            r, p, a, b, BITS))(rows, probs, alpha, beta)
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_mixed_expectation_grad_parity(mesh_shape, rng):
    """The row-parallel kernel's backward under a mesh: each shard's ∂α and
    ∂β are partial sums, and the shard_map transpose sums them over the
    shards, so they equal the one-device path's up to that reassociation;
    ∂rows and ∂probs are row-local and bit-identical."""
    from repro.kernels.mpe_qat.ops import mixed_expectation_kernel
    m, d = len(BITS), 128
    rows = jnp.asarray(rng.normal(0, 3e-3, (301, d)), jnp.float32)
    probs = jax.nn.softmax(jnp.asarray(rng.normal(0, 1, (301, m)),
                                       jnp.float32), -1)
    alpha = jnp.asarray([quantizer.init_alpha(3e-3, b) for b in BITS])
    beta = jnp.asarray(rng.normal(0, 1e-4, (d,)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (301, d)), jnp.float32)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda r, p, a, b: jnp.sum(jnp.sin(fn(r, p, a, b, BITS)) * w),
            argnums=(0, 1, 2, 3)))(rows, probs, alpha, beta)

    ref = grads(mixed_expectation_kernel)
    with use_mesh(_mesh(mesh_shape)):
        got = grads(shard.sharded_mixed_expectation)
    for a, r in zip(got[:2], ref[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    for a, r in zip(got[2:], ref[2:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-6 * float(jnp.max(jnp.abs(r))))


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_tiered_hot_lookup_parity(mesh_shape, rng):
    from repro.cache import TieredTableStore
    from repro.cache.tiers import tiered_hot_lookup
    from repro.embeddings.frequency import zipf_frequencies
    table, meta = _random_packed_table()
    store = TieredTableStore(table, meta, zipf_frequencies(meta["n"], seed=1),
                             0.4)
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(37,)), jnp.int32)
    ref = np.asarray(jax.jit(lambda h, i: tiered_hot_lookup(
        h, meta["bits"], meta["d"], i))(store.hot, ids))
    with use_mesh(_mesh(mesh_shape)):
        got = jax.jit(lambda h, i: shard.sharded_tiered_hot_lookup(
            h, meta["bits"], meta["d"], i))(store.hot, ids)
    np.testing.assert_array_equal(np.asarray(got), ref)
    # hot shards really live on the model axis when it has > 1 device
    if mesh_shape[1] > 1:
        from repro.dist.sharding import tiered_hot_pspecs, tree_named_shardings
        mesh = _mesh(mesh_shape)
        ns = tree_named_shardings(mesh, tiered_hot_pspecs(store.hot))
        placed = jax.device_put(store.hot["subtables"], ns["subtables"])
        for sub in jax.tree.leaves(placed):
            # distinct row blocks along "model"; replicated over "data"
            n_shards = len({str(s.index) for s in sub.addressable_shards})
            assert n_shards == mesh.shape["model"], sub.sharding


# ---------------------------------------------------------------------------
# serve cells: engine-level parity + zero recompiles
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_model():
    from repro.launch.serve import train_packed_dlrm
    return train_packed_dlrm(field_vocabs=(150, 100, 120), train_steps=10,
                             train_batch=128, d_embed=8, mlp_hidden=(16,),
                             seed=4)


def _single_device_mesh():
    from repro.dist.mesh import host_mesh
    return host_mesh(n_data=1, n_model=1)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_serve_cells_sharded_parity_and_zero_recompile(mesh_shape,
                                                       served_model):
    from repro.data.synthetic import SyntheticCTR
    from repro.launch.serve import build_engine
    cfg, params, state, buffers, spec, res = served_model
    ids = SyntheticCTR(spec._replace(batch_size=300)).batch(50_000)["ids"]

    ref_engine = build_engine(cfg, params, state, buffers, p99_rows=64,
                              bulk_rows=256, mesh=_single_device_mesh())
    ref = ref_engine.score(ids)

    engine = build_engine(cfg, params, state, buffers, p99_rows=64,
                          bulk_rows=256, mesh=_mesh(mesh_shape))
    got = engine.score(ids)
    np.testing.assert_array_equal(got, ref)

    # warm process ⇒ zero recompiles, asserted via the CellCache counters
    n_compiles = engine.compile_count
    engine.score(ids)
    assert engine.compile_count == n_compiles
    assert engine.counters()["hits"] == 0  # distinct shapes, no double compile


@pytest.mark.parametrize("mesh_shape", [(2, 2)])
@multidevice
def test_tiered_serve_cells_sharded_parity(mesh_shape, served_model):
    from repro.cache import TieredTableStore
    from repro.data.synthetic import SyntheticCTR
    from repro.launch.serve import build_engine
    cfg, params, state, buffers, spec, res = served_model
    freqs = SyntheticCTR(spec).expected_frequencies()
    ids = SyntheticCTR(spec._replace(batch_size=300)).batch(60_000)["ids"]

    def tiered_engine(mesh):
        store = TieredTableStore(res["packed_table"], res["packed_meta"],
                                 freqs, 0.3)
        return build_engine(cfg, params, state, buffers, p99_rows=64,
                            bulk_rows=256, store=store, mesh=mesh)

    ref = tiered_engine(_single_device_mesh()).score_tiered(ids)
    engine = tiered_engine(_mesh(mesh_shape))
    got = engine.score_tiered(ids)
    np.testing.assert_array_equal(got, ref)  # hot psum + cold fill: exact
    n_compiles = engine.compile_count
    engine.score_tiered(ids)
    assert engine.compile_count == n_compiles


# ---------------------------------------------------------------------------
# train step under shard_map
# ---------------------------------------------------------------------------

def _tiny_builder(seed=0):
    from repro.data.synthetic import CTRSpec, SyntheticCTR
    from repro.embeddings.table import FieldSpec
    from repro.models.dlrm import DLRMConfig
    from repro.zoo import dlrm_builder
    spec = CTRSpec(field_vocabs=(300, 200), batch_size=64, seed=seed)
    ds = SyntheticCTR(spec)
    fields = tuple(FieldSpec(f"f{i}", v) for i, v in enumerate(spec.field_vocabs))
    # batchnorm off: DP batch statistics are per-shard (standard non-sync-BN
    # semantics), which is a semantic — not numerical — difference
    base = DLRMConfig(fields=fields, d_embed=8, mlp_hidden=(16,),
                      backbone="dnn", use_batchnorm=False)
    return ds, dlrm_builder(base, ds.expected_frequencies())


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_sharded_value_and_grad_parity(mesh_shape):
    ds, build = _tiny_builder()
    b = build(jax.random.PRNGKey(0), "plain", {})
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    (l_ref, (st_ref, m_ref)), g_ref = jax.jit(
        lambda p, bu, st, ba: jax.value_and_grad(b["loss_fn"], has_aux=True)(
            p, bu, st, ba, step=0))(b["params"], b["buffers"], b["state"], batch)

    mesh = _mesh(mesh_shape)
    vag = shard.sharded_value_and_grad(b["loss_fn"], mesh)
    (l_sh, (st_sh, m_sh)), g_sh = jax.jit(
        lambda p, bu, st, ba: vag(p, bu, st, ba, step=0))(
        b["params"], b["buffers"], b["state"], batch)

    np.testing.assert_allclose(float(l_sh), float(l_ref), rtol=1e-6)
    for a, r in zip(jax.tree.leaves(g_sh), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-7)
    # the table's grads arrive row-shard-local when the rows divide the axis
    if mesh_shape[1] > 1:
        assert g_sh["embedding"]["emb"].sharding.spec[0] == "model"


@pytest.mark.parametrize("mesh_shape", [(2, 2)])
@multidevice
def test_trainer_mesh_loss_trajectory(mesh_shape):
    """Trainer(mesh=...) trains to the same losses as the single-device loop
    (documented fp32 tolerance: mean-of-shard-means + psum-scattered table
    grads reassociate reductions)."""
    from repro.train.loop import Trainer
    from repro.train.optimizer import adam
    runs = {}
    for mesh in (None, _mesh(mesh_shape)):
        ds, build = _tiny_builder()
        b = build(jax.random.PRNGKey(0), "plain", {})
        tr = Trainer(b["loss_fn"], b["params"], b["buffers"], b["state"],
                     adam(1e-3), mesh=mesh)
        losses = []
        tr.run(lambda s: ds.batch(s), 8, log_every=1,
               log_fn=lambda m: losses.append(float(m.split("loss ")[1]
                                                    .split(" ")[0])))
        runs[mesh is None] = (losses, jax.tree.map(np.asarray, tr.params))
    np.testing.assert_allclose(runs[False][0], runs[True][0], rtol=1e-4)
    for a, r in zip(jax.tree.leaves(runs[False][1]),
                    jax.tree.leaves(runs[True][1])):
        np.testing.assert_allclose(a, r, rtol=2e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# single-device degradation (runs everywhere — no marker)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_lookup_degrades_without_mesh(use_kernel, rng):
    table, meta = _random_packed_table()
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(9, 3)), jnp.int32)
    got = shard.sharded_packed_lookup(table, meta, ids, use_kernel=use_kernel)
    ref = packed_lookup(table, meta, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


# ---------------------------------------------------------------------------
# subprocess fallback: single-device sessions re-run the suite on 4 virtual
# devices (the CI `test` job path; the `multidevice` job runs in-process)
# ---------------------------------------------------------------------------

def test_shard_suite_subprocess_fallback():
    if jax.device_count() >= 4:
        pytest.skip("in-process multidevice tests cover this session")
    from test_dist import subprocess_env_4dev
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "multidevice",
         "-p", "no:cacheprovider", os.path.join(here, "test_shard.py"),
         os.path.join(here, "test_shard_a2a.py"),
         os.path.join(here, "test_dist.py"),
         os.path.join(here, "test_chip_smoke.py")],
        env=subprocess_env_4dev(), capture_output=True, text=True,
        timeout=1800, cwd=os.path.join(here, os.pardir))
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout[-4000:]}\nstderr:\n{proc.stderr[-2000:]}"
    assert " passed" in proc.stdout and "failed" not in proc.stdout