"""Capacity-bucketed all-to-all lookup suite (ISSUE 10).

Covers the ``lookup_comms="a2a"`` path of ``repro.dist.shard`` and the new
sharded backward paths:

  - bucket-planner properties in the ``tests/lifecycle_props.py`` style
    (seeded-numpy sweeps, plain asserts): no id dropped or duplicated under
    overflow, slots unique and bucket-local, spill bounded by
    ``spill_capacity``;
  - bit-exact parity a2a vs psum vs the single-device reference on 1x1,
    1x4, 2x2 and 1x2x2 meshes — at full capacity, under a forced-overflow
    capacity, and through the Pallas kernel path;
  - engine-level: ``lookup_comms`` forks the cell fingerprint, repeat
    shapes recompile nothing (CellCache counters);
  - grad parity for the sharded ``embedding_bag`` / ``flash_attention``
    backward paths vs ``jax.value_and_grad`` on the unsharded kernels,
    plus the explicit ~1e-6 psum reassociation tolerance pin for
    ``sharded_embedding_bag``;
  - HLO attribution: the compiled a2a cell really moves its bytes through
    ``all-to-all`` (and the psum cell through ``all-reduce``), as
    ``hlo_analysis`` reports them to roofline/BC501.

Marked ``multidevice`` like tests/test_shard.py; on single-device sessions
the subprocess fallback there re-runs this file under 4 virtual devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.inference import packed_lookup
from repro.dist import shard
from repro.dist.mesh import host_mesh, make_device_mesh, use_mesh

from test_shard import _mesh, _random_packed_table

multidevice = pytest.mark.multidevice

CAPACITIES = (None, 8, 1)  # full slice / partial / forced overflow


@pytest.fixture(scope="module")
def served_model():
    from repro.launch.serve import train_packed_dlrm
    return train_packed_dlrm(field_vocabs=(150, 100, 120), train_steps=10,
                             train_batch=128, d_embed=8, mlp_hidden=(16,),
                             seed=4)


# ---------------------------------------------------------------------------
# bucket planner properties (lifecycle_props style: seeded sweeps, no drops)
# ---------------------------------------------------------------------------

def check_plan(owner, valid, n_shards, capacity):
    """Assert the BucketPlan contract over one (owner, valid) instance."""
    plan = shard.plan_buckets(jnp.asarray(owner), jnp.asarray(valid),
                              n_shards=n_shards, capacity=capacity)
    slot = np.asarray(plan.slot)
    inb = np.asarray(plan.in_bucket)
    spl = np.asarray(plan.spilled)
    counts = np.asarray(plan.counts)
    owner = np.asarray(owner)
    valid = np.asarray(valid)

    # no drop, no dup: every valid id is bucketed XOR spilled
    assert not (inb & spl).any()
    np.testing.assert_array_equal(inb | spl, valid)
    assert not (inb & ~valid).any() and not (spl & ~valid).any()

    o2 = owner.reshape(-1, owner.shape[-1])
    v2 = valid.reshape(-1, owner.shape[-1])
    i2 = inb.reshape(-1, owner.shape[-1])
    s2 = slot.reshape(-1, owner.shape[-1])
    c2 = counts.reshape(-1, n_shards)
    for sl in range(o2.shape[0]):
        # slots of bucketed ids are unique and land in the owner's bucket
        used = s2[sl][i2[sl]]
        assert len(set(used.tolist())) == len(used)
        np.testing.assert_array_equal(used // capacity, o2[sl][i2[sl]])
        # counts = raw per-bucket demand; occupancy = min(demand, capacity)
        for dest in range(n_shards):
            demand = int((v2[sl] & (o2[sl] == dest)).sum())
            assert c2[sl, dest] == demand
            got = int((i2[sl] & (o2[sl] == dest)).sum())
            assert got == min(demand, capacity)
    # total spill bounded by the static spill buffer
    per_slice_spill = spl.reshape(-1, owner.shape[-1]).sum(axis=-1)
    cap_bound = shard.spill_capacity(owner.shape[-1], capacity, n_shards)
    assert (per_slice_spill <= cap_bound).all()


def test_plan_buckets_properties_sweep():
    rng = np.random.default_rng(11)
    for _ in range(40):
        n_shards = int(rng.integers(2, 5))
        slice_len = int(rng.integers(1, 24))
        n_slices = int(rng.integers(1, 4))
        capacity = int(rng.integers(1, slice_len + 1))
        shape = (n_slices, slice_len) if n_slices > 1 else (slice_len,)
        owner = rng.integers(0, n_shards, size=shape).astype(np.int32)
        valid = rng.random(shape) < rng.choice([0.3, 0.8, 1.0])
        check_plan(owner, valid, n_shards, capacity)


def test_plan_buckets_all_one_owner_overflow():
    """Worst case: every id of a slice targets one shard at capacity 1 —
    all but the first spill, none drop."""
    owner = np.zeros((2, 9), np.int32)
    valid = np.ones((2, 9), bool)
    check_plan(owner, valid, 4, 1)
    plan = shard.plan_buckets(jnp.asarray(owner), jnp.asarray(valid),
                              n_shards=4, capacity=1)
    assert int(np.asarray(plan.in_bucket).sum()) == 2   # one per slice
    assert int(np.asarray(plan.spilled).sum()) == 16
    assert shard.spill_capacity(9, 1, 4) >= 8  # per-slice bound holds


def test_spill_capacity_bound():
    # per slice at most slice_len - capacity ids can overflow (the first
    # `capacity` of any bucket fit by construction)
    assert shard.spill_capacity(16, 16, 4) == 0
    assert shard.spill_capacity(16, 4, 4) == 4 * 12
    assert shard.spill_capacity(3, 8, 2) == 0  # capacity clamps at slice


# ---------------------------------------------------------------------------
# lookup parity: a2a vs psum vs single-device reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4), (2, 2)])
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("capacity", CAPACITIES)
@multidevice
def test_a2a_lookup_parity(mesh_shape, use_kernel, capacity, rng):
    table, meta = _random_packed_table()
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(24, 3)), jnp.int32)
    ref = np.asarray(jax.jit(
        lambda t, i: packed_lookup(t, meta, i))(table, ids))
    with use_mesh(_mesh(mesh_shape)):
        a2a = jax.jit(lambda t, i: shard.sharded_packed_lookup(
            t, meta, i, use_kernel=use_kernel, lookup_comms="a2a",
            bucket_capacity=capacity))(table, ids)
        psum = jax.jit(lambda t, i: shard.sharded_packed_lookup(
            t, meta, i, use_kernel=use_kernel))(table, ids)
    np.testing.assert_array_equal(np.asarray(a2a), ref)
    np.testing.assert_array_equal(np.asarray(psum), ref)


@pytest.mark.parametrize("capacity", CAPACITIES)
@multidevice
def test_a2a_lookup_parity_pod_mesh(capacity, rng):
    """1x2x2 ("pod", "data", "model") mesh: default rows over "model", and
    rows over the ("pod", "model") tuple via host_packed_table_pspecs —
    the multi-host layout, exercised with pod laid over local devices."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    table, meta = _random_packed_table(n=150, row_pad_multiple=1)
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(41,)), jnp.int32)
    ref = np.asarray(jax.jit(
        lambda t, i: packed_lookup(t, meta, i))(table, ids))
    for mesh_shape, rows_axes in [((1, 2, 2), ("model",)),
                                  ((2, 1, 2), ("pod", "model"))]:
        mesh = make_device_mesh(mesh_shape, ("pod", "data", "model"))
        with use_mesh(mesh):
            got = jax.jit(lambda t, i, _ra=rows_axes: shard.sharded_packed_lookup(
                t, meta, i, rows_axes=_ra, lookup_comms="a2a",
                bucket_capacity=capacity))(table, ids)
        np.testing.assert_array_equal(np.asarray(got), ref, err_msg=str(
            (mesh_shape, rows_axes)))


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@pytest.mark.parametrize("capacity", (None, 4, 1))
@multidevice
def test_tiered_a2a_parity(mesh_shape, capacity, rng):
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
            h, meta["bits"], meta["d"], i, lookup_comms="a2a",
            bucket_capacity=capacity))(store.hot, ids)
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_lookup_comms_validation(rng):
    table, meta = _random_packed_table()
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(8,)), jnp.int32)
    with pytest.raises(ValueError, match="lookup_comms"):
        shard.sharded_packed_lookup(table, meta, ids, lookup_comms="ring")
    with pytest.raises(ValueError, match="lookup_comms"):
        shard.sharded_tiered_hot_lookup({}, meta["bits"], meta["d"], ids,
                                        lookup_comms="ring")


def test_route_stats_deterministic(rng):
    table, meta = _random_packed_table()
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(64,)), jnp.int32)
    a = shard.lookup_route_stats(table, meta, ids, n_shards=4,
                                 bucket_capacity=4)
    b = shard.lookup_route_stats(table, meta, ids, n_shards=4,
                                 bucket_capacity=4)
    assert a == b
    assert a["routed"] == a["bucketed"] + a["spilled"]
    assert a["capacity"] == 4 and a["slice_len"] == 16
    full = shard.lookup_route_stats(table, meta, ids, n_shards=4)
    assert full["spilled"] == 0 and full["capacity"] == 16


# ---------------------------------------------------------------------------
# engine: fingerprint fork + zero recompiles on repeat shapes
# ---------------------------------------------------------------------------

@multidevice
def test_engine_a2a_parity_and_zero_recompile(served_model):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from repro.data.synthetic import SyntheticCTR
    from repro.launch.serve import build_engine
    cfg, params, state, buffers, spec, res = served_model
    ids = SyntheticCTR(spec._replace(batch_size=300)).batch(50_000)["ids"]

    ref_engine = build_engine(cfg, params, state, buffers, p99_rows=64,
                              bulk_rows=256, mesh=host_mesh(1, 1))
    ref = ref_engine.score(ids)

    engine = build_engine(cfg, params, state, buffers, p99_rows=64,
                          bulk_rows=256, mesh=_mesh((2, 2)),
                          lookup_comms="a2a", bucket_capacity=16)
    got = engine.score(ids)
    np.testing.assert_array_equal(got, ref)

    # repeat shape on a warm engine ⇒ zero recompiles
    n_compiles = engine.compile_count
    engine.score(ids)
    assert engine.compile_count == n_compiles
    assert engine.counters()["hits"] == 0


@multidevice
def test_lookup_comms_forks_cell_fingerprint(served_model):
    """psum and a2a cells of the same shape must not share a cache entry —
    ``lookup_comms``/``bucket_capacity`` are part of the cell meta."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from repro.models.dlrm import DLRM
    from repro.serve.cells import packed_score_cell
    cfg, params, state, buffers, spec, res = served_model
    mk = lambda comms, cap: packed_score_cell(  # noqa: E731
        DLRM, cfg, params, state, buffers, batch=64, arch="dlrm",
        shape="p99", lookup_comms=comms,
        bucket_capacity=cap)
    fps = {mk("psum", None).fingerprint, mk("a2a", None).fingerprint,
           mk("a2a", 8).fingerprint}
    assert len(fps) == 3


# ---------------------------------------------------------------------------
# sharded backward paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2), (4, 1)])
@multidevice
def test_embedding_bag_grad_parity(mesh_shape, rng):
    from repro.kernels.embedding_bag.ops import embedding_bag_kernel
    rows, d, B, L = 64, 8, 16, 6
    tab = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, rows, size=(B, L)).astype(np.int32))
    mask = jnp.asarray(rng.random((B, L)) < 0.8)

    def loss_ref(t):
        return jnp.sum(embedding_bag_kernel(t, ids, mask) ** 2)

    lr, gr = jax.jit(jax.value_and_grad(loss_ref))(tab)
    mesh = _mesh(mesh_shape)

    def loss_sh(t):
        return jnp.sum(
            shard.sharded_embedding_bag(t, ids, mask, mesh=mesh) ** 2)

    ls, gs = jax.jit(jax.value_and_grad(loss_sh))(tab)
    np.testing.assert_allclose(float(ls), float(lr), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_embedding_bag_psum_tolerance(mesh_shape, rng):
    """The documented ~1e-6 psum reassociation tolerance, pinned: when the
    row axis really splits, the sharded forward may differ from the
    single-device kernel only by reassociation of the bag sum — bounded at
    1e-6 absolute for O(1)-magnitude rows. A reduction-order change that
    drifts past this fails here instead of silently."""
    from repro.kernels.embedding_bag.ops import embedding_bag_kernel
    rows, d, B, L = 96, 16, 32, 8
    tab = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, rows, size=(B, L)).astype(np.int32))
    mask = jnp.asarray(rng.random((B, L)) < 0.9)
    ref = np.asarray(embedding_bag_kernel(tab, ids, mask))
    with use_mesh(_mesh(mesh_shape)):
        got = np.asarray(jax.jit(lambda t, i, m: shard.sharded_embedding_bag(
            t, i, m))(tab, ids, mask))
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
@multidevice
def test_flash_attention_grad_parity(mesh_shape, rng):
    """Sharded flash grads are bit-exact vs the unsharded kernel (the bwd
    kernel runs per-device on whole heads — no cross-shard reduction
    touches dq/dk/dv)."""
    from repro.kernels.flash_attention.ops import flash_attention_kernel
    B, S, H, hd = 4, 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
               for _ in range(3))

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_kernel(q, k, v, bq=16, bk=16) ** 2)

    vr, gr = jax.jit(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    mesh = _mesh(mesh_shape)

    def loss_sh(q, k, v):
        return jnp.sum(shard.sharded_flash_attention(
            q, k, v, bq=16, bk=16, mesh=mesh) ** 2)

    vs, gs = jax.jit(jax.value_and_grad(loss_sh, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(vs), float(vr), rtol=1e-5)
    for a, b in zip(gs, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# HLO attribution: the a2a cell moves bytes through all-to-all
# ---------------------------------------------------------------------------

@multidevice
def test_hlo_attributes_all_to_all(rng):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from repro.launch.hlo_analysis import analyze
    table, meta = _random_packed_table()
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(64,)), jnp.int32)
    mesh = _mesh((1, 4))

    def coll(comms, cap=None):
        jitted = jax.jit(lambda t, i: shard.sharded_packed_lookup(
            t, meta, i, mesh=mesh, lookup_comms=comms, bucket_capacity=cap))
        txt = jitted.lower(table, ids).compile().as_text()
        return analyze(txt)["collectives_per_device"]

    a2a = coll("a2a")
    assert "all-to-all" in a2a and a2a["all-to-all"]["bytes"] > 0
    assert a2a["all-to-all"]["count"] == 2  # ids out, packed words back
    psum = coll("psum")
    assert "all-to-all" not in psum
    # the headline claim: fewer collective bytes than the dense psum merge
    # at model-axis width 4 (d=12 f32 partials vs <=3-word packed rows)
    assert a2a["total_bytes"] < psum["total_bytes"]
    # forced overflow adds the integer spill psum but stays attributed
    spill = coll("a2a", cap=1)
    assert "all-reduce" in spill and spill["all-reduce"]["bytes"] > 0
