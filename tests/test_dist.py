"""repro.dist contract tests: pspec families, no-op degradation on one
device, and a real NamedSharding round-trip on a simulated 4-device CPU mesh.

The multi-device case runs **in-process** when the session already has ≥ 4
devices (the blocking CI ``multidevice`` job sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — see
tests/conftest.py) and falls back to a subprocess otherwise:
``--xla_force_host_platform_device_count`` must be set before jax
initializes its backend, and a single-device pytest session has already
pinned it.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.inference import packed_specs
from repro.core.mpe import MPEConfig
from repro.dist import (current_dp_axes, dp_axes, host_mesh, lm_batch_pspecs,
                        lm_cache_pspecs, lm_param_pspecs, maybe_shard,
                        packed_table_pspecs, recsys_table_pspecs,
                        replicate_like, shard_batch_dim,
                        tree_named_shardings, use_mesh)
from repro.dist.sharding import normalize_entry

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# pspec families
# ---------------------------------------------------------------------------

def test_dp_axes():
    assert dp_axes(False) == ("data",)
    assert dp_axes(True) == ("pod", "data")


def test_lm_param_pspecs_fsdp_rule():
    params = {
        "layers": {
            "attn": {"wq": {"kernel": SDS((64, 5120, 8192), jnp.float32)}},
            "ln_attn": {"scale": SDS((64, 5120), jnp.float32)},
        },
        "lm_head": SDS((5120, 151936), jnp.float32),
        "ln_f": {"scale": SDS((5120,), jnp.float32)},
        "embedding": {"emb": SDS((151936, 5120), jnp.float32)},
    }
    ps = lm_param_pspecs(params, None)
    # 2-D+: last dim over "model", second-to-last over "data" when divisible
    assert ps["layers"]["attn"]["wq"]["kernel"] == P(None, "data", "model")
    assert ps["lm_head"] == P("data", "model")
    assert ps["embedding"]["emb"] == P("data", "model")
    # stacked norm scale: 64 % 16 == 0 so the layer axis FSDP-shards too
    assert ps["layers"]["ln_attn"]["scale"] == P("data", "model")
    # 1-D leaves replicate
    assert ps["ln_f"]["scale"] == P(None)


def test_lm_param_pspecs_indivisible_dims_replicate():
    ps = lm_param_pspecs({"w": SDS((24, 100), jnp.float32)}, None)
    assert ps["w"] == P(None, None)


def test_lm_batch_and_cache_pspecs():
    assert lm_batch_pspecs(False) == {"tokens": P(("data",), None),
                                      "labels": P(("data",), None)}
    cache = lm_cache_pspecs(long_context=False, multi_pod=False)
    assert cache["k"] == P(None, ("data",), "model", None, None)
    assert cache["v"] == cache["k"]
    assert cache["len"] == P()
    # cells.py derives scale pspecs from it; PartitionSpec stores a
    # one-axis tuple as the bare axis name
    assert normalize_entry(cache["k"][1]) == ("data",)
    long = lm_cache_pspecs(long_context=True, multi_pod=True)
    assert long["k"] == P(None, None, "model", None, None)  # B=1: no batch axis


def test_recsys_table_pspecs():
    rows = ("data", "model")
    ps = recsys_table_pspecs(rows)
    assert ps["emb"] == P(rows, None)
    assert ps["gamma"] == P(None, None)
    assert ps["alpha"] == P(None) and ps["beta"] == P(None)
    # structure-matching mode: unknown leaves get rank-matched replication
    sds = {"emb": SDS((4096, 16), jnp.float32), "extra": SDS((3, 3, 3), jnp.float32)}
    ps2 = recsys_table_pspecs(rows, sds)
    assert set(ps2) == {"emb", "extra"}
    assert ps2["extra"] == P(None, None, None)


def test_packed_table_pspecs_group_alignment():
    hist = (0.0, 0.30, 0.20, 0.20, 0.10, 0.10, 0.10)
    sds = packed_specs(100_000, 16, MPEConfig(), hist)
    ps = packed_table_pspecs(sds, rows_axes=("data", "model"))
    for name, sub in sds["subtables"].items():
        assert ps["subtables"][name] == P(("data", "model"), None)
        # row shards stay aligned to the 512-row padding groups, so a packed
        # row (whose codes straddle uint32 word boundaries) never splits
        assert sub.shape[0] % 512 == 0
    for k in ("local_idx", "width_idx", "alpha", "beta"):
        assert ps[k] == P(None)


def test_replicate_like_preserves_structure():
    tree = {"a": {"b": jnp.zeros((2, 3)), "c": jnp.zeros(())},
            "d": [jnp.zeros((4,)), jnp.zeros((1, 2, 3))]}
    ps = replicate_like(tree)
    assert jax.tree.structure(ps, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(tree)
    assert ps["a"]["b"] == P(None, None)
    assert ps["a"]["c"] == P()
    assert ps["d"][1] == P(None, None, None)


# ---------------------------------------------------------------------------
# single-device degradation
# ---------------------------------------------------------------------------

def test_noop_without_mesh():
    x = jnp.ones((8, 4))
    assert current_dp_axes() is None
    assert shard_batch_dim(x) is x
    assert maybe_shard(x, P("data", None)) is x


def test_noop_on_single_device_mesh():
    mesh = host_mesh(n_data=1, n_model=1)
    with use_mesh(mesh):
        x = jnp.ones((8, 4))
        assert current_dp_axes() is None
        assert shard_batch_dim(x) is x


def test_tree_named_shardings_on_host_mesh():
    mesh = host_mesh()
    tree = {"emb": P("data", None), "alpha": P(None), "opt": {"step": P()}}
    ns = tree_named_shardings(mesh, tree)
    assert ns["emb"].mesh == mesh and ns["emb"].spec == P("data", None)
    assert ns["opt"]["step"].spec == P()
    # a pspec-typed tree maps leaf-for-leaf (P must be treated as a leaf)
    assert jax.tree.structure(
        ns, is_leaf=lambda x: hasattr(x, "spec")).num_leaves == 3


# ---------------------------------------------------------------------------
# simulated 4-device mesh (in-process under the multidevice marker; a
# subprocess fallback keeps single-device sessions covered)
# ---------------------------------------------------------------------------

def _four_device_round_trip_checks():
    """The 4-device NamedSharding round-trip — shared by the in-process
    ``multidevice`` test and the single-device subprocess fallback."""
    import numpy as np
    from repro.dist import (current_dp_axes, make_device_mesh, maybe_shard,
                            shard_batch_dim, tree_named_shardings, use_mesh)

    assert jax.device_count() >= 4, jax.devices()
    mesh = make_device_mesh((2, 2), ("data", "model"))

    # round-trip: place a pytree with tree_named_shardings, read it back
    tree = {"emb": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "alpha": jnp.arange(7, dtype=jnp.float32),
            "opt": {"step": jnp.zeros((), jnp.int32)}}
    pspecs = {"emb": P(("data", "model"), None), "alpha": P(None),
              "opt": {"step": P()}}
    shardings = tree_named_shardings(mesh, pspecs)
    placed = jax.tree.map(jax.device_put, tree, shardings)
    assert placed["emb"].sharding.spec == P(("data", "model"), None)
    assert len({s.data.tobytes() for s in placed["emb"].addressable_shards}) == 4
    for k in tree:
        np.testing.assert_array_equal(np.asarray(jax.tree.leaves(placed[k])[0]),
                                      np.asarray(jax.tree.leaves(tree[k])[0]))

    # maybe_shard applies a real constraint under the mesh...
    with use_mesh(mesh):
        assert current_dp_axes() == ("data",)
        out = jax.jit(lambda x: shard_batch_dim(x) * 2)(jnp.ones((8, 4)))
        assert out.sharding.spec[0] in (("data",), "data"), out.sharding
        # ...but skips axes the array can't divide (batch 3 on 2-way data)
        odd = jax.jit(lambda x: shard_batch_dim(x) * 2)(jnp.ones((3, 4)))
        np.testing.assert_array_equal(np.asarray(odd), 2.0)
    # ...and degrades to identity outside it
    x = jnp.ones((8, 4))
    assert maybe_shard(x, P("data", None)) is x


@pytest.mark.multidevice
def test_four_device_round_trip_in_process():
    _four_device_round_trip_checks()


_FALLBACK_SCRIPT = """
import test_dist
test_dist._four_device_round_trip_checks()
print("4-device dist round-trip OK")
"""


def subprocess_env_4dev():
    """Env for a 4-virtual-device child: src + tests on the path, XLA flag
    set before the child's jax initializes its backend."""
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.abspath(os.path.join(here, os.pardir, "src"))
    env["PYTHONPATH"] = os.pathsep.join(
        [src, here] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_four_device_round_trip_subprocess():
    if jax.device_count() >= 4:
        pytest.skip("in-process multidevice test covers this session")
    proc = subprocess.run([sys.executable, "-c", _FALLBACK_SCRIPT],
                          env=subprocess_env_4dev(), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "4-device dist round-trip OK" in proc.stdout


# ---------------------------------------------------------------------------
# three-axis ("pod", "data", "model") mesh — the multi-pod CLI layout.
# parse_mesh_flag accepts 'pod,dp,mp'; the shard wrappers are axis-generic,
# so the sharded lookup must stay bit-exact on the 1x2x2 mesh too.
# ---------------------------------------------------------------------------

def test_parse_mesh_flag_rejects_garbage():
    from repro.dist.mesh import parse_mesh_flag
    assert parse_mesh_flag(None) is None
    assert parse_mesh_flag("") is None
    for bad in ("2", "2,2,2,2", "a,b", "2;2"):
        with pytest.raises(SystemExit):
            parse_mesh_flag(bad)


def _pod_mesh_checks():
    """1x2x2 ("pod", "data", "model") mesh drive — shared by the in-process
    ``multidevice`` test and the single-device subprocess fallback."""
    import numpy as np
    from repro.core.inference import build_packed_table, packed_lookup
    from repro.core.mpe import MPEConfig
    from repro.dist import shard
    from repro.dist.mesh import parse_mesh_flag

    assert jax.device_count() >= 4, jax.devices()
    mesh = parse_mesh_flag("1,2,2")
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.devices.shape == (1, 2, 2)

    rng = __import__("numpy").random.default_rng(0)
    cfg = MPEConfig()
    emb = rng.normal(size=(160, 12)).astype(np.float32)
    fbits = rng.integers(0, len(cfg.bits), size=160).astype(np.int32)
    alpha = (np.abs(rng.normal(size=len(cfg.bits))) * 0.1 + 0.01).astype(
        np.float32)
    beta = (rng.normal(size=12) * 0.01).astype(np.float32)
    table, meta = build_packed_table(emb, fbits, alpha, beta, cfg)
    ids = jnp.asarray(rng.integers(0, meta["n"], size=(24, 3)), jnp.int32)
    ref = np.asarray(jax.jit(lambda t, i: packed_lookup(t, meta, i))(table,
                                                                     ids))
    with use_mesh(mesh):
        # batch axes of the pod mesh are every non-"model" axis
        assert current_dp_axes() == ("pod", "data")
        got = jax.jit(lambda t, i: shard.sharded_packed_lookup(t, meta, i))(
            table, ids)
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.multidevice
def test_pod_mesh_in_process():
    _pod_mesh_checks()


_POD_FALLBACK_SCRIPT = """
import test_dist
test_dist._pod_mesh_checks()
print("1x2x2 pod-mesh drive OK")
"""


def test_pod_mesh_subprocess():
    if jax.device_count() >= 4:
        pytest.skip("in-process multidevice test covers this session")
    proc = subprocess.run([sys.executable, "-c", _POD_FALLBACK_SCRIPT],
                          env=subprocess_env_4dev(), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "1x2x2 pod-mesh drive OK" in proc.stdout
