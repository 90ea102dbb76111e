"""Analysis corpus: the standard serving fleet, built tiny.

The trace-level passes need real cells to walk — a jaxpr checker with no
jaxprs audits nothing. Rather than invent synthetic cells (which would
drift from what production registers), the corpus builds the same fleet
``launch.serve`` ships, at toy sizes: the packed DLRM score cells with
their lookup-split companions, the tiered hot/cold cells over a
``TieredTableStore``, and the LM decode + continuous-batching decode
cells with int8 KV caches. ~10 cells covering every cell kind and every
shard_map wrapper in the repo.

Mesh policy mirrors the test suite: with ≥4 devices (the CI staticcheck
job sets ``--xla_force_host_platform_device_count=4`` before importing
jax) the corpus compiles on a 2×2 ``("data", "model")`` mesh, where the
lookups run as ``shard_map``s, so the SC204 and BC5xx passes see the real
lowerings; on a stock single-device CPU it degrades to the
1×1 host mesh (sharding no-ops, still full precision/recompile
coverage).

Registration AOT-compiles every cell (``CellCache``), so each
``RegisteredCell`` arrives with its HLO text for the collective-budget
pass; ``trace_cell`` re-traces the step closure for the jaxpr passes.
"""
from __future__ import annotations

import jax

from repro.dist.mesh import host_mesh, use_mesh

def budget_name(key) -> str:
    """budgets.json key for one cell: ``arch/shape@batch`` — stable across
    mesh-signature and static-config (fingerprint) churn, which move the
    ``CellKey`` but not the layout the budget bounds."""
    return f"{key.arch}/{key.shape.split('#')[0]}"


def corpus_mesh():
    """2×2 ``("data", "model")`` when ≥4 devices are visible, else the
    host mesh (1×1 on a stock CPU)."""
    if len(jax.devices()) >= 4:
        return host_mesh(n_data=2, n_model=2)
    return host_mesh()


def build_corpus(mesh=None, *, seed: int = 4):
    """Build and register the standard cell fleet at toy sizes.

    Returns the ``Engine``; walk ``engine.registered_cells()`` for the
    per-cell definitions + warm executables.
    """
    from repro.cache import TieredTableStore
    from repro.data.synthetic import SyntheticCTR
    from repro.launch.serve import build_engine, train_packed_dlrm
    from repro.models.lm import LM, LMConfig
    from repro.serve.cells import lm_decode_cell, lm_decode_slotted_cell

    mesh = mesh if mesh is not None else corpus_mesh()

    cfg, params, state, buffers, spec, res = train_packed_dlrm(
        field_vocabs=(150, 100, 120), train_steps=6, train_batch=128,
        d_embed=8, mlp_hidden=(16,), seed=seed)
    freqs = SyntheticCTR(spec).expected_frequencies()
    store = TieredTableStore(res["packed_table"], res["packed_meta"],
                             freqs, 0.3)
    engine = build_engine(cfg, params, state, buffers, p99_rows=64,
                          bulk_rows=256, store=store, mesh=mesh)
    if engine.mesh.size > 1:
        # a2a comms variants under their own shape names: BC501 budgets the
        # all-to-all id/word shuffle separately from (and below) the dense
        # psum merge of the plain cells (ISSUE 10 crossover)
        from repro.models.dlrm import DLRM
        engine.register_packed_model(
            "dlrm", DLRM, cfg, params, state, buffers,
            shapes={"serve_p99_a2a": 64}, lookup_split=False,
            lookup_comms="a2a", bucket_capacity=16)
        engine.register_tiered_model(
            "dlrm", DLRM, cfg, params, state, buffers, store,
            shapes={"tiered_p99_a2a": 64}, lookup_comms="a2a",
            bucket_capacity=16)

    lm_cfg = LMConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                      head_dim=16, d_ff=64, vocab=50, remat=False)
    lm_params, lm_buffers = LM.init(jax.random.PRNGKey(0), lm_cfg)
    engine.register(lm_decode_cell(lm_cfg, lm_params, lm_buffers,
                                   batch=4, max_len=8, arch="lm-tiny"))
    engine.register(lm_decode_slotted_cell(lm_cfg, lm_params, lm_buffers,
                                           batch=2, max_len=8,
                                           arch="lm-cb"))
    return engine


#: cell kinds whose traces carry packed/quantized table codes as int32 —
#: PF102 widens its narrow set for these (see repro.analysis.precision).
PACKED_KINDS = frozenset({"score", "lookup", "tiered_score"})


def is_packed(celldef) -> bool:
    return celldef.kind in PACKED_KINDS


def trace_cell(reg, mesh):
    """ClosedJaxpr of a registered cell's step over its compiled avals —
    same closure + arg specs ``CellCache.get_or_compile`` lowered, traced
    under the same mesh so shard_map bodies appear."""
    celldef = reg.celldef
    args = celldef.bound + celldef.request_specs
    # the fresh wrapper defeats make_jaxpr's trace cache (keyed on function
    # identity) — the RC304 double-trace check needs each call to really
    # re-run the Python closure
    step = celldef.step_fn
    with use_mesh(mesh):
        return jax.make_jaxpr(lambda *a: step(*a))(*args)
