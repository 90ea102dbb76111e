"""Partition-spec contract for every workload family on the production mesh.

Mesh axes: ``("data", "model")`` single-pod (16×16 TPU v5e), with a leading
``"pod"`` axis (2×16×16) multi-pod. Three pspec families:

  LM       — params FSDP-style (last dim over "model", second-to-last over
             "data"); token batches over the data axes; KV caches with the
             sequence dim over "model" (batch over data when batch > 1).
  recsys   — (n, d) embedding tables row-sharded over ``rows_axes`` (vocab
             rows are the dominant bytes); γ/α/β side params and the MLP
             stay replicated.
  MPE pack — one bit-packed uint32 subtable per candidate width, each
             row-sharded over ``rows_axes``. Rows are padded to multiples of
             512 (``core.inference._pad_rows``), so row shards stay aligned
             to the packed-row groups of ``core/packing.py`` — the uint32
             words of one embedding row never split across devices (codes
             straddle word boundaries; a row is only decodable whole).

In-model helpers (``maybe_shard``, ``shard_batch_dim``, ``current_dp_axes``)
read the registry in ``repro.dist.mesh`` at trace time and degrade to no-ops
when no mesh (or a single-device mesh) is active, so the same model code runs
unmodified in 1-device tests and 512-chip dry-runs.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.mesh import current_mesh

# Production axis sizes (launch/mesh.py): only dims divisible by these are
# assigned a mesh axis — everything else stays replicated, which keeps every
# pspec valid on any submesh (1×1 test mesh included).
PROD_AXIS_SIZE = {"pod": 2, "data": 16, "model": 16}

# ---------------------------------------------------------------------------
# machine-readable spec contract (read by repro.analysis.shardspec)
# ---------------------------------------------------------------------------

#: Every mesh axis any pspec family may name. A spec entry naming an axis
#: outside this set can never resolve on a production mesh (SC201).
MESH_AXES = frozenset(PROD_AXIS_SIZE)

#: The axis *groups* a single pspec dim may combine, normalized to tuples in
#: mesh order. ``("pod", "data")`` is the multi-pod batch dim;
#: ``("data", "model")`` / ("pod","data","model") are the every-axis row
#: splits of ``sharded_mixed_expectation``; ``("pod", "model")`` is the
#: cross-host table-row split of ``host_packed_table_pspecs`` (pod-major:
#: host boundaries outermost, so a shard's neighbours along "model" stay
#: host-local); singletons are the common case. A dim entry outside this
#: family is out of contract (SC202) — e.g. ``("model", "data")`` (wrong
#: order ⇒ wrong row-major shard index) or an ad-hoc axis pairing no
#: wrapper produces.
AXIS_GROUPS = frozenset({
    ("pod",), ("data",), ("model",),
    ("pod", "data"), ("pod", "model"), ("data", "model"),
    ("pod", "data", "model"),
})

#: name → builder for every pspec family below; ``repro.analysis`` resolves
#: cell/wrapper specs against this registry (a spec is in contract when each
#: of its dim entries normalizes into AXIS_GROUPS — the families themselves
#: only ever emit such entries).
SPEC_FAMILIES = {}


def _family(fn):
    SPEC_FAMILIES[fn.__name__] = fn
    return fn


def normalize_entry(entry) -> tuple[str, ...] | None:
    """One PartitionSpec dim entry → tuple-of-axes (None stays None).

    ``P("data")`` and ``P(("data",))`` are the same placement; the analysis
    passes compare normalized entries against ``AXIS_GROUPS``."""
    if entry is None:
        return None
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def spec_in_contract(spec) -> bool:
    """True when every dim entry of ``spec`` is a registered axis group."""
    for entry in tuple(spec):
        norm = normalize_entry(entry)
        if norm is not None and norm not in AXIS_GROUPS:
            return False
    return True


def dp_axes(multi_pod: bool = False) -> tuple[str, ...]:
    """The data-parallel (batch) axes of the production mesh."""
    return ("pod", "data") if multi_pod else ("data",)


def current_dp_axes() -> tuple[str, ...] | None:
    """Batch axes of the active mesh, or None when sharding is a no-op."""
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return None
    dp = tuple(n for n in mesh.axis_names if n != "model")
    return dp or None


# ---------------------------------------------------------------------------
# constraint helpers (trace-time no-ops without a multi-device mesh)
# ---------------------------------------------------------------------------

def _axes_size(mesh, entry) -> int:
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def _fit_spec(shape, spec, mesh):
    """Drop pspec entries whose axes are unknown to ``mesh`` or don't divide
    the dim — a constraint we can't honor cleanly becomes "replicated"."""
    fitted = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            fitted.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        if not all(n in mesh.shape for n in names):
            fitted.append(None)
            continue
        fitted.append(entry if dim % _axes_size(mesh, entry) == 0 else None)
    return P(*fitted)


def maybe_shard(x, spec: P):
    """``with_sharding_constraint`` against the active mesh; identity when no
    multi-device mesh is installed or the spec doesn't fit ``x``."""
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return x
    fitted = _fit_spec(x.shape, spec, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


def shard_batch_dim(x, axis: int = 0):
    """Pin ``x``'s batch dim to the data axes (other dims replicated)."""
    dp = current_dp_axes()
    if dp is None:
        return x
    entries = [None] * x.ndim
    entries[axis] = dp
    return maybe_shard(x, P(*entries))


# ---------------------------------------------------------------------------
# tree utilities
# ---------------------------------------------------------------------------

def _is_pspec(x) -> bool:
    return isinstance(x, P)


def tree_named_shardings(mesh, pspec_tree):
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``."""
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps), pspec_tree,
                        is_leaf=_is_pspec)


def replicate_like(tree):
    """Rank-matched fully-replicated pspecs for every leaf of ``tree``."""
    return jax.tree.map(lambda x: P(*([None] * x.ndim)), tree)


def cell_shardings(mesh, cell):
    """(in_shardings, out_shardings) NamedShardings for a launch cell."""
    ins = tuple(tree_named_shardings(mesh, ps) for ps in cell.in_pspecs)
    outs = tree_named_shardings(mesh, cell.out_pspecs)
    return ins, outs


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _fsdp_leaf_spec(leaf) -> P:
    """FSDP-style storage spec: last dim over "model", second-to-last over
    "data" — assigned only when the production axis size divides the dim.
    1-D leaves (norm scales, biases) and scalars stay replicated."""
    nd = leaf.ndim
    if nd < 2:
        return P(*([None] * nd))
    entries = [None] * nd
    if leaf.shape[-1] % PROD_AXIS_SIZE["model"] == 0:
        entries[-1] = "model"
    if leaf.shape[-2] % PROD_AXIS_SIZE["data"] == 0:
        entries[-2] = "data"
    return P(*entries)


@_family
def lm_param_pspecs(params_sds, cfg=None):
    """Pspecs matching the LM param tree (stacked-layer leaves included).

    Weights live FSDP-sharded in HBM; ``LM._gather_fsdp_weights`` re-pins
    them to "model"-only layouts inside the scan body at apply time, so this
    only fixes the at-rest placement. ``cfg`` is accepted for call-site
    stability (expert layout already falls out of the generic rule).
    """
    del cfg
    return jax.tree.map(_fsdp_leaf_spec, params_sds)


@_family
def lm_logits_pspecs(batch: int, *, vocab_sharded: bool = False, dp=None,
                     multi_pod: bool = False) -> P:
    """Logits ``(B, V)`` of a prefill/decode step.

    Batched steps shard the batch over the data axes (``dp`` overrides the
    production ``dp_axes`` for cells compiled against a custom data tuple)
    with the vocab dim optionally over "model" (prefill keeps it sharded —
    the ``lm_head`` matmul output layout); a ``batch == 1`` step has nothing
    to split on the data axes, so the vocab dim takes "model" instead. The
    serve/launch decode cells previously hand-rolled this split at four call
    sites — staticcheck SC202 now pins them here."""
    if batch > 1:
        axes = tuple(dp) if dp is not None else dp_axes(multi_pod)
        return P(axes, "model" if vocab_sharded else None)
    return P(None, "model")


@_family
def lm_batch_pspecs(multi_pod: bool = False):
    """{"tokens", "labels"}: (B, S) int32, batch over the data axes."""
    dp = dp_axes(multi_pod)
    return {"tokens": P(dp, None), "labels": P(dp, None)}


@_family
def lm_cache_pspecs(*, long_context: bool = False, multi_pod: bool = False):
    """Stacked KV caches {"k","v": (L, B, T, n_kv, hd), "len": ()}.

    The cache-length dim T shards over "model" (always mesh-divisible at the
    assigned shapes; kv-head counts are not). Batch shards over the data axes
    except in the long-context cell (B=1 — nothing to split)."""
    batch_ax = None if long_context else dp_axes(multi_pod)
    kv = P(None, batch_ax, "model", None, None)
    return {"k": kv, "v": kv, "len": P()}


@_family
def lm_kv_cache_pspecs(*, quantized: bool = False, long_context: bool = False,
                       multi_pod: bool = False):
    """``lm_cache_pspecs`` plus the int8 per-(layer, batch, head) scale
    entries {"k_scale","v_scale": (L, B, 1, n_kv, 1)} when ``quantized``.

    Scales shard with the cache batch axis only — the T and head dims are
    size-1/ungathered, so everything else replicates."""
    ps = lm_cache_pspecs(long_context=long_context, multi_pod=multi_pod)
    if quantized:
        scale_ps = P(None, ps["k"][1], None, None, None)
        ps = dict(ps, k_scale=scale_ps, v_scale=scale_ps)
    return ps


# ---------------------------------------------------------------------------
# recsys embedding tables (search/train phase)
# ---------------------------------------------------------------------------

@_family
def recsys_table_pspecs(rows_axes, emb_sds=None):
    """MPE search-phase embedding params: the (n, d) table row-shards over
    ``rows_axes``; γ is (n/group_size, m) — not generally mesh-divisible and
    7 floats per group — so it and α/β replicate.

    With ``emb_sds`` (a param dict from any compressor), unknown leaves get
    rank-matched replicated specs so the tree structures always align."""
    base = {"emb": P(rows_axes, None), "gamma": P(None, None),
            "alpha": P(None), "beta": P(None)}
    if emb_sds is None:
        return base
    return {k: base[k] if k in base else P(*([None] * v.ndim))
            for k, v in emb_sds.items()}


# ---------------------------------------------------------------------------
# MPE packed serving tables
# ---------------------------------------------------------------------------

@_family
def packed_table_pspecs(table_sds, *, rows_axes=("model",)):
    """Pspecs for a packed inference table (core/inference.py layout).

    Each per-width subtable (rows, words_per_row) row-shards over
    ``rows_axes``; production-scale subtables pad their rows to multiples of
    512 (``core.inference._auto_pad_multiple``), which every production axis
    combination divides, so shard boundaries always land on whole packed
    rows. Small tables pad to a smaller power of two and simply replicate
    (``maybe_shard`` drops non-dividing axes). The word dim is never split
    (a row's codes straddle word boundaries). The id→(bucket, local row)
    index vectors are gathered by every device and replicate, as do the
    dequant params α/β."""
    return {
        "subtables": {k: P(rows_axes, None) for k in table_sds["subtables"]},
        "local_idx": P(None),
        "width_idx": P(None),
        "alpha": P(None),
        "beta": P(None),
    }


@_family
def host_packed_table_pspecs(table_sds, *, rows_axes=("pod", "model")):
    """Multi-host layout for a packed inference table: subtable rows shard
    over ``("pod", "model")`` — the vocab split that fits on no single host.

    The "pod" axis sits on host boundaries (``mesh.host_boundary_groups`` /
    ``host_mesh(n_pod=...)``), so the row-major shard index of
    ``rows_shard_index`` walks hosts outermost: one host owns a contiguous
    row range and its "model"-axis neighbours are host-local, which keeps
    the capacity-bucketed all-to-all's dense peer traffic on-host and only
    the pod hop cross-host. Everything else matches
    ``packed_table_pspecs``: the word dim never splits, the id→(bucket,
    row) vectors and α/β replicate (every host resolves every id)."""
    return packed_table_pspecs(table_sds, rows_axes=tuple(rows_axes))


@_family
def tiered_hot_pspecs(hot_sds, *, rows_axes=("model",)):
    """Pspecs for the **hot tier** of a ``cache.tiers.TieredTableStore``.

    The hot tier is the device-resident half of the hot/cold split and the
    only half that ever sees the mesh — the cold tier lives in host memory
    and reaches devices per request as already-placed ``device_put`` fills.
    Hot subtables row-shard over ``rows_axes`` exactly like the monolithic
    ``packed_table_pspecs`` layout (rows padded to the same multiples, so
    shard boundaries land on whole packed rows); the id→(tier, local row)
    routing vectors and the dequant params replicate, as every device
    resolves every id."""
    return {
        "subtables": {k: P(rows_axes, None) for k in hot_sds["subtables"]},
        "tier_local": P(None),
        "is_hot": P(None),
        "width_idx": P(None),
        "alpha": P(None),
        "beta": P(None),
    }


@_family
def packed_serve_pspecs(params, *, rows_axes=("model",),
                        row_keys=("wide", "fm_linear")):
    """Full param-tree pspecs for a model serving from a packed table.

    ``params["embedding"]`` gets the packed-table layout above; per-feature
    1-D vectors named in ``row_keys`` (wide & deep's linear term, DeepFM's
    first-order weights) row-shard with the vocab; everything else — MLP,
    cross layers, towers — replicates. Used by both the dry-run serve cells
    (``launch/cells.py``) and the live engine (``repro.serve``)."""
    pspecs = {k: replicate_like(v) for k, v in params.items()
              if k != "embedding"}
    pspecs["embedding"] = packed_table_pspecs(params["embedding"],
                                              rows_axes=rows_axes)
    for k in row_keys:
        if k in params:
            pspecs[k] = P(rows_axes)
    return pspecs
