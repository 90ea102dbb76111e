"""shard_map layer: run the fused kernels and cells *inside* the partitioner.

``pjit`` slices a computation after the fact; ``shard_map`` places it — each
device runs the body on its local block and every cross-device byte is an
explicit collective. This module is the bridge between the Pallas kernels
(written against local arrays) and the ``("data", "model")`` mesh contract of
``repro.dist.sharding``: every wrapper derives its in/out specs from the
pspec families (``packed_table_pspecs``, ``tiered_hot_pspecs``,
``recsys_table_pspecs``) and degrades to the single-device path when no
multi-device mesh is active, so the same call site serves 1-CPU tests and a
real mesh.

Placement per wrapper:

  ``sharded_packed_lookup``    one lookup, with an optional hot mask: the
  ``sharded_tiered_hot_lookup``  packed table's subtables (or a
                               ``cache.tiers.TieredTableStore`` hot tier's,
                               keeping hot ids only — zeros at cold
                               positions, merged by the caller) row-sharded
                               over ``rows_axes`` ("model"), ids
                               batch-sharded over the data axes. Each device
                               decodes with ``core.inference``'s routine,
                               told which row shard it owns. Two comms
                               paths, selected by ``lookup_comms``:
                               ``"psum"`` (default) merges the device-local
                               results with ONE ``psum`` over the row axes —
                               each id owns exactly one (bucket, row), so
                               the psum adds one non-zero term to zeros,
                               bit-exact against the jitted single-device
                               reference. ``"a2a"`` ships only the *packed
                               uint32 words*: a capacity-bucketed
                               ``all_to_all`` id shuffle (``plan_buckets``)
                               routes each id to its owner shard, the owner
                               gathers the packed row, a second
                               ``all_to_all`` returns the words and the
                               *requesting* shard decodes them — ~32/b×
                               fewer bytes than psum-ing the dequantized
                               (batch, d) f32 activation when the row axes
                               are wide. Ids that overflow a bucket
                               deterministically spill to a masked integer
                               psum of the same packed words, so the a2a
                               path is bit-exact at ANY capacity (nothing is
                               dropped; see ``plan_buckets``).
  ``sharded_embedding_bag``    table rows over ``rows_axes``, bags over the
                               data axes; per-device partial bag sums +
                               psum. Differentiable: a ``custom_vjp`` runs
                               the backward as a per-device ``segment_sum``
                               of the owned slot cotangents into the local
                               row block (psum-merged over the batch axes
                               when the bags are split). NOT bit-exact for
                               >1 row shard (the psum reassociates the bag
                               sum) — documented tolerance ~1e-6 relative,
                               pinned by tests/test_shard_a2a.py.
  ``sharded_flash_attention``  batch over the data axes, heads over
                               "model"; no collectives, bit-exact.
                               Differentiable: a ``custom_vjp`` runs the
                               fused fwd-stats/bwd Pallas kernels in their
                               own shard_maps with the (o, lse) residuals
                               stored sharded.
  ``sharded_mixed_expectation`` rows over every mesh axis (row-parallel
                               QAT); forward collective-free and bit-exact,
                               backward one psum of the replicated α's and
                               β's cotangents.
  ``sharded_value_and_grad``   the train step's grad: batch data-parallel
                               over the mesh, embedding-table leaves stored
                               row-sharded over ``rows_axes`` (specs from
                               ``recsys_table_pspecs``) and all-gathered in
                               the body; autodiff transposes the gather into
                               a psum-scatter, so table grads arrive
                               row-shard-local while replicated MLP/side
                               params get a ``pmean`` over the batch axes.

Tables whose rows don't divide the row-axis size are padded up to the next
multiple (``pad_rows_to_shard``) — pad rows carry zero words and are never
owned by a real id, so they change no result (the pad-to-shard path).

Call the wrappers from traced code (under ``jax.jit`` — the serve cells and
the train step always are): eagerly-executed ``shard_map`` on jax 0.4.37
reassembles replicated outputs incorrectly for some mesh shapes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import packing
from repro.core.inference import _lookup_rows
from repro.dist.mesh import current_mesh
from repro.dist.sharding import replicate_like

__all__ = [
    "active_mesh", "pad_rows_to_shard", "rows_shard_index",
    "LOOKUP_COMMS", "BucketPlan", "plan_buckets", "spill_capacity",
    "lookup_route_stats",
    "sharded_packed_lookup", "sharded_tiered_hot_lookup",
    "sharded_embedding_bag", "sharded_flash_attention",
    "sharded_mixed_expectation", "sharded_value_and_grad",
]


# ---------------------------------------------------------------------------
# mesh plumbing
# ---------------------------------------------------------------------------

def active_mesh(mesh=None):
    """``mesh`` or the registry's current mesh — None when sharding is a
    no-op (no mesh, or a 1-device mesh)."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None or mesh.size <= 1:
        return None
    return mesh


def _present_axes(mesh, axes) -> tuple[str, ...]:
    return tuple(a for a in axes if a in mesh.shape)


def _axes_size(mesh, axes) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _dp_axes_of(mesh, rows_axes) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a not in rows_axes)


def _batch_entry(mesh, dim: int, axes) -> tuple[str, ...] | None:
    """The pspec entry for a batch dim: ``axes`` when they divide it, else
    replicated (mirrors ``sharding._fit_spec``)."""
    if axes and dim % _axes_size(mesh, axes) == 0:
        return tuple(axes)
    return None


def pad_rows_to_shard(x, n_shards: int):
    """Pad dim 0 up to a multiple of ``n_shards`` with zeros (the
    pad-to-shard path for tables whose rows don't divide the row axes).
    Zero packed words decode to the most-negative code, but pad rows are
    never *owned* by a real id, so no result can read them.

    Implemented with ``jnp.pad``, NOT ``jnp.concatenate``: on jax 0.4.37 the
    SPMD partitioner mis-lowers an uneven concatenate that feeds a
    ``shard_map`` row-sharded operand (wrong rows reach the shards on a 2×2
    mesh — see tests/test_shard.py::test_packed_lookup_pad_to_shard_edge,
    which fails with the concatenate formulation)."""
    pad = (-x.shape[0]) % n_shards
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def rows_shard_index(mesh, rows_axes):
    """Linear shard index of this device along ``rows_axes`` (row-major over
    the axes tuple, matching ``PartitionSpec((a, b), ...)`` layout). Call
    inside a ``shard_map`` body."""
    idx = jnp.int32(0)
    for a in rows_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# capacity-bucketed all-to-all routing plan
# ---------------------------------------------------------------------------

#: Comms paths for the sharded lookups: "psum" merges dequantized partials
#: with one float psum; "a2a" ships the packed words through two all_to_alls
#: (+ an integer spill psum) and dequantizes on the requesting shard.
LOOKUP_COMMS = ("psum", "a2a")


class BucketPlan(NamedTuple):
    """Static-shape routing plan for the capacity-bucketed all-to-all.

    ``slot``/``in_bucket``/``spilled`` share ``owner``'s shape, with the
    second-to-last axis enumerating the ids of one batch slice: ``slot`` is
    the flat position in the (n_shards × capacity) send buffer
    (``owner * capacity + rank`` within the (slice, owner) bucket);
    ``in_bucket`` marks ids that fit under the capacity; ``spilled`` marks
    valid ids that overflowed — the lookup merges those through the integer
    psum spill path instead of dropping them. ``counts`` replaces the id
    axis with an ``n_shards`` axis: the total per-bucket demand (occupancy
    is ``min(counts, capacity)``). The plan is a pure function of
    ``(owner, valid)``, so every device derives the identical plan from
    replicated inputs — that determinism is what lets the spill psum write
    each overflow row from exactly one owner."""
    slot: jnp.ndarray
    in_bucket: jnp.ndarray
    spilled: jnp.ndarray
    counts: jnp.ndarray


def plan_buckets(owner, valid, *, n_shards: int, capacity: int) -> BucketPlan:
    """Plan per-destination-shard buckets under a static ``capacity``.

    ``owner[..., j]`` is the shard that holds id j's row; ``valid`` masks
    the ids that participate (batch padding and zero-width/cold ids don't).
    Rank within a bucket is the id's order of appearance in its slice, so
    the plan — and therefore which ids spill — is deterministic."""
    owner = jnp.asarray(owner, jnp.int32)
    valid = jnp.asarray(valid, bool)
    oc = jnp.clip(owner, 0, n_shards - 1)
    onehot = (oc[..., None] == jnp.arange(n_shards, dtype=jnp.int32)) \
        & valid[..., None]
    cum = jnp.cumsum(onehot.astype(jnp.int32), axis=-2)
    rank = jnp.take_along_axis(cum, oc[..., None], axis=-1)[..., 0] - 1
    in_bucket = valid & (rank < capacity)
    return BucketPlan(slot=(oc * capacity + rank).astype(jnp.int32),
                      in_bucket=in_bucket,
                      spilled=valid & ~in_bucket,
                      counts=onehot.sum(axis=-2).astype(jnp.int32))


def spill_capacity(slice_len: int, capacity: int, n_shards: int) -> int:
    """Static row count of the overflow spill buffer.

    One slice of ``slice_len`` ids spills at most ``slice_len - capacity``:
    summing ``max(0, count_o - capacity)`` over the owners with overflow
    gives ``sum(count_o) - |overflowing| * capacity <= slice_len -
    capacity``. ``n_shards`` slices therefore always fit."""
    return n_shards * max(0, slice_len - capacity)


def _route_plan(flat, width_idx, local_idx, shard_rows, bits, *,
                n_shards: int, capacity, keep=None):
    """The a2a routing plan of one id batch, shared by the lookup body and
    ``lookup_route_stats``: pad the batch to ``n_shards`` equal slices, find
    each id's owner shard (``shard_rows[i]`` rows of width ``i`` per shard,
    the ``pad_rows_to_shard``-ed count), route the ids in the batch with a
    non-zero width (and ``keep``, where given) and bucket them under
    ``capacity``, clamped to the slice (None: statically spill-free).
    Returns ``(padded ids, widx, lidx, route, plan, clamped capacity)``."""
    batch = flat.shape[0]
    slice_len = -(-batch // n_shards)
    cap = slice_len if capacity is None else \
        max(1, min(int(capacity), slice_len))
    bp = n_shards * slice_len
    fl_p = jnp.pad(flat, (0, bp - batch))
    widx = jnp.take(width_idx, fl_p, axis=0)
    lidx = jnp.take(local_idx, fl_p, axis=0)
    nz = jnp.asarray([b != 0 for b in bits])
    route = (jnp.arange(bp) < batch) & jnp.take(nz, widx, axis=0)
    if keep is not None:
        route = route & jnp.take(keep, fl_p, axis=0)
    owner = jnp.clip(
        lidx // jnp.take(jnp.asarray(shard_rows, jnp.int32), widx, axis=0),
        0, n_shards - 1)
    plan = plan_buckets(owner.reshape(n_shards, slice_len),
                        route.reshape(n_shards, slice_len),
                        n_shards=n_shards, capacity=cap)
    return fl_p, widx, lidx, route, plan, cap


def _route_words(subs, widths, widx, lidx, shard, n_words, mask=None):
    """Packed words of the locally-owned rows among ``(widx, lidx)``,
    zero-padded to ``n_words`` columns → (words, owned). Positions this
    shard doesn't own (or ``mask`` excludes) stay zero."""
    n = widx.shape[0]
    words = jnp.zeros((n, n_words), jnp.uint32)
    owned = jnp.zeros((n,), bool)
    for i, b in widths:
        sub = subs[f"b{b}"]
        rows_loc = sub.shape[0]
        loc = lidx - shard * rows_loc
        own = (widx == i) & (loc >= 0) & (loc < rows_loc)
        if mask is not None:
            own = own & mask
        w = jnp.take(sub, jnp.clip(loc, 0, rows_loc - 1), axis=0)
        w = jnp.pad(w, ((0, 0), (0, n_words - w.shape[1])))
        words = jnp.where(own[:, None], w, words)
        owned = owned | own
    return words, owned


def _a2a_lookup(subs, vecs, fl, *, mesh, rows_ax, bits, d, capacity,
                use_kernel):
    """Body of the capacity-bucketed all-to-all lookup (inside shard_map).

    The ids are replicated along ``rows_ax`` (they enter sharded over the
    batch axes only), so shard s takes ownership of batch slice s and every
    device computes the identical replicated ``plan_buckets`` plan. Steps:

      1. all_to_all the bucketed ids (static shape (n_shards, capacity));
      2. the owner gathers the packed uint32 words of its rows;
      3. all_to_all the words back; the requester collects its slice and an
         ``all_gather`` rebuilds the full (batch, words) array;
      4. overflowed ids merge through ONE masked integer psum of a static
         ``spill_capacity``-row buffer — exact (each row has one writer);
      5. the requesting shard decodes the words with ``core.inference``'s
         lookup routine, the gathered words standing in for the subtables.

    Identical words → identical static-shift unpack → identical dequant, so
    the result is bit-exact vs the psum path at ANY capacity. Ids outside
    ``vecs["keep"]`` (the tiered hot bit, where given) are not routed and
    output zeros, matching the psum path's keep mask."""
    mp = _axes_size(mesh, rows_ax)
    widths = [(i, b) for i, b in enumerate(bits) if b != 0]
    n_words = max(packing.words_per_row(d, b) for _, b in widths)
    local_idx, width_idx = vecs["local"], vecs["width"]
    fl_p, widx, lidx, route, plan, cap = _route_plan(
        fl, width_idx, local_idx,
        [subs[f"b{b}"].shape[0] if b else 1 for b in bits], bits,
        n_shards=mp, capacity=capacity, keep=vecs.get("keep"))
    bp = fl_p.shape[0]
    slice_len = bp // mp
    n_spill = spill_capacity(slice_len, cap, mp)

    me = rows_shard_index(mesh, rows_ax)
    ids_me = jax.lax.dynamic_slice_in_dim(fl_p, me * slice_len, slice_len)
    slot_me = jnp.take(plan.slot, me, axis=0)
    inb_me = jnp.take(plan.in_bucket, me, axis=0)

    # (1) ship the bucketed ids; pad slots carry id 0 and are never read
    send = jnp.zeros((mp * cap,), fl_p.dtype).at[
        jnp.where(inb_me, slot_me, mp * cap)].set(ids_me, mode="drop")
    recv = jax.lax.all_to_all(send.reshape(mp, cap), rows_ax, 0, 0)

    # (2) owner-local gather of the packed words
    r_flat = recv.reshape(-1)
    words, _ = _route_words(subs, widths, jnp.take(width_idx, r_flat, axis=0),
                            jnp.take(local_idx, r_flat, axis=0), me, n_words)

    # (3) words travel back; collect my slice, share all slices
    ret = jax.lax.all_to_all(words.reshape(mp, cap, n_words), rows_ax, 0, 0)
    ret = ret.reshape(mp * cap, n_words)
    w_me = jnp.where(
        inb_me[:, None],
        jnp.take(ret, jnp.clip(slot_me, 0, mp * cap - 1), axis=0),
        jnp.zeros((), jnp.uint32))
    full = jax.lax.all_gather(w_me, rows_ax, axis=0, tiled=True)

    # (4) deterministic overflow spill: masked integer psum, exact
    if n_spill > 0:
        sp = plan.spilled.reshape(bp)
        sp_rank = jnp.cumsum(sp.astype(jnp.int32)) - 1
        contrib, owned = _route_words(subs, widths, widx, lidx, me, n_words,
                                      mask=sp)
        buf = jnp.zeros((n_spill, n_words), jnp.uint32).at[
            jnp.where(owned, sp_rank, n_spill)].set(contrib, mode="drop")
        buf = jax.lax.psum(buf, rows_ax)
        full = jnp.where(
            sp[:, None],
            jnp.take(buf, jnp.clip(sp_rank, 0, n_spill - 1), axis=0), full)

    # (5) decode on the requesting shard: row j of ``full`` is id j's row
    words_by_width = {f"b{b}": full[:, :packing.words_per_row(d, b)]
                      for _, b in widths}
    out = _lookup_rows(words_by_width, vecs["alpha"], vecs["beta"], bits, d,
                       widx, jnp.arange(bp), keep=route,
                       use_kernel=use_kernel)
    return out[:fl.shape[0]]


def lookup_route_stats(table, meta, ids, *, n_shards: int,
                       bucket_capacity: int | None = None) -> dict:
    """Deterministic routing counters for the a2a path of one lookup — the
    lookup body's own plan (``_route_plan``), so the numbers are
    reproducible bench-gate metrics, not samples."""
    bits = meta["bits"]
    flat = jnp.asarray(ids).reshape(-1)
    shard_rows = [-(-table["subtables"][f"b{b}"].shape[0] // n_shards)
                  if b else 1 for b in bits]
    fl_p, _, _, route, plan, cap = _route_plan(
        flat, table["width_idx"], table["local_idx"], shard_rows, bits,
        n_shards=n_shards, capacity=bucket_capacity)
    slice_len = fl_p.shape[0] // n_shards
    n_slots = n_shards * n_shards * cap
    return {
        "slice_len": slice_len,
        "capacity": cap,
        "spill_cap": spill_capacity(slice_len, cap, n_shards),
        "routed": int(route.sum()),
        "bucketed": int(plan.in_bucket.sum()),
        "spilled": int(plan.spilled.sum()),
        "bucket_demand_max": int(plan.counts.max()),
        "occupancy_pct": round(100.0 * int(plan.in_bucket.sum()) / n_slots,
                               4),
    }


# ---------------------------------------------------------------------------
# packed-table lookup (core.inference)
# ---------------------------------------------------------------------------

def _decode(subs, vecs, fl, *, bits, d, shard=0, use_kernel=False):
    """``core.inference._lookup_rows`` of the flat ids ``fl`` through the
    per-feature vectors ``vecs`` (see ``_packed_lookup``)."""
    keep = vecs.get("keep")
    return _lookup_rows(
        subs, vecs["alpha"], vecs["beta"], bits, d,
        jnp.take(vecs["width"], fl, axis=0),
        jnp.take(vecs["local"], fl, axis=0),
        keep=None if keep is None else jnp.take(keep, fl, axis=0),
        shard=shard, use_kernel=use_kernel)


def _packed_lookup(tree, bits, d: int, ids, *, local: str, keep=None,
                   rows_axes, mesh, use_kernel, lookup_comms, bucket_capacity):
    """The one sharded lookup behind both public wrappers, over a packed
    table or a hot tier ``tree``: its row index is ``tree[local]`` and, for
    a hot tier, ``tree[keep]`` is the keep mask (zeros elsewhere). The
    replicated per-feature vectors travel as ``vecs``: ``width`` (bucket),
    ``local``, ``alpha``, ``beta`` and ``keep``.

    Off a multi-device mesh this is ``core.inference``'s routine on one
    device. On one, the subtables row-shard over ``rows_axes`` and the ids
    batch-shard over the other axes; the psum body runs the routine on the
    device's own row shard and ONE psum adds the one non-zero term per id
    to zeros — bit-exact. ``lookup_comms="a2a"`` runs ``_a2a_lookup``
    instead, falling back to psum when the row axes hold a single shard."""
    if lookup_comms not in LOOKUP_COMMS:
        raise ValueError(f"lookup_comms must be one of {LOOKUP_COMMS}, "
                         f"got {lookup_comms!r}")
    subtables = tree["subtables"]
    vecs = {"width": tree["width_idx"], "local": tree[local],
            "alpha": tree["alpha"], "beta": tree["beta"]}
    if keep is not None:
        vecs["keep"] = tree[keep]
    flat = ids.reshape(-1)
    mesh = active_mesh(mesh)
    if mesh is None:
        out = _decode(subtables, vecs, flat, bits=bits, d=d,
                      use_kernel=use_kernel)
        return out.reshape(*ids.shape, d)
    rows_ax = _present_axes(mesh, rows_axes)
    mp = _axes_size(mesh, rows_ax)
    use_a2a = lookup_comms == "a2a" and mp > 1 and any(bits)
    batch_ax = _batch_entry(mesh, flat.shape[0], _dp_axes_of(mesh, rows_ax))

    def body(subs, v, fl):
        if use_a2a:
            return _a2a_lookup(subs, v, fl, mesh=mesh, rows_ax=rows_ax,
                               bits=bits, d=d, capacity=bucket_capacity,
                               use_kernel=use_kernel)
        out = _decode(subs, v, fl, bits=bits, d=d,
                      shard=rows_shard_index(mesh, rows_ax),
                      use_kernel=use_kernel)
        return jax.lax.psum(out, rows_ax) if rows_ax else out

    subs = {k: pad_rows_to_shard(v, mp) for k, v in subtables.items()}
    in_specs = ({k: P(rows_ax or None, None) for k in subs}, P(),
                P(batch_ax))
    out = shard_map(body, mesh=mesh, in_specs=in_specs,
                    out_specs=P(batch_ax, None), check_vma=False)(
        subs, vecs, flat)
    return out.reshape(*ids.shape, d)


def sharded_packed_lookup(table, meta, ids, *, rows_axes=("model",),
                          mesh=None, use_kernel: bool = False,
                          lookup_comms: str = "psum",
                          bucket_capacity: int | None = None):
    """``core.inference.packed_lookup`` under ``shard_map``: subtables
    row-sharded over ``rows_axes`` (layout: ``packed_table_pspecs``), ids
    batch-sharded over the remaining axes. ``lookup_comms`` picks the merge:
    ``"psum"`` (one float psum over the row axes) or ``"a2a"`` (the
    capacity-bucketed all-to-all of ``_a2a_lookup`` — ``bucket_capacity``
    ids per (slice, shard) bucket, overflow spilling to an integer psum).
    Both are bit-exact vs the single-device reference.

    Degrades to the single-device lookup when no multi-device mesh is active
    (or none of ``rows_axes`` is on it). ``use_kernel`` runs the fused
    Pallas kernel per bucket, on a mesh or off it."""
    return _packed_lookup(table, meta["bits"], meta["d"], ids,
                          local="local_idx", rows_axes=rows_axes, mesh=mesh,
                          use_kernel=use_kernel, lookup_comms=lookup_comms,
                          bucket_capacity=bucket_capacity)


def sharded_tiered_hot_lookup(hot, bits, d: int, ids, *,
                              rows_axes=("model",), mesh=None,
                              lookup_comms: str = "psum",
                              bucket_capacity: int | None = None):
    """``cache.tiers.tiered_hot_lookup`` under ``shard_map``: the same
    lookup as ``sharded_packed_lookup`` over the hot subtables (layout:
    ``tiered_hot_pspecs``), rows found through ``tier_local`` and the hot
    bit as the keep mask — zeros at cold positions, which the caller fills
    (under a2a, only hot ids are routed)."""
    return _packed_lookup(hot, tuple(bits), d, ids, local="tier_local",
                          keep="is_hot", rows_axes=rows_axes, mesh=mesh,
                          use_kernel=False,
                          lookup_comms=lookup_comms,
                          bucket_capacity=bucket_capacity)


# ---------------------------------------------------------------------------
# embedding bag (repro.kernels.embedding_bag)
# ---------------------------------------------------------------------------

def sharded_embedding_bag(table, ids, mask, *, rows_axes=("model",),
                          mesh=None, use_kernel: bool = True):
    """Multi-hot embedding bag under ``shard_map``: the (N, d) table
    row-sharded over ``rows_axes`` (layout: ``recsys_table_pspecs``), bags
    batch-sharded over the data axes; each device sums its owned slots with
    the fused kernel, one ``psum`` merges the partial bags.

    Differentiable w.r.t. the table: a ``custom_vjp`` runs the backward in
    its own shard_map — per-device ``segment_sum`` of the owned slot
    cotangents into the local row block (the transpose of the ownership
    mask), psum-merged over the batch axes only when the bags are actually
    split — so ``sharded_value_and_grad`` and training loss functions no
    longer fall back to the jnp bag. Table grads land row-shard-local.

    NOT bit-exact for >1 row shard: a bag whose slots land on different
    shards has its sum reassociated by the psum (~1e-6 relative on fp32,
    pinned by tests/test_shard_a2a.py::test_embedding_bag_psum_tolerance).
    Exact when ``rows_axes`` resolve to a single shard (pure batch
    sharding)."""
    from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
    from repro.kernels.embedding_bag.ops import embedding_bag_kernel
    from repro.kernels.embedding_bag.ref import embedding_bag_ref

    mesh = active_mesh(mesh)
    rows_ax = _present_axes(mesh, rows_axes) if mesh is not None else ()
    mp = _axes_size(mesh, rows_ax) if mesh is not None else 1
    if mesh is None:
        if use_kernel:  # the custom_vjp wrapper: same kernel, differentiable
            return embedding_bag_kernel(table, ids, mask)
        return embedding_bag_ref(table, ids, mask)
    local = (embedding_bag_pallas if use_kernel else embedding_bag_ref)

    dp = _dp_axes_of(mesh, rows_ax)
    batch_ax = _batch_entry(mesh, ids.shape[0], dp)
    bsplit = batch_ax is not None and _axes_size(mesh, batch_ax) > 1
    tab = pad_rows_to_shard(table, mp) if mp > 1 else table
    rows_entry = rows_ax if mp > 1 else None
    d_model = table.shape[1]

    def fwd_body(tab_loc, ids_b, mask_b):
        rows_loc = tab_loc.shape[0]
        base = rows_shard_index(mesh, rows_ax) * rows_loc if mp > 1 else 0
        own = (ids_b >= base) & (ids_b < base + rows_loc)
        loc = jnp.clip(ids_b - base, 0, rows_loc - 1)
        part = local(tab_loc, loc, mask_b & own)
        return jax.lax.psum(part, rows_ax) if mp > 1 else part

    run_fwd = shard_map(
        fwd_body, mesh=mesh,
        in_specs=(P(rows_entry, None), P(batch_ax, None), P(batch_ax, None)),
        out_specs=P(batch_ax, None), check_vma=False)

    def bwd_body(g_loc, ids_b, mask_b):
        rows_loc = tab.shape[0] // mp
        base = rows_shard_index(mesh, rows_ax) * rows_loc if mp > 1 else 0
        own = mask_b & (ids_b >= base) & (ids_b < base + rows_loc)
        loc = jnp.clip(ids_b - base, 0, rows_loc - 1)
        contrib = jnp.where(
            own[..., None],
            jnp.broadcast_to(g_loc[:, None, :], (*ids_b.shape, d_model)),
            0.0)
        d_loc = jax.ops.segment_sum(contrib.reshape(-1, d_model),
                                    loc.reshape(-1), num_segments=rows_loc)
        if bsplit:  # replicated bags would double-count under a psum
            d_loc = jax.lax.psum(d_loc, batch_ax)
        return d_loc.astype(g_loc.dtype)

    run_bwd = shard_map(
        bwd_body, mesh=mesh,
        in_specs=(P(batch_ax, None), P(batch_ax, None), P(batch_ax, None)),
        out_specs=P(rows_entry, None), check_vma=False)

    @jax.custom_vjp
    def bag(tab_p, ids_b, mask_b):
        return run_fwd(tab_p, ids_b, mask_b)

    def bag_fwd(tab_p, ids_b, mask_b):
        return run_fwd(tab_p, ids_b, mask_b), (ids_b, mask_b)

    def bag_bwd(res, g):
        return run_bwd(g, *res), None, None

    bag.defvjp(bag_fwd, bag_bwd)
    # the jnp.pad to the padded table is differentiated *outside* the
    # custom_vjp, so grads slice back to the caller's row count
    return bag(tab, ids.astype(jnp.int32), mask.astype(bool))


# ---------------------------------------------------------------------------
# flash attention (repro.kernels.flash_attention)
# ---------------------------------------------------------------------------

def sharded_flash_attention(q, k, v, *, n_kv_heads: int | None = None,
                            causal: bool = True, bq: int = 128, bk: int = 128,
                            head_axes=("model",), mesh=None):
    """Flash attention under ``shard_map``: batch over the data axes, query
    heads over ``head_axes`` — every (batch, head) pair computes wholly on
    one device, so there are no collectives and the result is bit-exact
    against the single-device kernel. GQA KV expansion happens *before* the
    shard_map so the head sharding stays aligned.

    Differentiable: a ``custom_vjp`` places the fused fwd-stats and
    backward Pallas kernels in their own shard_maps, with the (o, lse)
    residuals stored under the same batch/head sharding as the activations
    — training through the sharded wrapper runs the flash backward kernel
    per device instead of falling back to the jnp attention, and the grads
    are bit-exact vs the single-device kernel's (still collective-free)."""
    from repro.kernels.flash_attention.kernel import (
        flash_attention_bwd, flash_attention_fwd_stats)
    from repro.kernels.flash_attention.ops import flash_attention_kernel

    del n_kv_heads  # derived from the shapes, as in the flat wrapper
    mesh = active_mesh(mesh)
    if mesh is None:
        return flash_attention_kernel(q, k, v, causal=causal, bq=bq, bk=bk)

    hq, hkv = q.shape[2], k.shape[2]
    if hkv != hq:  # GQA: expand KV to query heads before placing
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)

    head_ax = _present_axes(mesh, head_axes)
    dp = _dp_axes_of(mesh, head_ax)
    batch_ax = _batch_entry(mesh, q.shape[0], dp)
    head_entry = _batch_entry(mesh, hq, head_ax)
    spec = P(batch_ax, None, head_entry, None)
    lse_spec = P(batch_ax, head_entry, None)
    bq_, bk_ = min(bq, q.shape[1]), min(bk, q.shape[1])

    def _flat(x):  # (b, s, h, hd) -> the kernels' (b*h, s, hd)
        b, s, h, hd = x.shape
        return jnp.moveaxis(x, 2, 1).reshape(b * h, s, hd)

    def _unflat(xf, b, s, h):
        return jnp.moveaxis(xf.reshape(b, h, s, -1), 1, 2)

    def fwd_body(qb, kb, vb):
        return flash_attention_kernel(qb, kb, vb, causal=causal, bq=bq,
                                      bk=bk)

    def stats_body(qb, kb, vb):
        b, s, h, _ = qb.shape
        o, lse = flash_attention_fwd_stats(
            _flat(qb), _flat(kb), _flat(vb), causal=causal, bq=bq_, bk=bk_)
        return _unflat(o, b, s, h), lse.reshape(b, h, s)

    def bwd_body(qb, kb, vb, ob, lseb, dob):
        b, s, h, _ = qb.shape
        dq, dk, dv = flash_attention_bwd(
            _flat(qb), _flat(kb), _flat(vb), _flat(ob),
            lseb.reshape(b * h, s), _flat(dob), causal=causal, bq=bq_,
            bk=bk_)
        return (_unflat(dq, b, s, h), _unflat(dk, b, s, h),
                _unflat(dv, b, s, h))

    run_fwd = shard_map(fwd_body, mesh=mesh, in_specs=(spec,) * 3,
                        out_specs=spec, check_vma=False)
    run_stats = shard_map(stats_body, mesh=mesh, in_specs=(spec,) * 3,
                          out_specs=(spec, lse_spec), check_vma=False)
    run_bwd = shard_map(bwd_body, mesh=mesh,
                        in_specs=(spec, spec, spec, spec, lse_spec, spec),
                        out_specs=(spec, spec, spec), check_vma=False)

    @jax.custom_vjp
    def fa(qx, kx, vx):
        return run_fwd(qx, kx, vx)

    def fa_fwd(qx, kx, vx):
        o, lse = run_stats(qx, kx, vx)
        return o, (qx, kx, vx, o, lse)

    def fa_bwd(res, do):
        return run_bwd(*res, do)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa(q, k, v)


# ---------------------------------------------------------------------------
# QAT mixed expectation (repro.kernels.mpe_qat)
# ---------------------------------------------------------------------------

def sharded_mixed_expectation(rows, probs, alpha, beta, bits, *, mesh=None):
    """Eq. (9) expectation-over-widths under ``shard_map``: rows split over
    *every* mesh axis (the op is row-parallel — the natural placement for
    the gathered rows of a batch-sharded train step); α/β replicated. The
    forward has no collectives and is bit-exact; in the backward each shard
    reduces ∂α and ∂β over its own rows and the ``shard_map`` transpose
    psums them over the mesh (``check_vma=False`` inserts that psum for
    every replicated input). Rows pad up to the device count and unpad
    after (the pad-to-shard path)."""
    from repro.kernels.mpe_qat.ops import mixed_expectation_kernel

    mesh = active_mesh(mesh)
    if mesh is None:
        return mixed_expectation_kernel(rows, probs, alpha, beta, bits)

    axes = tuple(mesh.axis_names)
    n = rows.shape[0]
    rows_p = pad_rows_to_shard(rows, mesh.size)
    probs_p = pad_rows_to_shard(probs, mesh.size)

    def body(r, p, a, b_):
        return mixed_expectation_kernel(r, p, a, b_, bits)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(None), P(None)),
        out_specs=P(axes, None), check_vma=False)(rows_p, probs_p, alpha, beta)
    return out[:n]


# ---------------------------------------------------------------------------
# train step: DP batch + row-sharded tables
# ---------------------------------------------------------------------------

def _table_pspecs(params, mesh, rows_axes):
    """Param pspecs for the train step: ``recsys_table_pspecs`` for the
    ``"embedding"`` entry (row axes only where the rows divide), everything
    else replicated."""
    from repro.dist.sharding import recsys_table_pspecs

    pspecs = replicate_like(params)
    emb = params.get("embedding") if isinstance(params, dict) else None
    if not isinstance(emb, dict):
        return pspecs
    wanted = recsys_table_pspecs(tuple(rows_axes), emb)
    fitted = {}
    for k, v in emb.items():
        spec = wanted[k]
        entry = spec[0] if len(spec) else None
        if entry and v.ndim >= 1 and v.shape[0] % _axes_size(mesh, rows_axes) == 0:
            fitted[k] = spec
        else:
            fitted[k] = P(*([None] * v.ndim))
    pspecs = dict(pspecs)
    pspecs["embedding"] = fitted
    return pspecs


def _is_row_sharded(spec) -> bool:
    return len(spec) > 0 and spec[0] is not None


def sharded_value_and_grad(loss_fn, mesh, *, rows_axes=("model",)):
    """A drop-in for ``jax.value_and_grad(loss_fn, has_aux=True)`` that runs
    the loss+grad *inside* ``shard_map`` on ``mesh``.

    Layout: the batch is data-parallel over every mesh axis that divides it
    (falling back to the non-row axes, then to replicated); dense embedding
    leaves (``params["embedding"]``, per ``recsys_table_pspecs``) are stored
    row-sharded over ``rows_axes`` and all-gathered in the body, so autodiff
    transposes the gather into a psum-scatter — table grads arrive
    row-shard-local ("row-shard-local updates") while every replicated leaf
    gets a ``pmean`` over the mesh ("gradient reduction for replicated MLP
    params"). Loss and float aux leaves are ``pmean``-ed to replication;
    integer/bool aux leaves must already be batch-independent.

    Parity: mean-of-shard-means reassociates the batch reduction, so losses
    and grads match the single-device step to fp32 tolerance (~1e-6), not
    bit-exactly.

    Returns ``vag(params, buffers, state, batch, *, step)`` →
    ``((loss, aux), grads)``.
    """
    rows_ax = _present_axes(mesh, rows_axes)
    mp = _axes_size(mesh, rows_ax)
    other_axes = _dp_axes_of(mesh, rows_ax)
    axes_all = tuple(mesh.axis_names)

    def vag(params, buffers, state, batch, *, step):
        leaves = jax.tree.leaves(batch)
        bsz = leaves[0].shape[0] if leaves else 0
        if bsz and bsz % mesh.size == 0:
            batch_ax = axes_all
        elif bsz and other_axes and bsz % _axes_size(mesh, other_axes) == 0:
            batch_ax = other_axes
        else:
            batch_ax = ()
        batch_specs = jax.tree.map(
            lambda x: P(batch_ax or None, *([None] * (x.ndim - 1))), batch)
        pspecs = _table_pspecs(params, mesh, rows_ax) if mp > 1 \
            else replicate_like(params)

        def gather_tables(p_sh):
            return jax.tree_util.tree_map_with_path(
                lambda path, x: _gather_leaf(pspecs, path, x), p_sh)

        def _gather_leaf(specs, path, x):
            spec = _leaf_spec(specs, path)
            if _is_row_sharded(spec):
                return jax.lax.all_gather(x, spec[0], axis=0, tiled=True)
            return x

        def inner(p_sh, bu, st, ba, stp):
            def local(p_sh):
                return loss_fn(gather_tables(p_sh), bu, st, ba, step=stp)

            (loss, aux), grads = jax.value_and_grad(
                local, has_aux=True)(p_sh)
            loss = jax.lax.pmean(loss, axes_all)
            aux = jax.tree.map(
                lambda x: jax.lax.pmean(x, axes_all)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact) else x,
                aux)
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: _reduce_grad(path, g), grads)
            return (loss, aux), grads

        def _reduce_grad(path, g):
            spec = _leaf_spec(pspecs, path)
            if _is_row_sharded(spec):
                # the all_gather transpose already psum-scattered over the
                # row axes; average the rest and undo the row-axis sum/dup
                g = jax.lax.pmean(g, other_axes) if other_axes else g
                return g / mp
            return jax.lax.pmean(g, axes_all)

        aux_sds = jax.eval_shape(
            lambda p, bu, st, ba: loss_fn(p, bu, st, ba, step=step)[1],
            params, buffers, state, batch)
        aux_specs = jax.tree.map(lambda s: P(*([None] * len(s.shape))),
                                 aux_sds)
        out_specs = ((P(), aux_specs), pspecs)
        f = shard_map(inner, mesh=mesh,
                      in_specs=(pspecs, replicate_like(buffers),
                                replicate_like(state), batch_specs, P()),
                      out_specs=out_specs, check_vma=False)
        return f(params, buffers, state, batch, jnp.asarray(step))

    return vag


def _leaf_spec(specs, path):
    """The PartitionSpec at ``path`` of a spec tree mirroring the params."""
    node = specs
    for entry in path:
        if isinstance(node, P):
            break
        key = getattr(entry, "key", getattr(entry, "idx", None))
        node = node[key]
    return node
