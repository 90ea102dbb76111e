"""Serve-cell builders: (model, config, bound state) → compilable cell defs.

These are the serving counterparts of ``repro.launch.cells`` — but where the
dry-run builds production-scale ShapeDtypeStruct stand-ins, these bind *real*
trained arrays (a packed table, tower MLPs, KV caches) and parameterize the
batch shape, so the same builder serves a 4-field test table on one CPU
device and the Criteo-scale table on the production mesh. The dry-run serve
cells reuse ``score_step`` (the model's own forward, its packed lookup
partitioned by GSPMD); on one device ``packed_score_step`` compiles to the
same program.

A ``ServeCellDef`` separates *bound* inputs (params/state/buffers — device_put
once at registration) from *request* inputs (ids/tokens/caches — fresh every
call); ``repro.serve.cache.CellCache`` compiles the pair into one executable
with explicit shardings.
"""
from __future__ import annotations

import hashlib
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.inference import packed_lookup_fn
from repro.dist.sharding import (lm_kv_cache_pspecs, lm_logits_pspecs,
                                 lm_param_pspecs, packed_serve_pspecs,
                                 replicate_like, tiered_hot_pspecs)


class ServeCellDef(NamedTuple):
    """One compilable serving cell: a step function plus everything the
    ``CellCache`` needs to AOT-compile it — *bound* inputs (params/state,
    device_put once at registration) with their pspecs, *request* input
    ShapeDtypeStructs with theirs, output pspecs, and the identity fields
    (``arch``/``shape``/``kind``/``batch``) that key the compile cache."""
    arch: str              # architecture identity (cache-key component)
    shape: str             # shape name, e.g. "serve_p99"
    kind: str              # score | lookup | retrieve | decode
    batch: int             # leading-dim capacity of the compiled executable
    step_fn: Callable      # step_fn(*bound, *request) -> outputs
    bound: tuple           # pytrees fixed at registration (params, state, ...)
    bound_pspecs: tuple
    request_specs: tuple   # ShapeDtypeStructs for the per-request inputs
    request_pspecs: tuple
    out_pspecs: Any
    meta: dict
    static: Any = None     # config baked into step_fn closures (cfg, top_k…)
    make_request_state: Callable | None = None  # e.g. fresh KV caches

    @property
    def name(self) -> str:
        return f"{self.arch}/{self.shape}"

    @property
    def fingerprint_blob(self) -> str:
        """The raw repr the fingerprint digests — exposed so the
        recompile-hazard pass (``repro.analysis.recompile``) can inspect it
        for unstable content (``0x...`` object addresses from a default
        ``__repr__``, which would fork the compile cache every process
        restart) instead of reasoning about an opaque hash."""
        return repr((self.kind, self.batch, sorted(self.meta.items(), key=str),
                     self.static))

    @property
    def fingerprint(self) -> str:
        """Digest of everything baked into the compiled executable beyond the
        input avals — the step closure's static config (``static``), kind and
        meta. Part of the cache key: two same-named registrations with
        different baked-in config must not share an executable."""
        return hashlib.sha1(self.fingerprint_blob.encode()).hexdigest()[:12]

    def abstract_signature(self) -> tuple:
        """Traced-abstract-value signature of every input the executable sees:
        ``((shape, dtype, weak_type), ...)`` over the flattened bound +
        request pytrees, in call order.

        This is exactly what distinguishes executables *beyond* the cache
        key — two cells whose keys collide but whose signatures differ would
        silently fork (or worse, warm-hit a wrong executable). The
        recompile-hazard pass diffs keys against these signatures; weak-typed
        leaves (Python scalars closed into ``bound``) are flagged because
        their weak dtype re-traces against strongly-typed request arrays."""
        sig = []
        for leaf in jax.tree.leaves((self.bound, self.request_specs)):
            aval = jax.api_util.shaped_abstractify(leaf)
            sig.append((tuple(aval.shape), str(aval.dtype),
                        bool(getattr(aval, "weak_type", False))))
        return tuple(sig)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _topped(logits, top_k):
    return tuple(jax.lax.top_k(logits, top_k)) if top_k is not None \
        else logits


def score_step(model, cfg, *, top_k: int | None = None):
    """Eval-mode ``model.apply`` scoring, optionally topped with a candidate
    ``top_k``: the dense baselines' step, and the dry-run's packed step
    (its packed lookup partitioned by GSPMD over the production mesh)."""
    def serve_step(params, state, buffers, ids):
        logits, _, _ = model.apply(params, buffers, state, {"ids": ids},
                                   cfg, train=False)
        return _topped(logits, top_k)
    return serve_step


def packed_score_step(model, cfg, *, top_k: int | None = None,
                      rows_axes=("model",), lookup_comms: str = "psum",
                      bucket_capacity: int | None = None):
    """The packed-table scoring computation of the live engine: the packed
    lookup, then the model's interaction net (``model.interact``),
    optionally topped with a candidate ``top_k``.

    The lookup is ``repro.dist.shard.sharded_packed_lookup``: on the mesh
    active at trace time (the ``CellCache`` compiles under the engine's
    mesh) it runs *inside* the partitioner as a ``shard_map``, subtables
    row-sharded over ``rows_axes``; with no multi-device mesh it is the
    one-device ``packed_lookup``. ``lookup_comms`` picks the merge
    collective — ``"psum"`` (dequantized partials) or ``"a2a"`` (the
    capacity-bucketed all-to-all of the packed words, ``bucket_capacity``
    ids per bucket) — both bit-exact, so the embeddings match the one-device
    lookup's either way. On a TPU the sharded and one-device programs may
    still order the interaction net's f32 sums differently, so scores can
    differ in the last bits."""
    from repro.dist.shard import sharded_packed_lookup
    meta = {k: cfg.comp_cfg[k] for k in ("bits", "d", "n")}

    def serve_step(params, state, buffers, ids):
        gids = ids + buffers["offsets"][None, :]
        emb = sharded_packed_lookup(params["embedding"], meta, gids,
                                    rows_axes=rows_axes,
                                    lookup_comms=lookup_comms,
                                    bucket_capacity=bucket_capacity)
        logits, _ = model.interact(params, state, emb, gids, cfg, train=False)
        return _topped(logits, top_k)
    return serve_step


def packed_score_cell(model, cfg, params, state, buffers, *, batch: int,
                      arch: str, shape: str, dp=("data",),
                      rows_axes=("model",), lookup_comms: str = "psum",
                      bucket_capacity: int | None = None) -> ServeCellDef:
    """Batched CTR scoring from a packed table: ``ids (B, F) -> logits (B,)``.

    ``cfg`` must carry ``compressor="packed"`` with the table's comp_cfg;
    ``params["embedding"]`` is the packed table pytree. ``lookup_comms``/
    ``bucket_capacity`` pick the sharded lookup's merge collective (see
    ``packed_score_step``); both enter the cell fingerprint, so a psum cell
    and an a2a cell never share an executable."""
    n_fields = len(cfg.fields)
    return ServeCellDef(
        arch=arch, shape=shape, kind="score", batch=batch,
        step_fn=packed_score_step(model, cfg, rows_axes=rows_axes,
                                  lookup_comms=lookup_comms,
                                  bucket_capacity=bucket_capacity),
        bound=(params, state, buffers),
        bound_pspecs=(packed_serve_pspecs(params, rows_axes=rows_axes),
                      replicate_like(state), replicate_like(buffers)),
        request_specs=(_sds((batch, n_fields), jnp.int32),),
        request_pspecs=(P(dp, None),),
        out_pspecs=P(dp),
        meta={"kind": "score", "batch": batch, "n_fields": n_fields,
              "lookup_comms": lookup_comms,
              "bucket_capacity": bucket_capacity},
        static=cfg,
    )


def baseline_score_cell(model, cfg, params, state, buffers, *, batch: int,
                        arch: str, shape: str, dp=("data",)) -> ServeCellDef:
    """Batched CTR scoring for a *baseline* compressor (plain, qr, pep,
    optfs, alpt, lsq — anything registered in ``core.compressors``):
    ``ids (B, F) -> logits (B,)``.

    The same eval-mode forward as ``packed_score_cell``, but the dense
    baseline ``params`` replicate instead of packed-table row-sharding —
    baseline tables aren't width-bucketed, so ``packed_serve_pspecs`` doesn't
    apply. This is how ``benchmarks/compression_bench.py`` gets
    apples-to-apples serve p50/p99 for every ``repro.core.baselines`` method
    against the packed MPE path."""
    n_fields = len(cfg.fields)
    return ServeCellDef(
        arch=arch, shape=shape, kind="score", batch=batch,
        step_fn=score_step(model, cfg),
        bound=(params, state, buffers),
        bound_pspecs=(replicate_like(params), replicate_like(state),
                      replicate_like(buffers)),
        request_specs=(_sds((batch, n_fields), jnp.int32),),
        request_pspecs=(P(dp, None),),
        out_pspecs=P(dp),
        meta={"kind": "score", "batch": batch, "n_fields": n_fields},
        static=cfg,
    )


def packed_lookup_cell(table, meta, offsets, *, batch: int, n_fields: int,
                       arch: str, shape: str, dp=("data",),
                       rows_axes=("model",)) -> ServeCellDef:
    """Lookup-only companion cell: the packed gather+unpack+dequant slice of a
    score cell, compiled at the same padded shape. The engine times it per
    request to report the Figure-5 lookup-vs-compute split."""
    from repro.dist.sharding import packed_table_pspecs
    lookup = packed_lookup_fn(meta)

    def lookup_step(tbl, offs, ids):
        return lookup(tbl, ids + offs[None, :])

    return ServeCellDef(
        arch=arch, shape=f"{shape}.lookup", kind="lookup", batch=batch,
        step_fn=lookup_step,
        bound=(table, offsets),
        bound_pspecs=(packed_table_pspecs(table, rows_axes=rows_axes),
                      P(None)),
        request_specs=(_sds((batch, n_fields), jnp.int32),),
        request_pspecs=(P(dp, None),),
        out_pspecs=P(dp, None, None),
        meta={"kind": "lookup", "batch": batch, "n_fields": n_fields},
        static=(meta["bits"], meta["d"], meta["n"]),
    )


def tiered_score_cell(model, cfg, params, state, buffers, hot, meta, *,
                      batch: int, arch: str, shape: str, dp=("data",),
                      rows_axes=("model",), row_keys=("wide", "fm_linear"),
                      lookup_comms: str = "psum",
                      bucket_capacity: int | None = None) -> ServeCellDef:
    """Batched CTR scoring from a **tiered** table: ``(ids (B, F), cold_fill
    (B, F, d)) -> logits (B,)``.

    Hot rows are gathered device-locally inside the cell from the bound hot
    tier (row-sharded like the monolithic table, ``tiered_hot_pspecs``);
    the cold rows arrive as a per-request dense fill staged by the engine's
    prefetch (``TieredTableStore.prefetch_cold`` → ``cold_part``), so their
    host→device transfer overlaps the previous chunk's compute. The merge is
    a ``jnp.where`` on the tier mask and the interaction net is the model's
    own ``interact`` — the scores match the monolithic score cell.

    ``params`` is the serving param tree *without* the ``"embedding"`` entry
    (the tiered store owns the table); ``hot`` is ``TieredTableStore.hot``.
    The hot-tier gather is ``repro.dist.shard.sharded_tiered_hot_lookup``:
    a ``shard_map`` over the mesh active at compile time (hot subtables
    row-sharded per ``tiered_hot_pspecs``, ``lookup_comms``/
    ``bucket_capacity`` selecting the psum or capacity-bucketed a2a merge),
    the one-device hot lookup without one — scores match the monolithic
    cell either way.
    """
    from repro.dist.shard import sharded_tiered_hot_lookup
    n_fields = len(cfg.fields)
    d = int(meta["d"])
    bits = tuple(meta["bits"])

    def tiered_step(p, st, bufs, hot_tree, ids, cold_fill):
        gids = ids + bufs["offsets"][None, :]
        hot_emb = sharded_tiered_hot_lookup(                    # 0 at cold
            hot_tree, bits, d, gids, rows_axes=rows_axes,
            lookup_comms=lookup_comms, bucket_capacity=bucket_capacity)
        is_hot = jnp.take(hot_tree["is_hot"], gids, axis=0)
        emb = jnp.where(is_hot[..., None], hot_emb, cold_fill)
        logits, _ = model.interact(p, st, emb, gids, cfg, train=False)
        return logits

    param_pspecs = {k: replicate_like(v) for k, v in params.items()}
    for k in row_keys:
        if k in params:
            param_pspecs[k] = P(rows_axes)

    return ServeCellDef(
        arch=arch, shape=shape, kind="tiered_score", batch=batch,
        step_fn=tiered_step,
        bound=(params, state, buffers, hot),
        bound_pspecs=(param_pspecs, replicate_like(state),
                      replicate_like(buffers),
                      tiered_hot_pspecs(hot, rows_axes=rows_axes)),
        request_specs=(_sds((batch, n_fields), jnp.int32),
                       _sds((batch, n_fields, d), jnp.float32)),
        request_pspecs=(P(dp, None), P(dp, None, None)),
        out_pspecs=P(dp),
        meta={"kind": "tiered_score", "batch": batch, "n_fields": n_fields,
              "lookup_comms": lookup_comms,
              "bucket_capacity": bucket_capacity},
        static=(cfg, bits, d),
    )


def two_tower_retrieval_cell(model, cfg, params, state, buffers, *,
                             n_cands: int, top_k: int = 100, arch: str,
                             shape: str = "retrieval_cand",
                             rows_axes=("model",)) -> ServeCellDef:
    """One user against a padded candidate corpus → masked top-k.

    Padded candidates score ``-inf`` through the validity mask, so they can
    never enter the top-k of a real request."""
    fu, fi = len(cfg.user_fields), len(cfg.item_fields)

    def retrieve_step(p, st, bufs, user_ids, cand_ids, cand_mask):
        u, _ = model.user_tower(p, bufs, st, user_ids, cfg)
        v, _ = model.item_tower(p, bufs, st, cand_ids, cfg)
        scores = (v @ u[0]) / cfg.temperature
        scores = jnp.where(cand_mask, scores, -jnp.inf)
        return tuple(jax.lax.top_k(scores, top_k))

    return ServeCellDef(
        arch=arch, shape=shape, kind="retrieve", batch=n_cands,
        step_fn=retrieve_step,
        bound=(params, state, buffers),
        bound_pspecs=(packed_serve_pspecs(params, rows_axes=rows_axes),
                      replicate_like(state), replicate_like(buffers)),
        request_specs=(_sds((1, fu), jnp.int32), _sds((n_cands, fi), jnp.int32),
                       _sds((n_cands,), jnp.bool_)),
        request_pspecs=(P(None, None), P(rows_axes, None), P(rows_axes)),
        out_pspecs=(P(None), P(None)),
        meta={"kind": "retrieve", "n_cands": n_cands, "top_k": top_k},
        static=cfg,
    )


def lm_decode_slotted_cell(cfg, params, buffers, *, batch: int, max_len: int,
                           kv_int8: bool = True, arch: str,
                           shape: str = "decode_cb",
                           dp=("data",)) -> ServeCellDef:
    """Continuous-batching decode: per-slot cache lengths.

    The compiled batch dim is a pool of ``batch`` KV-cache *slots*; each slot
    holds one request's sequence at its own length. Request inputs are
    ``(tokens (B, 1), lens (B,) int32, caches)`` where ``lens`` is the
    scheduler-owned per-slot valid length (a recycled slot rejoins at 0,
    which re-seeds its int8 scale on first write) and ``caches`` omits the
    shared ``"len"`` entry of the classic decode cell. Requests join/leave
    the running batch between steps without recompiling — the scheduler's
    ``DecodeSession`` owns the slot free-list."""
    from repro.models.lm import LM

    def decode_step(p, tokens, lens, caches):
        return LM.decode_step_slotted(p, buffers, tokens, lens, caches, cfg)

    kv_dtype = jnp.int8 if kv_int8 else jnp.bfloat16

    def make_caches():
        caches = LM.make_kv_caches(cfg, batch, max_len, kv_dtype)
        caches.pop("len")
        return caches

    caches_sds = jax.eval_shape(make_caches)
    cache_ps = {k: v for k, v in
                lm_kv_cache_pspecs(quantized=kv_int8).items() if k != "len"}
    tok_ps = P(dp, None) if batch > 1 else P(None, None)
    lens_ps = P(dp) if batch > 1 else P(None)
    params_pspecs = lm_param_pspecs(params, cfg)

    return ServeCellDef(
        arch=arch, shape=shape, kind="decode_slotted", batch=batch,
        step_fn=decode_step,
        bound=(params,),
        bound_pspecs=(params_pspecs,),
        request_specs=(_sds((batch, 1), jnp.int32), _sds((batch,), jnp.int32),
                       caches_sds),
        request_pspecs=(tok_ps, lens_ps, cache_ps),
        out_pspecs=(lm_logits_pspecs(batch, dp=dp), cache_ps),
        meta={"kind": "decode_slotted", "batch": batch, "max_len": max_len,
              "kv_int8": kv_int8},
        static=cfg,
        make_request_state=make_caches,
    )


def lm_decode_cell(cfg, params, buffers, *, batch: int, max_len: int,
                   kv_int8: bool = True, arch: str, shape: str = "decode",
                   dp=("data",)) -> ServeCellDef:
    """One-token decode against a persistent KV cache.

    The int8 cache with running-absmax scale calibration (``LM._requant_cache``)
    is the default — the paper-aligned halving of the decode-dominant KV
    traffic; pass ``kv_int8=False`` for the bf16 reference cache."""
    from repro.models.lm import LM

    def decode_step(p, tokens, caches):
        return LM.decode_step(p, buffers, tokens, caches, cfg)

    kv_dtype = jnp.int8 if kv_int8 else jnp.bfloat16
    # the model owns cache layout + scale seeding; the SDS template and the
    # engine's fresh caches both derive from make_kv_caches
    caches_sds = jax.eval_shape(
        lambda: LM.make_kv_caches(cfg, batch, max_len, kv_dtype))
    cache_ps = lm_kv_cache_pspecs(quantized=kv_int8)
    tok_ps = P(dp, None) if batch > 1 else P(None, None)
    params_pspecs = lm_param_pspecs(params, cfg)

    return ServeCellDef(
        arch=arch, shape=shape, kind="decode", batch=batch,
        step_fn=decode_step,
        bound=(params,),
        bound_pspecs=(params_pspecs,),
        request_specs=(_sds((batch, 1), jnp.int32), caches_sds),
        request_pspecs=(tok_ps, cache_ps),
        out_pspecs=(lm_logits_pspecs(batch, dp=dp), cache_ps),
        meta={"kind": "decode", "batch": batch, "max_len": max_len,
              "kv_int8": kv_int8},
        static=cfg,
        make_request_state=lambda: LM.make_kv_caches(cfg, batch, max_len,
                                                     kv_dtype),
    )
