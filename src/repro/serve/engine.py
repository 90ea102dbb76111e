"""The serving engine: a submit/poll request lifecycle over compiled cells.

Request flow for a scored request:

  submit(ids) ──▶ AdmissionQueue (bounded; deadlines; shed-on-full)
      ──▶ Scheduler.step: coalesce pending requests across callers onto the
          registered cell shapes (one padded cell invocation serves many
          requests; outputs scatter back per requester via Chunk.spans)
      ──▶ poll(ticket) → probs (n,)

``score`` / ``score_tiered`` / ``decode`` are preserved as thin synchronous
wrappers (submit + drain + poll), so single-caller code and every pre-
lifecycle test keep working bit-identically — a lone request packs onto
exactly the chunks the old per-request planner chose. LM generation rides
the scheduler's **continuous-batching** decode lane (``submit_decode``):
sequences join/leave a persistent slot-pooled KV cache between steps.

Every executable is compiled exactly once per (arch, shape, mesh) by the
``CellCache``; bound state (packed table, MLPs, towers) is device_put with
its serving shardings at registration and reused across requests. Per-cell
wall-clock is recorded with a lookup-only companion executable timed
alongside to report the paper's Figure-5 lookup-vs-compute latency split,
plus per-dispatch occupancy; per-request queue-wait / batch-assembly /
compute land in ``RequestStats``. Timings cover executable dispatch-to-ready
(host→device transfer of the request ids is excluded, matching the Figure-5
protocol).
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax
import numpy as np

from repro.dist.mesh import host_mesh
from repro.serve.batcher import RequestBatcher
from repro.serve.cache import CellCache, CompiledCell
from repro.serve.cells import (ServeCellDef, packed_lookup_cell,
                               packed_score_cell, tiered_score_cell)
from repro.serve.queue import (DONE, FAILED, SHED, AdmissionQueue,
                               RequestFailedError, TenantQuota)
from repro.serve.scheduler import Scheduler
from repro.serve.stats import LatencyStats, RequestStats


class RegisteredCell(NamedTuple):
    """A cell after registration: its definition, the warm compiled
    executable, the bound inputs committed to their shardings, and the
    optional Figure-5 lookup-split companion cell."""
    celldef: ServeCellDef
    cell: CompiledCell        # the warm executable
    bound: tuple              # bound inputs, committed to their shardings
    lookup: "RegisteredCell | None"   # Figure-5 split companion


class TieredCell(NamedTuple):
    """A tiered score cell plus the ``TieredTableStore`` that feeds it and
    the per-field id offsets used to globalize request ids for the cold
    prefetch (the cell itself re-globalizes on device)."""
    reg: RegisteredCell
    store: object             # repro.cache.TieredTableStore
    offsets: np.ndarray       # (F,) int32


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


class Engine:
    """Front-end over the cell cache + request batcher.

    One engine holds one mesh (default: the host mesh — 1×1 on a stock CPU,
    where every sharding constraint is a no-op) and one ``CellCache``; cells
    from several models can coexist, keyed by their ``arch`` identity.
    """

    def __init__(self, mesh=None, cache: CellCache | None = None,
                 queue_capacity: int = 1024, *,
                 quotas: dict[str, TenantQuota] | None = None,
                 shed_watermark: float = 1.0,
                 coalesce_window_ms: float = 0.0,
                 clock=None):
        self.mesh = mesh if mesh is not None else host_mesh()
        self.cache = cache if cache is not None else CellCache(self.mesh)
        # every timestamp in the lifecycle flows from this one callable —
        # inject repro.serve.clock.ManualClock for deterministic tests
        self._clock = clock if clock is not None else time.perf_counter
        self.stats = LatencyStats()
        self.rstats = RequestStats()
        self.queue = AdmissionQueue(queue_capacity, quotas=quotas,
                                    shed_watermark=shed_watermark)
        self.scheduler = Scheduler(self,
                                   coalesce_window_ms=coalesce_window_ms)
        self._requests: dict[int, object] = {}          # ticket -> Request
        self._score: dict[str, RegisteredCell] = {}     # bucket name -> cell
        self._score_batcher = RequestBatcher()
        self._retrieve: dict[str, RegisteredCell] = {}  # arch -> cell
        self._decode: dict[str, RegisteredCell] = {}    # arch -> cell
        self._tiered: dict[str, TieredCell] = {}        # bucket name -> cell
        self._tiered_batcher = RequestBatcher()
        self._pending_swaps: list[tuple] = []           # (arch, table, meta)
        self.swaps_applied = 0
        # traffic-adaptive tiering (repro.cache.policy): one policy drives
        # every registered tiered store; adapters (repro.serve.repack.
        # PressureAdapter) ride the same cadence hook
        self._tier_policy = None
        self._policy_every = 8
        self._policy_rounds = 0
        self._adapters: list = []
        self._hot_seen: dict[str, int] = {}     # shape -> store.hot_version
        self.tier_moves = {"plans": 0, "promotions": 0, "demotions": 0,
                           "bytes": 0}

    # -- registration -------------------------------------------------------

    def _compile(self, celldef: ServeCellDef) -> RegisteredCell:
        # the fingerprint covers config baked into the step closure (model
        # cfg, top_k, …): same-named registrations with different static
        # config must compile their own executable, not warm-hit a wrong one
        key = self.cache.key(
            celldef.arch,
            f"{celldef.shape}@{celldef.batch}#{celldef.fingerprint}")

        def build():
            input_specs = celldef.bound + celldef.request_specs
            in_pspecs = celldef.bound_pspecs + celldef.request_pspecs
            return (celldef.step_fn, input_specs, in_pspecs,
                    celldef.out_pspecs, celldef.meta)

        cell = self.cache.get_or_compile(key, build)
        n_bound = len(celldef.bound)
        bound = tuple(jax.device_put(b, s) for b, s in
                      zip(celldef.bound, cell.in_shardings[:n_bound]))
        return RegisteredCell(celldef, cell, bound, None)

    def register(self, celldef: ServeCellDef,
                 lookup_cell: ServeCellDef | None = None) -> RegisteredCell:
        """Compile (or warm-hit) a cell and route it by kind. Score cells also
        register their capacity as a batcher bucket under their shape name."""
        reg = self._compile(celldef)
        if lookup_cell is not None:
            reg = reg._replace(lookup=self._compile(lookup_cell))
        if celldef.kind == "score":
            self._score[celldef.shape] = reg
            self._score_batcher.register(celldef.shape, celldef.batch)
        elif celldef.kind == "retrieve":
            self._retrieve[celldef.arch] = reg
        elif celldef.kind == "decode":
            self._decode[celldef.arch] = reg
        elif celldef.kind == "decode_slotted":
            self.scheduler.add_session(celldef.arch, reg)
        else:
            raise ValueError(f"unroutable cell kind {celldef.kind!r}")
        return reg

    def register_packed_model(self, arch, model, cfg, params, state, buffers,
                              *, shapes: dict[str, int],
                              lookup_split: bool = True, dp=("data",),
                              rows_axes=("model",),
                              lookup_comms: str = "psum",
                              bucket_capacity: int | None = None):
        """Register one score cell per (shape name → row capacity) for a flat
        CTR model serving from a packed table, each with its lookup-split
        companion when ``lookup_split``. On a multi-device mesh the lookup
        compiles as a ``shard_map`` against the engine's mesh (the fused
        gather runs inside the partitioner); ``lookup_comms``/
        ``bucket_capacity`` select its merge collective (psum, or the
        capacity-bucketed all-to-all) and enter the cell fingerprint."""
        meta = {k: cfg.comp_cfg[k] for k in ("bits", "d", "n")}
        n_fields = len(cfg.fields)
        for shape, rows in shapes.items():
            cd = packed_score_cell(model, cfg, params, state, buffers,
                                   batch=rows, arch=arch, shape=shape,
                                   dp=dp, rows_axes=rows_axes,
                                   lookup_comms=lookup_comms,
                                   bucket_capacity=bucket_capacity)
            lc = None
            if lookup_split:
                lc = packed_lookup_cell(params["embedding"], meta,
                                        buffers["offsets"], batch=rows,
                                        n_fields=n_fields, arch=arch,
                                        shape=shape, dp=dp,
                                        rows_axes=rows_axes)
            self.register(cd, lookup_cell=lc)

    def register_tiered_model(self, arch, model, cfg, params, state, buffers,
                              store, *, shapes: dict[str, int], dp=("data",),
                              rows_axes=("model",),
                              lookup_comms: str = "psum",
                              bucket_capacity: int | None = None):
        """Register one **tiered** score cell per (shape name → row capacity)
        serving from a ``repro.cache.TieredTableStore``: the store's hot tier
        binds into the executable (device-local gather), cold rows ride each
        request as prefetch-staged fills (see ``score_tiered``).

        ``params`` may carry an ``"embedding"`` entry (the monolithic packed
        table) — it is dropped; the store owns the table now."""
        p = {k: v for k, v in params.items() if k != "embedding"}
        offsets = np.asarray(buffers["offsets"], np.int32)
        for shape, rows in shapes.items():
            cd = tiered_score_cell(model, cfg, p, state, buffers, store.hot,
                                   store.meta, batch=rows, arch=arch,
                                   shape=shape, dp=dp, rows_axes=rows_axes,
                                   lookup_comms=lookup_comms,
                                   bucket_capacity=bucket_capacity)
            reg = self._compile(cd)
            self._tiered[shape] = TieredCell(reg, store, offsets)
            self._tiered_batcher.register(shape, rows)
            self._hot_seen[shape] = store.hot_version

    # -- serving-time precision adaptation (repro.serve.repack) -------------

    def request_swap(self, table, meta, *, arch: str | None = None):
        """Queue an atomic packed-table swap (serving-time precision
        adaptation, ``repro.serve.repack``).

        The swap applies at the **next ``sched_step`` boundary**, never
        mid-round: every dispatch reads an immutable ``bound`` tuple
        snapshot, and the scheduler's per-round cell lookups all happen after
        the swap point, so an in-flight coalesced batch can never observe a
        torn table. The new ``table`` must match the live table's leaf
        shapes/dtypes exactly (a capacity-conforming repack —
        ``TableSwapper`` guarantees this), so re-binding goes through the
        compiled ``in_shardings`` and **zero recompiles** occur."""
        self._pending_swaps.append((arch, table, dict(meta)))

    def live_packed_table(self, *, arch: str | None = None):
        """The packed-table pytree currently bound into the score cells of
        ``arch`` (host-side view from the cell definition) — the shape
        template a repack must conform to."""
        for reg in self._score.values():
            if arch is None or reg.celldef.arch == arch:
                return reg.celldef.bound[0]["embedding"]
        raise ValueError(f"no packed score cell registered for arch={arch!r}")

    def _apply_swaps(self):
        while self._pending_swaps:
            arch, table, meta = self._pending_swaps.pop(0)
            self._swap_now(arch, table, meta)

    def _swap_now(self, arch, table, meta):
        swapped = False
        for shape, reg in self._score.items():
            if arch is not None and reg.celldef.arch != arch:
                continue
            self._score[shape] = self._rebind_score(reg, table)
            swapped = True
        for shape, tc in self._tiered.items():
            if arch is not None and tc.reg.celldef.arch != arch:
                continue
            self._tiered[shape] = self._rebind_tiered(tc, table, meta)
            swapped = True
        if not swapped:
            raise ValueError(
                f"table swap targets no registered cell (arch={arch!r})")
        self.swaps_applied += 1

    @staticmethod
    def _check_swap_layout(old, new, what: str):
        """A swap must be invisible to the executable: identical pytree
        structure, shapes and dtypes — otherwise the compiled input avals no
        longer match and the call would have to recompile."""
        def sig(tree):
            return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                                tree)
        if sig(old) != sig(new):
            raise ValueError(
                f"table swap would change the compiled {what} layout — "
                f"repack with row_capacities pinned to the live table "
                f"(repro.serve.repack.subtable_capacities)")

    def _rebind_score(self, reg: RegisteredCell, table) -> RegisteredCell:
        celldef = reg.celldef
        params = dict(celldef.bound[0])
        self._check_swap_layout(params["embedding"], table, "packed-table")
        params["embedding"] = table
        celldef = celldef._replace(bound=(params,) + celldef.bound[1:])
        # same executable, same shardings: the new leaves re-shard under the
        # exact NamedShardings the cell compiled with (packed_table_pspecs)
        bound0 = jax.device_put(params, reg.cell.in_shardings[0])
        reg = reg._replace(celldef=celldef, bound=(bound0,) + reg.bound[1:])
        if reg.lookup is not None:
            lr = reg.lookup
            lcd = lr.celldef._replace(bound=(table,) + lr.celldef.bound[1:])
            lb0 = jax.device_put(table, lr.cell.in_shardings[0])
            reg = reg._replace(lookup=lr._replace(
                celldef=lcd, bound=(lb0,) + lr.bound[1:]))
        return reg

    def _rebind_tiered(self, tc: TieredCell, table, meta) -> TieredCell:
        tc.store.refresh(table, meta)
        reg = tc.reg
        hot_i = len(reg.bound) - 1          # (params, state, buffers, hot)
        self._check_swap_layout(reg.celldef.bound[hot_i], tc.store.hot,
                                "hot-tier")
        hot = jax.device_put(tc.store.hot, reg.cell.in_shardings[hot_i])
        celldef = reg.celldef._replace(
            bound=reg.celldef.bound[:hot_i] + (tc.store.hot,))
        reg = reg._replace(celldef=celldef,
                           bound=reg.bound[:hot_i] + (hot,))
        return TieredCell(reg, tc.store, tc.offsets)

    # -- traffic-adaptive tiering (repro.cache.policy) ----------------------

    def attach_tier_policy(self, policy, *, every: int = 8):
        """Wire an admission/eviction policy (``cache.DecayAdmissionPolicy``
        or ``cache.StaticTierPolicy``) into the serving loop: every
        registered tiered store feeds its lookup stream to the policy, and
        every ``every``-th ``sched_step`` the policy plans a bounded batch
        of promotions/demotions that the stores apply incrementally — no
        re-pack, no recompile (the moves are shape-preserving and the
        updated hot tier rebinds through the compiled ``in_shardings``).
        Returns the policy for chaining."""
        stores = self._tier_stores()
        if not stores:
            raise ValueError(
                "attach_tier_policy requires a registered tiered model "
                "(register_tiered_model)")
        for store in stores:
            store.attach_policy(policy)
        self._tier_policy = policy
        self._policy_every = int(every)
        return policy

    def attach_adapter(self, adapter):
        """Register a drift adapter (``repro.serve.repack.PressureAdapter``)
        on the policy cadence hook: ``adapter.step(engine)`` runs once per
        ``sched_step``, after tier moves apply — the adapter decides its own
        cadence and may queue atomic table swaps (``request_swap``), which
        land at the *next* round's swap point."""
        self._adapters.append(adapter)
        return adapter

    def _tier_stores(self) -> list:
        """The distinct ``TieredTableStore``s behind the tiered cells (one
        store usually backs several shape buckets)."""
        stores, seen = [], set()
        for tc in self._tiered.values():
            if id(tc.store) not in seen:
                seen.add(id(tc.store))
                stores.append(tc.store)
        return stores

    def _policy_step(self):
        if self._tier_policy is None and not self._adapters:
            return
        self._policy_rounds += 1
        if (self._tier_policy is not None
                and self._policy_rounds % self._policy_every == 0):
            for store in self._tier_stores():
                plan = self._tier_policy.plan(store)
                self.tier_moves["plans"] += 1
                if plan.n_moves:
                    s = store.apply_moves(plan.promote, plan.demote)
                    self.tier_moves["promotions"] += s["promotions"]
                    self.tier_moves["demotions"] += s["demotions"]
                    self.tier_moves["bytes"] += s["bytes"]
        for adapter in self._adapters:
            adapter.step(self)
        self._sync_tiered()

    def _sync_tiered(self):
        """Rebind every tiered cell whose store mutated its hot tier
        (promotions, writebacks) since the last sync — the incremental
        analogue of ``_rebind_tiered``, same shapes, zero recompiles."""
        for shape, tc in list(self._tiered.items()):
            if self._hot_seen.get(shape) != tc.store.hot_version:
                self._tiered[shape] = self._rebind_hot(tc)
                self._hot_seen[shape] = tc.store.hot_version

    def _rebind_hot(self, tc: TieredCell) -> TieredCell:
        """Re-``device_put`` the store's current hot tier through the
        compiled shardings — ``_rebind_tiered`` minus the refresh (the store
        already mutated itself shape-preservingly)."""
        reg = tc.reg
        hot_i = len(reg.bound) - 1          # (params, state, buffers, hot)
        self._check_swap_layout(reg.celldef.bound[hot_i], tc.store.hot,
                                "hot-tier")
        hot = jax.device_put(tc.store.hot, reg.cell.in_shardings[hot_i])
        celldef = reg.celldef._replace(
            bound=reg.celldef.bound[:hot_i] + (tc.store.hot,))
        reg = reg._replace(celldef=celldef,
                           bound=reg.bound[:hot_i] + (hot,))
        return TieredCell(reg, tc.store, tc.offsets)

    def writeback_embeddings(self, ids, vectors) -> dict:
        """Flow training-time embedding updates (global feature ids →
        full-precision vectors) into every registered tiered store:
        re-quantized under each feature's current width, mirror written
        first (no update can be lost to a concurrent demotion — see
        ``TieredTableStore.writeback``), hot copies patched and rebound
        without a recompile. Call between scheduling rounds."""
        out = {"written": 0, "bytes": 0}
        for store in self._tier_stores():
            s = store.writeback(ids, vectors)
            out["written"] += s["written"]
            out["bytes"] += s["bytes"]
        self._sync_tiered()
        return out

    # -- request lifecycle: submit / poll / drain ---------------------------

    def _timed_call(self, reg: RegisteredCell, *request):
        t0 = self._clock()
        out = reg.cell.compiled(*reg.bound, *request)
        # deliberate timing barrier: wall-clock per call is the product here
        jax.block_until_ready(out)  # staticcheck: ignore[RL403]
        return out, (self._clock() - t0) * 1e3

    def submit(self, ids, *, kind: str = "score",
               deadline_ms: float | None = None, now: float | None = None,
               overlap: bool = True, tenant: str = "default",
               priority: int = 0) -> int | None:
        """Admit an (n, F) scoring request into the queue -> ticket, or None
        when the admission policy sheds it (queue full, load watermark, or
        tenant queue-share quota; all counted per kind and tenant).

        ``kind`` routes the request to a lane: ``"score"`` (packed cells) or
        ``"tiered"`` (hot/cold store cells, where ``overlap`` controls the
        one-chunk-ahead cold-fill staging) — decode requests go through
        ``submit_decode``. ``tenant``/``priority`` place the request in the
        multi-tenant scheduling lanes (priority 0 is most urgent; dispatch is
        EDF within a lane). ``now`` overrides the arrival timestamp for
        open-loop replay; ``deadline_ms`` is relative to it — requests still
        queued past their deadline are shed at drain."""
        if kind not in ("score", "tiered"):
            raise ValueError(
                f"unroutable request kind {kind!r} (use 'score' or 'tiered'; "
                f"LM generation goes through submit_decode)")
        ids = np.asarray(ids, np.int32)
        req = self.queue.submit(
            kind, ids, ids.shape[0],
            now=self._clock() if now is None else now,
            deadline_ms=deadline_ms,
            meta={"overlap": overlap} if kind == "tiered" else None,
            tenant=tenant, priority=priority)
        if req is None:
            self.rstats.record_shed(kind, tenant=tenant)
            return None
        self._requests[req.ticket] = req
        return req.ticket

    def submit_decode(self, prompt, max_new: int, *, arch: str | None = None,
                      deadline_ms: float | None = None,
                      now: float | None = None, tenant: str = "default",
                      priority: int = 0) -> int | None:
        """Admit an LM generation request (prompt replay + ``max_new`` greedy
        tokens) into the continuous-batching decode lane -> ticket, or None
        when shed. Requires a registered ``lm_decode_slotted_cell``; the
        sequence joins the running decode batch when a KV-cache slot frees
        up, without recompiling or restarting the batch."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        session = self.scheduler._pick_session(arch)
        if prompt.shape[0] + int(max_new) > session.max_len:
            raise ValueError(
                f"sequence of {prompt.shape[0]}+{int(max_new)} tokens exceeds "
                f"the cell's max_len={session.max_len}")
        req = self.queue.submit(
            "decode", (prompt, int(max_new), arch), 1,
            now=self._clock() if now is None else now,
            deadline_ms=deadline_ms, tenant=tenant, priority=priority)
        if req is None:
            self.rstats.record_shed("decode", tenant=tenant)
            return None
        self._requests[req.ticket] = req
        return req.ticket

    def poll(self, ticket: int):
        """The completed result for ``ticket`` — scored requests return the
        (n,) logits, decode requests the generated tokens — or None while the
        request is still queued/in flight. Raises ``RuntimeError`` on a shed
        ticket and ``RequestFailedError`` on a ticket whose dispatch raised.

        A finished ticket (done, shed or failed) is consumed by its poll
        (its record is dropped so a long-running process doesn't accumulate
        per-request state); polling it again raises KeyError."""
        req = self._requests[ticket]
        if req.status == SHED:
            del self._requests[ticket]
            raise RuntimeError(
                f"request {ticket} was shed (deadline passed while queued)")
        if req.status == FAILED:
            del self._requests[ticket]
            raise RequestFailedError(
                f"request {ticket} failed in dispatch: {req.error}")
        if req.status != DONE:
            return None
        del self._requests[ticket]
        return req.result

    def try_poll(self, ticket: int) -> dict:
        """Non-raising poll for harness code (the socket server): always
        returns ``{"status": ...}`` — ``pending`` (ticket still in flight),
        ``done`` (+ ``result``), ``shed``, ``failed`` (+ ``error``), or
        ``unknown`` (never issued, or already consumed). Terminal tickets
        are consumed exactly like ``poll``."""
        req = self._requests.get(ticket)
        if req is None:
            return {"status": "unknown"}
        if req.status == SHED:
            del self._requests[ticket]
            return {"status": "shed"}
        if req.status == FAILED:
            del self._requests[ticket]
            return {"status": "failed", "error": req.error}
        if req.status != DONE:
            return {"status": "pending"}
        del self._requests[ticket]
        return {"status": "done", "result": req.result}

    def sched_step(self, *, now: float | None = None) -> float:
        """Run one scheduling round (coalesce + dispatch each lane once; one
        decode token per active session). ``now=None`` uses the wall clock;
        an explicit ``now`` threads a virtual open-loop timeline through the
        dispatch timestamps and returns the advanced cursor.

        Queued table swaps (``request_swap``) apply here, *before* the round
        dispatches — the atomic swap point of the serving-time precision
        adaptation path: every chunk of a round reads the same table. The
        tier policy and drift adapters run right after the swap point
        (``_policy_step``), so tier moves are likewise never observed
        mid-round."""
        self._apply_swaps()
        self._policy_step()
        return self.scheduler.step(now=now)

    def drain(self, *, now: float | None = None) -> float:
        """Scheduling rounds until the queue is empty and every decode
        session is idle. Returns the final clock cursor."""
        cursor = now
        while self.scheduler.busy:
            cursor = self.sched_step(now=cursor)
        return cursor if cursor is not None else self._clock()

    # -- synchronous wrappers (submit + drain + poll) -----------------------

    def score(self, ids, *, return_logits: bool = False) -> np.ndarray:
        """Score an (n, F) id batch; any n — the scheduler packs it onto the
        registered cell shapes. Returns probabilities (or raw logits).

        Thin synchronous wrapper over the lifecycle: a lone request packs
        onto exactly the chunks the per-request planner would choose, so
        results are bit-identical to pre-lifecycle engines."""
        ticket = self.submit(ids)
        if ticket is None:
            raise RuntimeError("request shed: admission queue full")
        self.drain()
        out = self.poll(ticket)
        return out if return_logits else _sigmoid(out)

    def score_tiered(self, ids, *, overlap: bool = True,
                     return_logits: bool = False) -> np.ndarray:
        """Score an (n, F) id batch through the tiered hot/cold store.

        Hot rows are gathered device-locally inside the compiled cell; each
        chunk's cold-row fill (packed words, host-gathered) is
        ``device_put`` **one chunk ahead** while the previous chunk's cell is
        still computing, so the cold transfer hides under compute.
        ``overlap=False`` stages each fill synchronously right before its
        dispatch — the reference timing in ``BENCH_prefetch.json``. Results
        are identical either way (the pipeline only moves bytes earlier)."""
        ticket = self.submit(ids, kind="tiered", overlap=overlap)
        if ticket is None:
            raise RuntimeError("request shed: admission queue full")
        self.drain()
        out = self.poll(ticket)
        return out if return_logits else _sigmoid(out)

    def tier_counters(self) -> dict:
        """Per-bucket ``TieredTableStore.counters()`` (stores may be shared
        across buckets, in which case the numbers repeat)."""
        return {name: tc.store.counters()
                for name, tc in sorted(self._tiered.items())}

    def retrieve(self, user_ids, cand_ids, *, arch: str | None = None):
        """Top-k retrieval of one user against an arbitrary-size candidate
        corpus. Oversized corpora are chunked onto the compiled candidate
        capacity and the per-chunk top-ks merged; padded candidates are
        masked to -inf inside the cell. Returns (scores, indices) sorted."""
        reg = self._pick(self._retrieve, arch, "retrieval")
        cap = reg.celldef.batch
        top_k = reg.celldef.meta["top_k"]
        user = jax.device_put(np.asarray(user_ids, np.int32),
                              reg.cell.in_shardings[len(reg.bound)])
        cand_ids = np.asarray(cand_ids, np.int32)
        all_scores, all_idx = [], []
        for start in range(0, cand_ids.shape[0], cap):
            part = cand_ids[start:start + cap]
            padded, mask = RequestBatcher.pad(part, cap)
            c = jax.device_put(padded,
                               reg.cell.in_shardings[len(reg.bound) + 1])
            m = jax.device_put(mask,
                               reg.cell.in_shardings[len(reg.bound) + 2])
            (scores, idx), total_ms = self._timed_call(reg, user, c, m)
            self.stats.record(reg.celldef.name, total_ms)
            keep = min(top_k, part.shape[0])
            all_scores.append(np.asarray(scores)[:keep])
            all_idx.append(np.asarray(idx)[:keep] + start)
        scores = np.concatenate(all_scores)
        idx = np.concatenate(all_idx)
        order = np.argsort(-scores)[:top_k]
        return scores[order], idx[order]

    def decode(self, tokens, caches=None, *, arch: str | None = None):
        """One decode step for a (b, 1) token batch, b ≤ the cell's capacity.
        ``caches=None`` starts fresh KV caches (int8 + running-absmax scales
        when the cell was registered with ``kv_int8``, the default). Returns
        (logits (b, V), new_caches) — feed ``new_caches`` back in."""
        reg = self._pick(self._decode, arch, "decode")
        cap = reg.celldef.batch
        tokens = np.asarray(tokens, np.int32)
        b = tokens.shape[0]
        padded, _ = RequestBatcher.pad(tokens, cap)
        toks = jax.device_put(padded,
                              reg.cell.in_shardings[len(reg.bound)])
        if caches is None:
            caches = self.fresh_caches(arch=reg.celldef.arch)
        (logits, new_caches), total_ms = self._timed_call(reg, toks, caches)
        self.stats.record(reg.celldef.name, total_ms)
        return np.asarray(logits)[:b], new_caches

    def fresh_caches(self, *, arch: str | None = None):
        """Fresh KV caches for a decode cell — built by the model's own cache
        constructor (bound at cell build time, so layout and scale seeding
        stay the model's single source of truth), committed to the compiled
        cache shardings."""
        reg = self._pick(self._decode, arch, "decode")
        caches = reg.celldef.make_request_state()
        return jax.device_put(caches,
                              reg.cell.in_shardings[len(reg.bound) + 1])

    @staticmethod
    def _pick(table: dict, arch: str | None, what: str) -> RegisteredCell:
        if not table:
            raise ValueError(f"no {what} cell registered")
        if arch is not None:
            return table[arch]
        if len(table) > 1:
            raise ValueError(
                f"multiple {what} cells registered ({sorted(table)}); "
                f"pass arch=")
        return next(iter(table.values()))

    # -- introspection ------------------------------------------------------

    def registered_cells(self) -> dict:
        """Every registered cell across the four lanes, keyed by its
        ``CellKey``: {key: RegisteredCell}. The static-analysis runner
        (``repro.analysis``) walks this to get each cell's definition *and*
        its warm compiled executable (HLO text, cost analysis) without
        re-deriving registration wiring — tiered cells unwrap to their
        ``RegisteredCell``; lookup-split companions are included under their
        own keys."""
        out = {}

        def add(reg):
            if reg is None:
                return
            out[reg.cell.key] = reg
            add(reg.lookup)

        for reg in self._score.values():
            add(reg)
        for tc in self._tiered.values():
            add(tc.reg)
        for reg in self._retrieve.values():
            add(reg)
        for reg in self._decode.values():
            add(reg)
        for session in self.scheduler.sessions.values():
            add(session.reg)
        return out

    @property
    def compile_count(self) -> int:
        return self.cache.compiles

    @property
    def registered_shapes(self) -> dict:
        """The score-path cell-shape registry: shape name → row capacity."""
        return self._score_batcher.shapes

    def counters(self) -> dict:
        """Cell-cache counters plus per-cell occupancy (valid rows / padded
        rows over every dispatch — the coalescing win), the admission
        queue's depth/shed counters (per kind and per tenant), and goodput —
        completed-request counts — split by lane and by tenant."""
        out = dict(self.cache.counters())
        out["occupancy"] = self.stats.occupancy()
        out["queue"] = self.queue.counters()
        out["goodput"] = {"by_lane": self.rstats.lane_counts(),
                          "by_tenant": self.rstats.tenant_counts()}
        out["tier_moves"] = dict(self.tier_moves)
        return out

    def summary(self, *, skip_warmup: int = 0) -> dict:
        """Per-cell latency percentiles (Figure-5 lookup/compute split) with
        per-cell ``occupancy`` merged in where dispatches recorded it."""
        return self.stats.summary(skip_warmup=skip_warmup)

    def request_summary(self, *, skip_warmup: int = 0,
                        by: str = "kind") -> dict:
        """Per-request breakdown: end-to-end latency plus the three-way
        queue-wait / batch-assembly / compute split. ``by`` groups the
        records: ``"kind"`` (back-compat shape), ``"lane"``
        (``kind:p<priority>``) or ``"tenant"`` (with per-tenant shed/failed
        counts)."""
        summaries = {"kind": self.rstats.summary,
                     "lane": self.rstats.lane_summary,
                     "tenant": self.rstats.tenant_summary}
        return summaries[by](skip_warmup=skip_warmup)
