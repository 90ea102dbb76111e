"""Serving launcher: drive the packed-table engine with a live traffic mix.

Thin CLI over ``repro.serve.Engine`` (the paper's §4 deployment path):
train-or-load a packed mixed-precision table, register the serve cell shapes
(``serve_p99`` for latency traffic, ``serve_bulk`` for offline jobs), then
stream request batches through ``engine.score``. Requests of any size ride
the registered shapes via pad-to-shape batching — ``--batch 300`` really
issues 300-row requests (padded onto the 512-row p99 cell), it no longer
silently falls back to the training batch size.

``--qps`` switches to **open-loop** mode: request arrivals follow seeded
exponential inter-arrival times at the offered rate (the way offline replay
of production traffic drives a server — arrivals don't wait for service), and
concurrent requests coalesce through the admission queue + scheduler onto
shared padded cells. The report then adds the per-request queue-wait /
batch-assembly / compute breakdown, shed counts and per-cell occupancy.

Per-cell p50/p99 latency is reported in the Figure-5 lookup-vs-compute split,
plus the cell-cache counters (a warm process performs zero recompiles).

``--repack-budget`` demonstrates **serving-time precision adaptation**
(``repro.serve.repack``): halfway through the request stream the planner
emits a new per-group assignment at that fraction of the current packed
payload bytes and the swapper re-packs + swaps it into the live cells — the
run asserts the swap compiled nothing. Pair with ``--repack-headroom`` to
pack the serving table with spare per-width row capacity so demoted groups
can land in intermediate widths instead of bottoming out at width 0.

``--cache-policy decay`` turns the tiered store's hit/miss stream into a
**traffic-adaptive hot set** (``repro.cache.policy``): exponential-decay
admission scores plan bounded promotion/demotion batches every
``--policy-every`` scheduling rounds, applied incrementally — no re-pack, no
recompile. ``--drift``/``--shift-at`` make the request stream non-stationary
(``DriftingCTR``), and ``--writeback N`` interleaves training-update
writebacks with live traffic.

    python -m repro.launch.serve --steps 20 --batch 300
    python -m repro.launch.serve --steps 50 --batch 300 --bulk 20000 --json out.json
    python -m repro.launch.serve --qps 20 --steps 100 --batch 60 --deadline-ms 2000
    python -m repro.launch.serve --steps 20 --repack-budget 0.6 --repack-headroom 0.5
    python -m repro.launch.serve --qps 40 --steps 200 --batch 60 --hot-frac 0.2 \
        --cache-policy decay --decay-halflife 64 --shift-at 60 --writeback 16
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.core.mpe import MPEConfig
from repro.core.pipeline import run_mpe_pipeline
from repro.data.synthetic import CTRSpec, SyntheticCTR
from repro.dist.mesh import init_distributed, parse_mesh_flag
from repro.embeddings.table import FieldSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.dlrm import DLRMConfig
from repro.serve import Engine
from repro.train.optimizer import adam
from repro.zoo import dlrm_builder

DEFAULT_VOCABS = (2000, 1000, 1500, 800)


def train_packed_dlrm(*, field_vocabs=DEFAULT_VOCABS, train_steps: int = 120,
                      train_batch: int = 1024, d_embed: int = 16,
                      mlp_hidden=(64, 32), lam: float = 3e-5, seed: int = 0,
                      log_every: int = 100):
    """Quick MPE pipeline → (serve cfg, params, state, buffers, dataset
    spec, pipeline result). The packed table + retrained interaction net are
    exactly what the engine binds at cell registration; ``train_steps``
    search steps are followed by as many retrain steps, and the losses at
    every ``log_every``-th step land in ``result["history"]``."""
    spec = CTRSpec(field_vocabs=tuple(field_vocabs), batch_size=train_batch,
                   seed=seed)
    ds = SyntheticCTR(spec)
    fields = tuple(FieldSpec(f"f{i}", v) for i, v in enumerate(spec.field_vocabs))
    base = DLRMConfig(fields=fields, d_embed=d_embed, mlp_hidden=tuple(mlp_hidden),
                      backbone="dnn")
    build = dlrm_builder(base, ds.expected_frequencies(), lam=lam)
    res = run_mpe_pipeline(build, lambda s: ds.batch(s),
                           key=jax.random.PRNGKey(seed), mpe_cfg=MPEConfig(lam=lam),
                           optimizer=adam(1e-3), search_steps=train_steps,
                           retrain_steps=train_steps, log_fn=lambda *a: None,
                           log_every=log_every)

    cfg = base._replace(compressor="packed",
                        comp_cfg={"bits": res["packed_meta"]["bits"],
                                  "d": res["packed_meta"]["d"],
                                  "n": res["packed_meta"]["n"]})
    params = {k: v for k, v in res["final_params"].items() if k != "embedding"}
    params["embedding"] = res["packed_table"]
    buffers = dict(res["buffers"], embedding={})
    return cfg, params, res["state"], buffers, spec, res


def build_engine(cfg, params, state, buffers, *, p99_rows: int = 512,
                 bulk_rows: int = 4096, lookup_split: bool = True,
                 store=None, mesh=None, lookup_comms: str = "psum",
                 bucket_capacity: int | None = None,
                 queue_capacity: int = 1024, quotas=None,
                 shed_watermark: float = 1.0,
                 coalesce_window_ms: float = 0.0, clock=None) -> Engine:
    """An engine with the standard cell-shape registry for one DLRM table.

    With a ``repro.cache.TieredTableStore`` in ``store``, the same shapes are
    additionally registered as tiered cells (``tiered_p99``/``tiered_bulk``)
    served through ``engine.score_tiered``. A multi-device ``mesh`` compiles
    every cell against it, the packed/hot gathers as the ``shard_map``
    lookups of ``repro.dist.shard``; ``lookup_comms="a2a"``
    switches those lookups to the capacity-bucketed all-to-all id shuffle
    (``bucket_capacity`` bounds ids per destination shard, overflow spills
    to the psum merge — bit-exact at any capacity). ``quotas`` /
    ``shed_watermark`` / ``coalesce_window_ms`` / ``clock`` pass through to
    the engine's multi-tenant admission and scheduling policy."""
    from repro.models.dlrm import DLRM
    engine = Engine(mesh=mesh, queue_capacity=queue_capacity, quotas=quotas,
                    shed_watermark=shed_watermark,
                    coalesce_window_ms=coalesce_window_ms, clock=clock)
    engine.register_packed_model(
        "dlrm", DLRM, cfg, params, state, buffers,
        shapes={"serve_p99": p99_rows, "serve_bulk": bulk_rows},
        lookup_split=lookup_split, lookup_comms=lookup_comms,
        bucket_capacity=bucket_capacity)
    if store is not None:
        engine.register_tiered_model(
            "dlrm", DLRM, cfg, params, state, buffers, store,
            shapes={"tiered_p99": p99_rows, "tiered_bulk": bulk_rows},
            lookup_comms=lookup_comms, bucket_capacity=bucket_capacity)
    return engine


def repack_tools(engine, res, frequencies, *, lam: float = 3e-5):
    """A ``(RepackPlanner, TableSwapper)`` pair bound to a live engine.

    ``res`` is the ``run_mpe_pipeline`` result dict (the swapper re-packs
    from its retrained full-precision master embedding); ``frequencies``
    orders the planner's demote/promote priorities and recovers the
    feature→group map the pipeline trained with (serving buffers don't carry
    it). Capacities default to the engine's live subtable shapes."""
    from repro.core.mpe import make_groups
    from repro.serve.repack import (RepackPlanner, TableSwapper,
                                    subtable_capacities)
    mpe_cfg = MPEConfig(lam=lam)
    gof, _ = make_groups(frequencies, mpe_cfg.group_size)
    planner = RepackPlanner(res["packed_meta"], gof,
                            subtable_capacities(engine.live_packed_table()),
                            frequencies=frequencies)
    emb = res["final_params"]["embedding"]
    swapper = TableSwapper(engine, emb["emb"], emb["alpha"], emb["beta"],
                           mpe_cfg)
    return planner, swapper


def run_open_loop(engine, make_ids, n_requests: int, qps: float, *,
                  seed: int = 0, deadline_ms: float | None = None,
                  kind: str = "score", on_submit=None) -> dict:
    """Open-loop replay: offered traffic at ``qps`` on a virtual timeline.

    Arrivals are seeded exponential inter-arrival times (Poisson traffic at
    the offered rate); they **don't wait for service** — when the offered
    rate exceeds capacity the queue grows until the admission policy sheds.
    The scheduler threads the virtual clock through dispatch (queue-wait is
    virtual-time from arrival to first dispatch) while assembly/compute are
    measured wall-clock, so one CPU run still produces an honest breakdown.
    Inject ``serve.TickClock`` into the engine to make the whole trajectory
    — coalescing, sheds, tier hits — deterministic for the CI bench gate.

    ``on_submit(i, ids)`` (optional) runs right before request ``i`` is
    admitted — the hook the launcher uses to interleave training-update
    writebacks (``Engine.writeback_embeddings``) with live traffic.

    Returns {tickets, makespan_s, offered_qps, goodput_qps, completed,
    shed} — per-request latency percentiles live in
    ``engine.request_summary()``.
    """
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n_requests))
    tickets, shed = [], 0
    now, i = 0.0, 0
    while i < n_requests or engine.scheduler.busy:
        if not engine.scheduler.busy and i < n_requests and arrivals[i] > now:
            now = float(arrivals[i])        # idle server: jump to the arrival
        while i < n_requests and arrivals[i] <= now:
            ids = make_ids(i)
            if on_submit is not None:
                on_submit(i, ids)
            t = engine.submit(ids, kind=kind, now=float(arrivals[i]),
                              deadline_ms=deadline_ms)
            if t is None:
                shed += 1
            tickets.append(t)
            i += 1
        now = engine.sched_step(now=now)
        if (not engine.scheduler._progress and i < n_requests
                and float(arrivals[i]) < now):
            # the round held for its coalescing window and jumped the cursor
            # past the next arrival — cap the jump so that arrival gets to
            # join the held batch before the window decision is remade
            now = float(arrivals[i])
    from repro.serve.queue import DONE, FAILED, SHED
    completed = sum(1 for t in tickets
                    if t is not None and engine._requests[t].status == DONE)
    shed += sum(1 for t in tickets
                if t is not None and engine._requests[t].status == SHED)
    failed = sum(1 for t in tickets
                 if t is not None and engine._requests[t].status == FAILED)
    makespan = max(now, float(arrivals[-1])) if n_requests else now
    return {"tickets": tickets, "makespan_s": makespan,
            "offered_qps": qps,
            "goodput_qps": completed / makespan if makespan > 0 else 0.0,
            "completed": completed, "shed": shed, "failed": failed}


def run_open_loop_mix(engine, make_ids, streams, *, seed: int = 0,
                      kind: str = "score") -> dict:
    """Multi-tenant open-loop replay: merge several Poisson request streams
    onto one virtual timeline.

    Each stream is a dict: ``{"tenant": str, "qps": float, "n_requests":
    int, "priority": int = 0, "deadline_ms": float | None = None,
    "batch": int | None = None}``. Arrivals across streams interleave in
    timestamp order and every request is submitted with its stream's
    tenant/priority/deadline — the two-tenant skewed-priority sweep
    ``queue_bench`` reports is exactly this with one latency-sensitive and
    one bulk stream. ``make_ids(i, batch)`` makes the i-th request's id
    batch (``batch=None`` means the stream's default size).

    Returns {makespan_s, per_stream: {tenant: {offered_qps, completed,
    shed, failed, goodput_qps}}}; per-lane/per-tenant percentiles live in
    ``engine.request_summary(by=...)``.
    """
    rng = np.random.default_rng(seed)
    events = []     # (arrival_t, global_idx, stream)
    gi = 0
    for s in streams:
        arr = np.cumsum(rng.exponential(1.0 / s["qps"],
                                        size=s["n_requests"]))
        for t in arr:
            events.append((float(t), gi, s))
            gi += 1
    events.sort(key=lambda e: (e[0], e[1]))
    tickets = {id(s): [] for s in streams}
    submitted_shed = {id(s): 0 for s in streams}
    now, i = 0.0, 0
    while i < len(events) or engine.scheduler.busy:
        if not engine.scheduler.busy and i < len(events) \
                and events[i][0] > now:
            now = events[i][0]
        while i < len(events) and events[i][0] <= now:
            t_arr, idx, s = events[i]
            t = engine.submit(make_ids(idx, s.get("batch")), kind=kind,
                              now=t_arr, deadline_ms=s.get("deadline_ms"),
                              tenant=s.get("tenant", "default"),
                              priority=s.get("priority", 0))
            if t is None:
                submitted_shed[id(s)] += 1
            tickets[id(s)].append(t)
            i += 1
        now = engine.sched_step(now=now)
        if (not engine.scheduler._progress and i < len(events)
                and events[i][0] < now):
            now = events[i][0]
    from repro.serve.queue import DONE, FAILED, SHED
    makespan = max(now, events[-1][0]) if events else now
    per_stream = {}
    for s in streams:
        stats = {DONE: 0, SHED: submitted_shed[id(s)], FAILED: 0}
        for t in tickets[id(s)]:
            if t is None:
                continue
            st = engine._requests[t].status
            if st in stats:
                stats[st] += 1
        per_stream[s.get("tenant", "default")] = {
            "offered_qps": s["qps"], "completed": stats[DONE],
            "shed": stats[SHED], "failed": stats[FAILED],
            "goodput_qps": (stats[DONE] / makespan if makespan > 0 else 0.0)}
    return {"makespan_s": makespan, "per_stream": per_stream}


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=512,
                    help="rows per scoring request (any size; the batcher "
                         "pads/chunks onto the registered cell shapes)")
    ap.add_argument("--steps", type=int, default=50,
                    help="number of scoring requests to issue")
    ap.add_argument("--train-steps", type=int, default=120)
    ap.add_argument("--p99-rows", type=int, default=512,
                    help="serve_p99 cell capacity")
    ap.add_argument("--bulk-rows", type=int, default=4096,
                    help="serve_bulk cell capacity")
    ap.add_argument("--bulk", type=int, default=0,
                    help="also issue one bulk job of this many rows")
    ap.add_argument("--qps", type=float, default=None,
                    help="open-loop mode: offer --steps requests of --batch "
                         "rows at this rate with seeded exponential "
                         "inter-arrival times (offline replay of production "
                         "traffic); concurrent requests coalesce through the "
                         "admission queue onto shared padded cells")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="open-loop per-request deadline: requests still "
                         "queued past it are shed instead of dispatched")
    ap.add_argument("--queue-capacity", type=int, default=1024,
                    help="admission-queue bound (reject-on-full shedding)")
    ap.add_argument("--coalesce-window-ms", type=float, default=0.0,
                    help="max-wait coalescing window: hold a lane's light "
                         "load up to this long for a fuller bucket (0 "
                         "dispatches immediately)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the open-loop inter-arrival times")
    ap.add_argument("--hot-frac", type=float, default=None,
                    help="also serve through a hot/cold TieredTableStore "
                         "pinning this fraction of features device-resident "
                         "(repro.cache; requests go through score_tiered "
                         "with cold fills prefetched one chunk ahead)")
    ap.add_argument("--cache-policy", choices=("static", "decay"),
                    default=None,
                    help="tier policy over the TieredTableStore (requires "
                         "--hot-frac; open-loop requests then ride the "
                         "tiered lane): 'decay' adapts the hot set with "
                         "exponential-decay admission scores "
                         "(repro.cache.policy), 'static' keeps the "
                         "training-frequency split but runs the identical "
                         "observation/plan machinery as the baseline")
    ap.add_argument("--decay-halflife", type=float, default=256.0,
                    help="decay-policy score half-life, in observation "
                         "ticks (one tick per dispatched chunk)")
    ap.add_argument("--policy-every", type=int, default=8,
                    help="plan/apply tier moves every this many scheduling "
                         "rounds")
    ap.add_argument("--writeback", type=int, default=0,
                    help="every N open-loop requests, write the request's "
                         "features' master embeddings back through "
                         "Engine.writeback_embeddings (train→serve update "
                         "flow; 0 disables)")
    ap.add_argument("--drift", type=float, default=0.0,
                    help="non-stationary traffic: rotate each field's "
                         "popularity ranks by this many ids per request "
                         "step (DriftingCTR)")
    ap.add_argument("--shift-at", type=int, default=None,
                    help="hard popularity shift: from this request step on, "
                         "rotate each field's hot set by --shift-frac of "
                         "its vocabulary")
    ap.add_argument("--shift-frac", type=float, default=0.3,
                    help="fraction of each field's vocabulary the "
                         "--shift-at popularity shift moves")
    ap.add_argument("--repack-budget", type=float, default=None,
                    help="serving-time precision adaptation: halfway through "
                         "the request stream, plan a new per-group "
                         "assignment at this fraction of the current packed "
                         "payload bytes and swap it into the live cells "
                         "(repro.serve.repack; zero recompiles, asserted)")
    ap.add_argument("--repack-headroom", type=float, default=None,
                    help="pack the serving table with every non-zero width "
                         "bucket sized to hold this fraction of the features "
                         "(headroom_capacities), so repacks can move groups "
                         "between intermediate widths")
    ap.add_argument("--mesh", default=None,
                    help="'dp,mp', 'pod,dp,mp' or 'auto': compile the serve "
                         "cells against a (data, model) — or multi-pod "
                         "(pod, data, model) — device mesh: requests "
                         "batch-shard over the non-model axes, packed "
                         "subtables row-shard over model and the fused "
                         "lookup runs under shard_map (repro.dist.shard). "
                         "Virtualize CPU devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--lookup-comms", choices=("psum", "a2a"), default="psum",
                    help="model-axis comms for the sharded packed lookup: "
                         "'psum' merges full dequantized partials (default), "
                         "'a2a' all-to-alls the ids and ships back only the "
                         "packed quantized words each shard owns "
                         "(capacity-bucketed; bit-exact either way)")
    ap.add_argument("--bucket-capacity", type=int, default=None,
                    help="a2a ids per destination shard per batch slice "
                         "(default: the full slice, i.e. no overflow); "
                         "overflow ids spill deterministically to the psum "
                         "merge")
    ap.add_argument("--coordinator", default=None,
                    help="multi-host: coordinator address host:port for "
                         "jax.distributed.initialize (single-host runs "
                         "leave this unset)")
    ap.add_argument("--num-hosts", type=int, default=None,
                    help="multi-host: total process count")
    ap.add_argument("--host-id", type=int, default=None,
                    help="multi-host: this process's index in [0, num-hosts)")
    ap.add_argument("--json", default=None,
                    help="write the latency/compile summary to this path")
    args = ap.parse_args(argv)
    init_distributed(coordinator=args.coordinator,
                     num_processes=args.num_hosts, process_id=args.host_id)
    mesh = parse_mesh_flag(args.mesh)
    if mesh is not None:
        print(f"[serve] mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    cfg, params, state, buffers, spec, res = train_packed_dlrm(
        train_steps=args.train_steps)
    print(f"[serve] packed table: ratio={res['storage_ratio']:.4f} "
          f"bytes={res['packed_bytes']}")

    if args.repack_headroom is not None:
        from repro.core.inference import build_packed_table
        from repro.serve.repack import headroom_capacities
        emb = res["final_params"]["embedding"]
        caps = headroom_capacities(res["packed_meta"],
                                   fraction=args.repack_headroom)
        table, meta = build_packed_table(
            emb["emb"], res["feature_bits_idx"], emb["alpha"], emb["beta"],
            MPEConfig(lam=3e-5), row_capacities=caps)
        params["embedding"] = table
        res = dict(res, packed_table=table, packed_meta=meta)
        print(f"[serve] headroom capacities: {caps}")

    store = None
    if args.cache_policy is not None and args.hot_frac is None:
        ap.error("--cache-policy requires --hot-frac (a tiered store)")
    if args.hot_frac is not None:
        from repro.cache import TieredTableStore
        freqs = SyntheticCTR(spec).expected_frequencies()
        store = TieredTableStore(res["packed_table"], res["packed_meta"],
                                 freqs, args.hot_frac)
        s = store.storage()
        print(f"[serve] tiered store: hot_frac={args.hot_frac} "
              f"hot={s['hot_bytes']}B (device) cold={s['cold_bytes']}B (host)")

    engine = build_engine(cfg, params, state, buffers,
                          p99_rows=args.p99_rows, bulk_rows=args.bulk_rows,
                          store=store, mesh=mesh,
                          lookup_comms=args.lookup_comms,
                          bucket_capacity=args.bucket_capacity,
                          queue_capacity=args.queue_capacity,
                          coalesce_window_ms=args.coalesce_window_ms)
    print(f"[serve] registered cells: "
          f"{dict(sorted(engine.registered_shapes.items()))} "
          f"(compiles={engine.compile_count})")

    if args.cache_policy is not None:
        from repro.cache import DecayAdmissionPolicy, StaticTierPolicy
        if args.cache_policy == "decay":
            policy = DecayAdmissionPolicy(store.meta["n"],
                                          halflife=args.decay_halflife)
        else:
            policy = StaticTierPolicy()
        engine.attach_tier_policy(policy, every=args.policy_every)
        print(f"[serve] cache policy: {args.cache_policy} "
              f"(halflife={args.decay_halflife}, every={args.policy_every})")

    # request stream at the *requested* batch size — decoupled from training
    if args.drift or args.shift_at is not None:
        from repro.data.synthetic import DriftingCTR
        req_ds = DriftingCTR(spec._replace(batch_size=args.batch),
                             drift_rate=args.drift, shift_at=args.shift_at,
                             shift_frac=args.shift_frac, step0=10_000)
        print(f"[serve] drifting traffic: rate={args.drift} "
              f"shift_at={args.shift_at} shift_frac={args.shift_frac}")
    else:
        req_ds = SyntheticCTR(spec._replace(batch_size=args.batch))

    on_submit = None
    if args.writeback:
        master = np.asarray(res["final_params"]["embedding"]["emb"])
        offs = np.asarray(buffers["offsets"], np.int64)

        def on_submit(i, ids):
            if i == 0 or i % args.writeback:
                return
            gids = np.unique(np.asarray(ids, np.int64) + offs[None, :])
            engine.writeback_embeddings(gids, master[gids])

    repack_info = None

    def _queue_repack():
        """Plan at the budget and queue the swap — it lands atomically at
        the engine's next ``sched_step`` boundary, mid-stream."""
        nonlocal repack_info
        freqs = SyntheticCTR(spec).expected_frequencies()
        planner, swapper = repack_tools(engine, res, freqs)
        gbits = np.asarray(res["group_bits"])
        plan = planner.plan_budget(
            gbits, int(args.repack_budget * planner.bytes_packed(gbits)))
        swapper.repack(plan)
        repack_info = (engine.compile_count, plan)

    req_kind = "tiered" if args.cache_policy is not None else "score"
    open_loop = None
    if args.qps:
        warm_ids = req_ds.batch(9_999)["ids"]
        engine.score(warm_ids)                     # warm the cells
        if req_kind == "tiered":
            engine.score_tiered(warm_ids)
        if args.repack_budget is not None:
            _queue_repack()   # applies at the open loop's first round
        open_loop = run_open_loop(
            engine, lambda i: req_ds.batch(10_000 + i)["ids"], args.steps,
            args.qps, seed=args.seed, deadline_ms=args.deadline_ms,
            kind=req_kind, on_submit=on_submit)
    else:
        for step in range(args.steps):
            if args.repack_budget is not None and step == args.steps // 2:
                _queue_repack()
            ids = req_ds.batch(10_000 + step)["ids"]
            if on_submit is not None:
                on_submit(step, ids)
            engine.score(ids)
            if store is not None:
                engine.score_tiered(ids)
    if repack_info is not None:
        c0, plan = repack_info
        if engine.compile_count != c0:
            raise RuntimeError("serving-time repack recompiled a cell — the "
                               "zero-recompile invariant is broken")
        print(f"[serve] repack: bytes {plan.bytes_before} -> "
              f"{plan.bytes_packed} ({plan.n_features_moved} features "
              f"moved), swaps={engine.swaps_applied}, recompiles=0")
    if args.bulk:
        bulk_ds = SyntheticCTR(spec._replace(batch_size=args.bulk))
        bulk_ids = bulk_ds.batch(99_999)["ids"]
        engine.score(bulk_ids)
        if store is not None:
            engine.score_tiered(bulk_ids)

    skip = min(3, max(args.steps - 1, 0))  # drop compile-adjacent warmup
    print(f"[serve] batch={args.batch} steps={args.steps}"
          + (f" bulk={args.bulk}" if args.bulk else "")
          + (f" qps={args.qps}" if args.qps else ""))
    print(engine.stats.format_table(skip_warmup=skip))
    if open_loop is not None:
        print(f"[serve] open loop: offered={open_loop['offered_qps']:.1f}qps "
              f"goodput={open_loop['goodput_qps']:.1f}qps "
              f"completed={open_loop['completed']} shed={open_loop['shed']}")
        print(engine.rstats.format_table(skip_warmup=skip))
    counters = engine.counters()
    print(f"[serve] cell cache: compiles={counters['compiles']} "
          f"hits={counters['hits']} (warm process ⇒ zero recompiles)")
    occ = counters["occupancy"]
    if occ:
        print("[serve] occupancy: " + " ".join(
            f"{cell}={v['occupancy']:.2f}" for cell, v in occ.items()))
    if store is not None:
        c = store.counters()
        print(f"[serve] tiers: hit_rate={c['hit_rate']:.3f} "
              f"cold_bytes_moved={c['bytes_moved']}")
        if args.cache_policy is not None:
            m = engine.tier_moves
            print(f"[serve] tier policy: plans={m['plans']} "
                  f"promotions={m['promotions']} demotions={m['demotions']} "
                  f"moved_bytes={m['bytes']}")
        if args.writeback:
            print(f"[serve] writeback: writes={c['writebacks']} "
                  f"bytes={c['writeback_bytes']}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"batch": args.batch, "steps": args.steps,
                       "cells": engine.summary(skip_warmup=skip),
                       "requests": engine.request_summary(skip_warmup=skip),
                       "open_loop": ({k: v for k, v in open_loop.items()
                                      if k != "tickets"}
                                     if open_loop is not None else None),
                       "cache": counters,
                       "tiers": (store.counters() if store is not None
                                 else None),
                       "storage_ratio": res["storage_ratio"],
                       "packed_bytes": res["packed_bytes"]}, f, indent=2)
        print(f"[serve] wrote {args.json}")
    return engine


if __name__ == "__main__":
    main()
