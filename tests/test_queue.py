"""The request-lifecycle stack (ISSUE 5): admission queue, coalescing
scheduler, continuous-batching decode.

Covers the acceptance criteria end-to-end:

  - coalescing: N interleaved requests are bit-identical to the per-request
    path with fewer cell invocations, strictly higher occupancy and zero
    recompiles (CellCache counters);
  - the coalescing packer: seeded-numpy randomized sweeps over request-size
    mixes (no hypothesis in this env) asserting round-trip integrity — every
    request gets exactly its own rows back, none dropped or duplicated, also
    under shedding;
  - continuous batching: sequences of different lengths join/leave the
    running decode batch, token-identical to per-request decode, KV-cache
    slots recycled with no new compiles after warmup;
  - admission policy: bounded-queue shedding, deadline shedding, and the
    three-way queue-wait / batch-assembly / compute breakdown;
  - multi-tenant SLO scheduling (ISSUE 8): priority lanes + EDF order,
    per-tenant quotas (queue share sheds, in-flight rows defer), watermark
    load shedding, the max-wait coalescing window (exact virtual times via
    ``ManualClock``), per-kind/per-tenant counter traces, fault injection
    (a raising dispatch fails only its chunk's tickets; decode KV slots
    recycle), and seeded sweeps of the lifecycle_props invariants shared
    with the hypothesis suite in test_scheduler_props.py.
"""
import jax
import numpy as np
import pytest

import lifecycle_props as props
from repro.data.synthetic import SyntheticCTR
from repro.launch.serve import (build_engine, run_open_loop,
                                run_open_loop_mix, train_packed_dlrm)
from repro.serve import (AdmissionQueue, Engine, ManualClock, RequestBatcher,
                         RequestFailedError, TenantQuota, lm_decode_cell,
                         lm_decode_slotted_cell)


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------

def test_queue_fifo_and_kind_routing():
    q = AdmissionQueue(capacity=8)
    a = q.submit("score", "A", 3, now=0.0)
    b = q.submit("tiered", "B", 2, now=0.1)
    c = q.submit("score", "C", 5, now=0.2)
    ready, expired = q.take("score", now=1.0)
    assert [r.payload for r in ready] == ["A", "C"] and not expired
    assert a.ticket < c.ticket
    # the tiered request stayed queued, in order
    ready, _ = q.take("tiered", now=1.0)
    assert [r.payload for r in ready] == ["B"] and b is ready[0]
    assert len(q) == 0


def test_queue_sheds_on_full_and_counts():
    q = AdmissionQueue(capacity=2)
    assert q.submit("score", 0, 1, now=0.0) is not None
    assert q.submit("score", 1, 1, now=0.0) is not None
    assert q.submit("score", 2, 1, now=0.0) is None     # reject-on-full
    assert q.counters()["shed_full"] == 1
    assert q.counters()["admitted"] == 2
    with pytest.raises(ValueError):
        AdmissionQueue(capacity=0)


def test_queue_deadline_shed_at_take():
    q = AdmissionQueue(capacity=8)
    q.submit("score", "late", 1, now=0.0, deadline_ms=100.0)
    q.submit("score", "ok", 1, now=0.0, deadline_ms=10_000.0)
    ready, expired = q.take("score", now=1.0)   # 1s > 100ms deadline
    assert [r.payload for r in ready] == ["ok"]
    assert [r.payload for r in expired] == ["late"]
    assert q.counters()["shed_deadline"] == 1


# ---------------------------------------------------------------------------
# multi-tenant admission: priority lanes, EDF, quotas, watermark
# ---------------------------------------------------------------------------

def test_take_priority_lanes_then_edf_then_ticket():
    q = AdmissionQueue(capacity=16)
    q.submit("score", "p1-late", 1, now=0.0, priority=1, deadline_ms=100.0)
    q.submit("score", "p0-no-deadline", 1, now=0.0)
    q.submit("score", "p0-tight", 1, now=0.0, deadline_ms=900.0)
    q.submit("score", "p0-loose", 1, now=0.0, deadline_ms=5_000.0)
    q.submit("score", "p1-none", 1, now=0.0, priority=1)
    ready, _ = q.take("score", now=0.05)
    # lane 0 first (EDF inside: tight < loose < no-deadline), then lane 1
    assert [r.payload for r in ready] == \
        ["p0-tight", "p0-loose", "p0-no-deadline", "p1-late", "p1-none"]


def test_tenant_queue_share_quota_sheds_at_submit():
    q = AdmissionQueue(capacity=16,
                       quotas={"a": TenantQuota(max_queued=2)})
    assert q.submit("score", 0, 1, now=0.0, tenant="a") is not None
    assert q.submit("score", 1, 1, now=0.0, tenant="a") is not None
    assert q.submit("score", 2, 1, now=0.0, tenant="a") is None  # share full
    assert q.submit("score", 3, 1, now=0.0, tenant="b") is not None
    assert q.counters()["per_tenant"]["a"]["shed_quota"] == 1
    # draining frees the share
    ready, _ = q.take("score", now=1.0)
    assert len(ready) == 3
    assert q.submit("score", 4, 1, now=2.0, tenant="a") is not None


def test_tenant_inflight_quota_defers_not_sheds():
    q = AdmissionQueue(capacity=16,
                       quotas={"a": TenantQuota(max_inflight_rows=10)})
    r1 = q.submit("score", 0, 8, now=0.0, tenant="a")
    r2 = q.submit("score", 1, 8, now=0.0, tenant="a")
    ready, _ = q.take("score", now=1.0)
    assert ready == [r1]                    # r2 deferred: 16 rows > 10
    assert len(q) == 1 and r2.status == "queued"
    ready, _ = q.take("score", now=2.0)     # still over quota: stays queued
    assert ready == []
    q.release(r1)                           # r1 completes
    ready, _ = q.take("score", now=3.0)
    assert ready == [r2]
    assert q.counters()["shed_quota"] == 0  # deferral is not shedding
    # a request that could never dispatch is rejected outright
    with pytest.raises(ValueError, match="max_inflight_rows"):
        q.submit("score", 2, 11, now=4.0, tenant="a")


def test_watermark_sheds_background_lane_first():
    q = AdmissionQueue(capacity=4, shed_watermark=0.5)
    assert q.submit("score", 0, 1, now=0.0) is not None
    assert q.submit("score", 1, 1, now=0.0) is not None
    # depth 2 = 0.5 * 4: background (priority > 0) sheds, urgent admits
    assert q.submit("score", 2, 1, now=0.0, priority=1) is None
    assert q.submit("score", 3, 1, now=0.0) is not None
    assert q.counters()["shed_load"] == 1
    with pytest.raises(ValueError):
        AdmissionQueue(capacity=4, shed_watermark=0.0)
    with pytest.raises(ValueError):
        q.submit("score", 4, 1, now=0.0, priority=-1)


def test_per_kind_counter_trace_hand_computed():
    """Every admission counter, per kind and per tenant, traced by hand
    through a fixed sequence (the ``test_cache.py`` counter-trace style)."""
    q = AdmissionQueue(capacity=4, shed_watermark=0.75,
                       quotas={"b": TenantQuota(max_queued=1)})
    zero = {"admitted": 0, "shed_full": 0, "shed_deadline": 0,
            "shed_quota": 0, "shed_load": 0}

    q.submit("score", 0, 1, now=0.0, tenant="a")              # admitted
    q.submit("tiered", 1, 1, now=0.0, tenant="b")             # admitted
    q.submit("tiered", 2, 1, now=0.0, tenant="b")             # b share full
    q.submit("score", 3, 1, now=0.0, tenant="a", priority=2)  # depth 2 < 3
    # depth now 3 = 0.75 * 4: the next background arrival sheds on load
    q.submit("score", 4, 1, now=0.0, tenant="a", priority=2)  # shed_load
    q.submit("score", 5, 1, now=0.0, tenant="a")              # admitted (4)
    q.submit("score", 6, 1, now=0.0, tenant="a")              # shed_full
    c = q.counters()
    assert c["depth"] == 4 and c["capacity"] == 4
    assert c["per_kind"] == {
        "score": dict(zero, admitted=3, shed_full=1, shed_load=1),
        "tiered": dict(zero, admitted=1, shed_quota=1)}
    assert c["per_tenant"] == {
        "a": dict(zero, admitted=3, shed_full=1, shed_load=1),
        "b": dict(zero, admitted=1, shed_quota=1)}
    # totals are the per-kind sums
    assert (c["admitted"], c["shed_full"], c["shed_quota"], c["shed_load"],
            c["shed_deadline"]) == (4, 1, 1, 1, 0)

    # deadline shed at take lands in the expiring request's kind/tenant
    q2 = AdmissionQueue(capacity=4)
    q2.submit("score", 0, 1, now=0.0, deadline_ms=10.0, tenant="late")
    ready, expired = q2.take("score", now=1.0)
    assert not ready and len(expired) == 1
    assert q2.counters()["per_kind"]["score"]["shed_deadline"] == 1
    assert q2.counters()["per_tenant"]["late"]["shed_deadline"] == 1


def test_max_wait_window_hold_and_release():
    """``take(min_rows=, max_wait_s=)``: a light load holds (everything
    stays queued) until the bucket fills or the oldest request ages out —
    exact times, virtual clock."""
    q = AdmissionQueue(capacity=16)
    r1 = q.submit("score", 0, 5, now=0.0)
    # 5 rows < 64 and age 10ms < 100ms window: hold
    ready, _ = q.take("score", now=0.01, min_rows=64, max_wait_s=0.1)
    assert ready == [] and len(q) == 1 and r1.status == "queued"
    # bucket fills: dispatch immediately, well inside the window
    r2 = q.submit("score", 1, 60, now=0.02)
    ready, _ = q.take("score", now=0.03, min_rows=64, max_wait_s=0.1)
    assert ready == [r1, r2]
    for r in ready:
        q.release(r)
    # window expiry: a lone request dispatches once it's 100ms old
    r3 = q.submit("score", 2, 5, now=1.0)
    ready, _ = q.take("score", now=1.05, min_rows=64, max_wait_s=0.1)
    assert ready == []
    ready, _ = q.take("score", now=1.11, min_rows=64, max_wait_s=0.1)
    assert ready == [r3]
    # expired requests shed even while the lane holds
    q.submit("score", 3, 5, now=2.0, deadline_ms=10.0)
    q.submit("score", 4, 5, now=2.0)
    ready, expired = q.take("score", now=2.05, min_rows=64, max_wait_s=0.1)
    assert ready == [] and len(expired) == 1


# ---------------------------------------------------------------------------
# seeded property sweeps over the new knobs (shared with the hypothesis
# suite in test_scheduler_props.py via lifecycle_props)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(10))
def test_multilane_stream_invariants_randomized(seed):
    """Randomized tenant/priority/deadline/quota streams: no dropped or
    duplicated tickets, EDF order within a lane, quota ceilings never
    exceeded, counters consistent."""
    rng = np.random.default_rng(seed)
    specs = props.random_stream(rng, int(rng.integers(10, 80)))
    cfg = props.random_config(rng)
    result = props.drive_queue(specs, cfg)
    props.check_no_drop_no_dup(result)
    props.check_edf_order(result)
    props.check_quota_ceilings(result, cfg.get("quotas"))
    props.check_counters_consistent(result)


@pytest.mark.parametrize("seed", range(4))
def test_fifo_identity_degenerate_stream_randomized(seed):
    rng = np.random.default_rng(seed)
    props.check_fifo_identity(
        [int(n) for n in rng.integers(1, 100, size=rng.integers(1, 30))])


# ---------------------------------------------------------------------------
# coalescing packer: seeded randomized sweeps (no hypothesis in this env)
# ---------------------------------------------------------------------------

def _packer():
    return RequestBatcher({"p99": 64, "bulk": 256})


@pytest.mark.parametrize("seed", range(8))
def test_pack_round_trip_integrity_randomized(seed):
    """Every request gets exactly its own rows back — none dropped, none
    duplicated — across random request-size mixes."""
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(1, 12))
    sizes = [int(rng.integers(1, 700)) for _ in range(n_req)]
    reqs = [rng.integers(0, 1000, size=(n, 3)).astype(np.int32)
            for n in sizes]
    batcher = _packer()
    chunks = batcher.pack(sizes)

    # spans tile each request exactly, in order
    per_req_rows = {i: [] for i in range(n_req)}
    for chunk in chunks:
        assert chunk.n_valid <= chunk.rows
        covered = 0
        for span in chunk.spans:
            assert span.dst_start == covered       # spans tile the chunk
            covered += span.n
            per_req_rows[span.req].append((span.src_start, span.n))
        assert covered == chunk.n_valid
    for i, n in enumerate(sizes):
        spans = sorted(per_req_rows[i])
        assert spans[0][0] == 0
        assert sum(s[1] for s in spans) == n       # no drop, no dup
        pos = 0
        for start, ln in spans:
            assert start == pos                    # contiguous, in order
            pos += ln

    # gather/scatter round-trip through padded chunks
    sinks = [np.full((n, 3), -1, np.int32) for n in sizes]
    for chunk in chunks:
        rows = RequestBatcher.gather(reqs, chunk)
        padded, mask = RequestBatcher.pad(rows, chunk.rows)
        assert mask.sum() == chunk.n_valid
        RequestBatcher.scatter(padded[:chunk.n_valid], chunk, sinks)
    for got, want in zip(sinks, reqs):
        np.testing.assert_array_equal(got, want)


def test_pack_single_request_equals_plan():
    batcher = _packer()
    for n in (1, 64, 65, 300, 700):
        packed = batcher.pack([n])
        planned = batcher.plan(n)
        assert [(c.bucket, c.rows, c.start, c.n_valid) for c in packed] == \
            [(c.bucket, c.rows, c.start, c.n_valid) for c in planned]
        assert all(len(c.spans) == 1 and c.spans[0].req == 0
                   for c in packed)


def test_pack_rejects_empty_requests():
    with pytest.raises(ValueError):
        _packer().pack([5, 0, 3])


# ---------------------------------------------------------------------------
# engine-level coalescing (bit-identical, fewer dispatches, higher
# occupancy, zero recompiles)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg, params, state, buffers, spec, res = train_packed_dlrm(
        field_vocabs=(600, 400, 500), train_steps=25, train_batch=256, seed=3)
    engine = build_engine(cfg, params, state, buffers,
                          p99_rows=64, bulk_rows=256)
    return {"engine": engine, "cfg": cfg, "params": params, "state": state,
            "buffers": buffers, "spec": spec}


def _twin(served, queue_capacity=1024):
    """A fresh engine sharing the warm CellCache (registration is pure
    hits — no compiles), so per-engine stats/occupancy start clean."""
    from repro.models.dlrm import DLRM
    base = served["engine"]
    twin = Engine(mesh=base.mesh, cache=base.cache,
                  queue_capacity=queue_capacity)
    twin.register_packed_model(
        "dlrm", DLRM, served["cfg"], served["params"], served["state"],
        served["buffers"], shapes={"serve_p99": 64, "serve_bulk": 256})
    return twin


def _dispatches(engine):
    return sum(s["count"] for s in engine.summary().values())


def test_coalesced_bit_identical_fewer_cells_higher_occupancy(served):
    ds = SyntheticCTR(served["spec"]._replace(batch_size=20))
    reqs = [ds.batch(500 + i)["ids"] for i in range(8)]

    solo = _twin(served)
    per_request = [solo.score(r, return_logits=True) for r in reqs]
    solo_occ = solo.counters()["occupancy"]

    co = _twin(served)
    compiles_before = co.compile_count
    tickets = [co.submit(r) for r in reqs]     # N interleaved submissions
    co.drain()
    coalesced = [co.poll(t) for t in tickets]
    co_occ = co.counters()["occupancy"]

    # bit-identical results to the per-request path
    for a, b in zip(per_request, coalesced):
        np.testing.assert_array_equal(a, b)
    # fewer cell invocations (8 per-request dispatches vs packed chunks)
    assert _dispatches(co) < _dispatches(solo)
    # strictly higher occupancy on every cell the coalesced path used
    solo_total = (sum(v["valid_rows"] for v in solo_occ.values()),
                  sum(v["padded_rows"] for v in solo_occ.values()))
    co_total = (sum(v["valid_rows"] for v in co_occ.values()),
                sum(v["padded_rows"] for v in co_occ.values()))
    assert co_total[0] == solo_total[0] == 8 * 20   # same real rows
    assert co_total[1] < solo_total[1]              # fewer padded rows
    assert (co_total[0] / co_total[1]) > (solo_total[0] / solo_total[1])
    # zero recompiles: both twins re-keyed the warm executables
    assert co.compile_count == compiles_before == served["engine"].compile_count


def test_shedding_no_drop_no_dup(served):
    """Admitted requests complete with exactly their own rows even when the
    bounded queue sheds the overflow."""
    ds = SyntheticCTR(served["spec"]._replace(batch_size=10))
    reqs = [ds.batch(900 + i)["ids"] for i in range(6)]
    engine = _twin(served, queue_capacity=4)
    tickets = [engine.submit(r) for r in reqs]
    assert tickets[4] is None and tickets[5] is None   # shed at capacity 4
    assert engine.queue.counters()["shed_full"] == 2
    engine.drain()
    for r, t in zip(reqs[:4], tickets[:4]):
        np.testing.assert_array_equal(
            engine.poll(t), _twin(served).score(r, return_logits=True))
    assert engine.rstats.shed == 2


def test_deadline_shed_poll_raises(served):
    ds = SyntheticCTR(served["spec"]._replace(batch_size=5))
    engine = _twin(served)
    # virtual clock: request arrives at t=0 with a 50ms deadline; the first
    # scheduling round happens at t=1s, so it must shed, not dispatch
    t = engine.submit(ds.batch(1)["ids"], now=0.0, deadline_ms=50.0)
    engine.sched_step(now=1.0)
    with pytest.raises(RuntimeError, match="shed"):
        engine.poll(t)
    assert engine.queue.counters()["shed_deadline"] == 1


def test_request_summary_three_way_breakdown(served):
    ds = SyntheticCTR(served["spec"]._replace(batch_size=30))
    engine = _twin(served)
    for i in range(3):
        engine.score(ds.batch(50 + i)["ids"])
    rs = engine.request_summary()["score"]
    assert rs["count"] == 3
    for part in ("latency", "queue", "assembly", "compute"):
        assert rs[part]["p50_ms"] >= 0.0
        assert rs[part]["p50_ms"] <= rs[part]["p99_ms"] + 1e-9
    # per-cell summaries carry occupancy for every scored cell
    for cell in engine.summary().values():
        assert 0.0 < cell["occupancy"] <= 1.0


def test_open_loop_replay_queue_wait_under_overload(served):
    """Open-loop arrivals above capacity accumulate *virtual* queue wait —
    the wait is separable from compute in the breakdown."""
    ds = SyntheticCTR(served["spec"]._replace(batch_size=20))
    engine = _twin(served)
    engine.score(ds.batch(1)["ids"])       # warm the dispatch path
    res = run_open_loop(engine, lambda i: ds.batch(100 + i)["ids"],
                        12, 100_000.0, seed=0)   # absurd offered rate
    assert res["completed"] == 12 and res["shed"] == 0
    assert res["goodput_qps"] > 0
    rs = engine.request_summary()["score"]
    # all 12 arrive before the first dispatch completes: later requests wait
    assert rs["queue"]["p99_ms"] > 0.0


# ---------------------------------------------------------------------------
# multi-tenant scheduling at the engine level: bit-identity, manual clock,
# max-wait window, fault injection, two-tenant open loop
# ---------------------------------------------------------------------------

def _mt_twin(served, **engine_kw):
    """A fresh engine on the warm CellCache with multi-tenant knobs."""
    from repro.models.dlrm import DLRM
    base = served["engine"]
    twin = Engine(mesh=base.mesh, cache=base.cache, **engine_kw)
    twin.register_packed_model(
        "dlrm", DLRM, served["cfg"], served["params"], served["state"],
        served["buffers"], shapes={"serve_p99": 64, "serve_bulk": 256})
    return twin


def test_single_tenant_no_contention_bit_identical_zero_recompiles(served):
    """Acceptance: single-tenant/no-contention traffic through the priority
    scheduler (quotas + watermark + lanes all configured) is bit-identical
    to the plain FIFO path, with zero recompiles (CellCache-asserted)."""
    ds = SyntheticCTR(served["spec"]._replace(batch_size=20))
    reqs = [ds.batch(700 + i)["ids"] for i in range(6)]

    fifo = _twin(served)
    f_tickets = [fifo.submit(r) for r in reqs]
    fifo.drain()
    f_out = [fifo.poll(t) for t in f_tickets]

    compiles_before = served["engine"].compile_count
    mt = _mt_twin(served,
                  quotas={"default": TenantQuota(max_queued=1000,
                                                 max_inflight_rows=100_000)},
                  shed_watermark=0.9)
    m_tickets = [mt.submit(r) for r in reqs]      # all default tenant, p0
    mt.drain()
    m_out = [mt.poll(t) for t in m_tickets]

    for a, b in zip(f_out, m_out):
        np.testing.assert_array_equal(a, b)       # bit-identical
    assert mt.compile_count == compiles_before    # zero recompiles
    assert mt.queue.counters()["shed_quota"] == 0
    assert mt.queue.counters()["shed_load"] == 0


def test_manual_clock_exact_queue_wait(served):
    """With ``ManualClock`` injected, every lifecycle timestamp is virtual:
    queue-wait is exactly the time the test advanced, no wall-clock."""
    ds = SyntheticCTR(served["spec"]._replace(batch_size=5))
    clock = ManualClock()
    engine = _mt_twin(served, clock=clock)
    t = engine.submit(ds.batch(1)["ids"])        # arrives at clock()=0.0
    clock.advance(0.25)
    engine.sched_step()                          # dispatches at clock()=0.25
    req = engine._requests[t]
    assert req.queue_ms == pytest.approx(250.0)
    out = engine.poll(t)
    assert out is not None
    # compute was measured on the same (frozen) clock: exactly zero
    rs = engine.request_summary()["score"]
    assert rs["queue"]["p50_ms"] == pytest.approx(250.0)


def test_coalesce_window_holds_then_dispatches_engine(served):
    """The max-wait window at the engine level, exact virtual times: a
    light request holds; a second arrival filling the bucket releases it;
    a lone request dispatches at exactly arrival + window."""
    ds = SyntheticCTR(served["spec"]._replace(batch_size=5))
    engine = _mt_twin(served, coalesce_window_ms=100.0, clock=ManualClock())
    t1 = engine.submit(ds.batch(1)["ids"], now=0.0)       # 5 rows < 64
    engine.sched_step(now=0.01)
    assert engine._requests[t1].status == "queued"        # held
    big = SyntheticCTR(served["spec"]._replace(batch_size=60))
    t2 = engine.submit(big.batch(2)["ids"], now=0.02)     # 65 rows ≥ 64
    engine.sched_step(now=0.03)
    assert engine._requests[t1].dispatch_t == 0.03        # released together
    assert engine._requests[t2].dispatch_t == 0.03
    engine.drain(now=0.03)
    assert engine.poll(t1) is not None and engine.poll(t2) is not None

    # a lone light request: virtual drain jumps the cursor to the window
    # expiry instead of spinning, and dispatches exactly there
    t3 = engine.submit(ds.batch(3)["ids"], now=1.0)
    cursor = engine.drain(now=1.0)
    assert engine._requests[t3].dispatch_t == pytest.approx(1.1)
    assert cursor >= 1.1
    assert engine.poll(t3) is not None


def test_fault_injection_fails_only_affected_chunk(served):
    """A dispatch that raises mid-``sched_step`` fails exactly the requests
    riding that chunk: their poll raises ``RequestFailedError``, every
    other request completes bit-identically, and the engine stays
    drainable with zero stuck requests."""
    ds_big = SyntheticCTR(served["spec"]._replace(batch_size=256))
    ds_small = SyntheticCTR(served["spec"]._replace(batch_size=64))
    a, b = ds_big.batch(11)["ids"], ds_small.batch(12)["ids"]
    want_b = _twin(served).score(b, return_logits=True)

    engine = _twin(served)
    orig = engine._timed_call
    calls = {"n": 0}

    def flaky(reg, *request):
        calls["n"] += 1
        if calls["n"] == 1:           # the first chunk's compute dispatch
            raise RuntimeError("injected fault")
        return orig(reg, *request)

    engine._timed_call = flaky
    ta = engine.submit(a)             # 256 rows -> fills the bulk chunk
    tb = engine.submit(b)             # 64 rows -> its own p99 chunk
    engine.drain()
    engine._timed_call = orig

    with pytest.raises(RequestFailedError, match="injected fault"):
        engine.poll(ta)
    np.testing.assert_array_equal(engine.poll(tb), want_b)
    assert engine.rstats.failed == 1
    assert len(engine.queue) == 0 and not engine.scheduler.busy
    assert engine.queue.counters()["inflight_rows"] == {}   # quota released
    # the engine keeps serving after the fault
    np.testing.assert_array_equal(engine.score(b, return_logits=True), want_b)


def test_lookup_companion_fault_fails_its_chunk(served):
    """The lookup-split companion is not a best-effort side call: when it
    raises, the requests riding that chunk fail like a compute fault (a
    smoke run can then never pass with a broken lookup executable), and the
    other chunks still complete."""
    ds_big = SyntheticCTR(served["spec"]._replace(batch_size=256))
    ds_small = SyntheticCTR(served["spec"]._replace(batch_size=64))
    a, b = ds_big.batch(21)["ids"], ds_small.batch(22)["ids"]
    want_b = _twin(served).score(b, return_logits=True)

    engine = _twin(served)
    orig = engine._timed_call
    faults = {"n": 0}

    def broken_companion(reg, *request):
        if reg.celldef.kind == "lookup" and faults["n"] == 0:
            faults["n"] += 1
            raise RuntimeError("companion fault")
        return orig(reg, *request)

    engine._timed_call = broken_companion
    ta = engine.submit(a)             # bulk chunk: its companion raises
    tb = engine.submit(b)             # p99 chunk
    engine.drain()
    engine._timed_call = orig

    with pytest.raises(RequestFailedError, match="companion fault"):
        engine.poll(ta)
    np.testing.assert_array_equal(engine.poll(tb), want_b)
    assert engine.rstats.failed == 1
    assert len(engine.queue) == 0 and not engine.scheduler.busy


def test_two_tenant_skewed_priority_open_loop(served):
    """``run_open_loop_mix``: a latency tenant (priority 0) and a bulk
    tenant (priority 1, quota-bounded) share the engine; both make
    progress and the per-tenant/per-lane split is reported."""
    ds = SyntheticCTR(served["spec"]._replace(batch_size=20))
    engine = _mt_twin(
        served, quotas={"bulk": TenantQuota(max_inflight_rows=512)})
    engine.score(ds.batch(1)["ids"])            # warm the dispatch path
    streams = [
        {"tenant": "latency", "qps": 500.0, "n_requests": 10, "priority": 0},
        {"tenant": "bulk", "qps": 500.0, "n_requests": 10, "priority": 1},
    ]
    res = run_open_loop_mix(engine, lambda i, _b: ds.batch(300 + i)["ids"],
                            streams, seed=0)
    per = res["per_stream"]
    assert per["latency"]["completed"] == 10
    assert per["bulk"]["completed"] == 10
    assert per["latency"]["goodput_qps"] > 0
    lanes = engine.request_summary(by="lane")
    assert lanes["score:p0"]["count"] == 11     # + the warm request
    assert lanes["score:p1"]["count"] == 10
    tenants = engine.request_summary(by="tenant")
    assert set(tenants) >= {"latency", "bulk"}
    assert engine.counters()["goodput"]["by_tenant"]["bulk"] == 10


# ---------------------------------------------------------------------------
# continuous-batching decode
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_setup():
    from repro.models.lm import LM, LMConfig
    cfg = LMConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                   head_dim=16, d_ff=64, vocab=50, remat=False)
    params, buffers = LM.init(jax.random.PRNGKey(0), cfg)
    return cfg, params, buffers


def _reference_generate(engine, prompt, max_new):
    """Per-request decode through the classic cell: one sequence alone,
    fed token-by-token (prompt replay then greedy feedback)."""
    caches, out = None, []
    toks = list(np.asarray(prompt).reshape(-1))
    for i in range(len(toks) + max_new - 1):
        tok = toks[i] if i < len(toks) else out[-1]
        logits, caches = engine.decode(np.array([[tok]], np.int32), caches)
        if i >= len(toks) - 1:
            out.append(int(np.argmax(logits[0])))
    return out


def test_continuous_batching_token_identical_and_slot_reuse(lm_setup):
    """Sequences of different lengths join/leave the running batch:
    token-identical to per-request decode, slots recycled (5 sequences
    through a 2-slot cache), zero new compiles after warmup."""
    cfg, params, buffers = lm_setup
    engine = Engine()
    engine.register(lm_decode_slotted_cell(cfg, params, buffers, batch=2,
                                           max_len=16, arch="lm"))
    session = engine.scheduler.sessions["lm"]
    warm = engine.submit_decode([1, 2], 2)
    engine.drain()
    engine.poll(warm)
    compiles = engine.compile_count

    prompts = [[3, 7, 11], [5], [9, 2], [4, 4, 4, 4], [1]]
    tickets = [engine.submit_decode(p, 4) for p in prompts]
    engine.drain()
    outs = [engine.poll(t).tolist() for t in tickets]

    # joined/left the 2-slot pool: never more than 2 active, all 5 served
    assert session.cap == 2 and len(session.active) == 0
    assert sorted(session.free) == [0, 1]
    assert engine.compile_count == compiles        # no new compiles

    ref_engine = Engine()
    ref_engine.register(lm_decode_cell(cfg, params, buffers, batch=2,
                                       max_len=16, arch="lm"))
    for p, got in zip(prompts, outs):
        assert got == _reference_generate(ref_engine, p, 4)


def test_decode_deadline_holds_while_waiting_for_a_slot(lm_setup):
    """A decode request's deadline is enforced while it waits for a free
    slot, not only while it sits in the admission queue."""
    cfg, params, buffers = lm_setup
    engine = Engine()
    engine.register(lm_decode_slotted_cell(cfg, params, buffers, batch=1,
                                           max_len=16, arch="lm"))
    # t1 takes the only slot first (it joins before t2 even arrives — under
    # EDF a deadline-carrying request in the same round would go first);
    # t2 then waits for the slot with a 50ms deadline
    t1 = engine.submit_decode([1, 2], 8, now=0.0)
    cursor = engine.sched_step(now=0.0)
    t2 = engine.submit_decode([3], 2, now=cursor, deadline_ms=50.0)
    # t2 starts waiting behind t1; by the next round (1s later) t2's
    # deadline passed long ago — it must never take the slot t1 frees
    while engine.scheduler.busy:
        cursor = engine.sched_step(now=max(cursor, 1.0))
    assert engine.poll(t1) is not None
    with pytest.raises(RuntimeError, match="shed"):
        engine.poll(t2)
    assert engine.queue.counters()["shed_deadline"] == 1


def test_decode_fault_recycles_slots_and_stays_drainable(lm_setup):
    """A decode-cell dispatch that raises fails the active jobs (poll
    raises), recycles their KV slots back to the free list, and the session
    keeps serving new sequences — no restart, no recompile."""
    cfg, params, buffers = lm_setup
    engine = Engine()
    engine.register(lm_decode_slotted_cell(cfg, params, buffers, batch=2,
                                           max_len=16, arch="lm"))
    warm = engine.submit_decode([1, 2], 2)
    engine.drain()
    engine.poll(warm)
    session = engine.scheduler.sessions["lm"]
    compiles = engine.compile_count

    t1 = engine.submit_decode([3, 7], 4)
    t2 = engine.submit_decode([5], 4)
    orig = engine._timed_call
    calls = {"n": 0}

    def flaky(reg, *request):
        calls["n"] += 1
        if calls["n"] == 2:       # fail the second decode step, mid-stream
            raise RuntimeError("decode fault")
        return orig(reg, *request)

    engine._timed_call = flaky
    engine.drain()                # must terminate: failed jobs leave slots
    engine._timed_call = orig

    for t in (t1, t2):
        with pytest.raises(RequestFailedError, match="decode fault"):
            engine.poll(t)
    assert not session.active and sorted(session.free) == [0, 1]  # recycled
    assert engine.rstats.failed == 2
    # the recycled slots serve new sequences, still zero new compiles
    t3 = engine.submit_decode([9], 3)
    engine.drain()
    assert engine.poll(t3) is not None
    assert engine.compile_count == compiles


def test_submit_rejects_unroutable_kind(served):
    with pytest.raises(ValueError, match="unroutable"):
        _twin(served).submit(np.zeros((2, 3), np.int32), kind="retrieve")


def test_poll_consumes_ticket(served):
    ds = SyntheticCTR(served["spec"]._replace(batch_size=5))
    engine = _twin(served)
    t = engine.submit(ds.batch(7)["ids"])
    assert engine.poll(t) is None          # pending: not consumed
    engine.drain()
    assert engine.poll(t) is not None
    with pytest.raises(KeyError):          # consumed by the first poll
        engine.poll(t)


def test_decode_deadline_and_capacity_guard(lm_setup):
    cfg, params, buffers = lm_setup
    engine = Engine()
    engine.register(lm_decode_slotted_cell(cfg, params, buffers, batch=2,
                                           max_len=8, arch="lm"))
    # a sequence that can't fit the compiled cache length is rejected at
    # submission (it could never join the slot pool)
    with pytest.raises(ValueError, match="max_len"):
        engine.submit_decode([1, 2, 3, 4, 5], 6)
    # occupancy of the decode cell reflects active slots per step
    t2 = engine.submit_decode([1, 2], 3)
    engine.drain()
    assert engine.poll(t2) is not None
    occ = engine.counters()["occupancy"]["lm/decode_cb"]
    assert 0.0 < occ["occupancy"] <= 1.0
