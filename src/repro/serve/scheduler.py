"""Coalescing scheduler + continuous-batching decode.

The dispatch edge of the request lifecycle: the scheduler drains the
``AdmissionQueue`` and turns *many* callers' requests into *few* cell-shaped
dispatches on the compiled-cell substrate (``CellCache`` executables — never
recompiled, never reshaped):

  - **score / tiered lanes** — pending requests come out of the queue in
    priority/EDF order (the queue owns lane ordering and per-tenant quotas)
    and are coalesced by ``RequestBatcher.pack`` into the registered cell
    shapes: one padded cell invocation carries row spans from many requests,
    and the outputs scatter back per requester (``Chunk.spans``). Concurrent
    small requests stop burning whole cells on padding — occupancy, not
    recompiles, absorbs the traffic mix.
  - **max-wait coalescing window** — with ``coalesce_window_ms > 0`` a lane
    *holds* a light load (fewer pending rows than the smallest registered
    bucket) for up to the window, trading p99 for occupancy; the window
    expires against the same clock that stamps arrivals, so held requests
    dispatch at a deterministic time on a virtual timeline. ``0`` (the
    default) dispatches immediately — exactly the pre-window behaviour.
  - **decode lane** — a ``DecodeSession`` per registered
    ``lm_decode_slotted_cell`` runs *continuous batching*: the compiled batch
    dim is a pool of KV-cache slots with a free-list; a request joins by
    taking a free slot at length 0 and replaying its prompt token-by-token
    through the running batch (other slots keep decoding their own
    sequences), and a finished sequence's slot is recycled for the next
    waiting request without recompiling or restarting the batch.
  - **fault isolation** — a dispatch that raises fails only the requests
    riding that chunk (status ``FAILED``; ``poll`` re-raises with the
    original error) and, on the decode lane, recycles the failed jobs' KV
    slots; every other pending request keeps flowing and the engine stays
    drainable.

Time is driven by the caller: ``step(now=None)`` uses the engine's clock
(live serving), while an explicit ``now`` advances a virtual timeline by
measured work (deterministic open-loop replay — ``launch/serve.py --qps``).
Either way, per-request queue-wait / batch-assembly / compute land in
``RequestStats`` tagged with the request's tenant and priority lane.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.serve.batcher import RequestBatcher
from repro.serve.queue import DISPATCHED, DONE, FAILED

# lanes the scheduler coalesces through RequestBatcher.pack (decode is the
# continuous-batching lane and paces itself)
SCORED_KINDS = ("score", "tiered")


class DecodeJob:
    """One generation request inside a ``DecodeSession``: replay the prompt,
    then greedy-decode ``max_new`` tokens."""
    __slots__ = ("req", "prompt", "fed", "out", "max_new")

    def __init__(self, req, prompt: np.ndarray, max_new: int):
        self.req = req
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.fed = 0          # tokens fed into the cell so far
        self.out: list[int] = []
        self.max_new = int(max_new)

    def next_token(self) -> int:
        """The next input token: prompt replay first, then feed back the
        previously generated token."""
        if self.fed < len(self.prompt):
            return int(self.prompt[self.fed])
        return self.out[self.fed - len(self.prompt)]

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new


class DecodeSession:
    """A persistent decode batch: one compiled slotted cell, one device-
    resident KV cache whose batch dim is a slot pool, and the free-list that
    recycles slots between steps."""

    def __init__(self, reg):
        self.reg = reg
        self.cap = reg.celldef.batch
        self.max_len = reg.celldef.meta["max_len"]
        n_bound = len(reg.bound)
        self._tok_sh = reg.cell.in_shardings[n_bound]
        self._lens_sh = reg.cell.in_shardings[n_bound + 1]
        self._cache_sh = reg.cell.in_shardings[n_bound + 2]
        self.caches = jax.device_put(reg.celldef.make_request_state(),
                                     self._cache_sh)
        self.lens = np.zeros((self.cap,), np.int32)
        self.free = list(range(self.cap - 1, -1, -1))
        self.active: dict[int, DecodeJob] = {}
        self.waiting: list[DecodeJob] = []
        self.steps = 0

    def admit(self, job: DecodeJob):
        if len(job.prompt) + job.max_new > self.max_len:
            raise ValueError(
                f"sequence of {len(job.prompt)}+{job.max_new} tokens exceeds "
                f"the cell's max_len={self.max_len}")
        self.waiting.append(job)

    @property
    def busy(self) -> bool:
        return bool(self.active or self.waiting)

    def join_waiting(self, now: float):
        """Move waiting jobs into free cache slots (joining the running
        batch is the job's dispatch moment)."""
        while self.waiting and self.free:
            slot = self.free.pop()
            job = self.waiting.pop(0)
            self.lens[slot] = 0
            self.active[slot] = job
            job.req.status = DISPATCHED
            job.req.dispatch_t = now
            job.req.queue_ms = (now - job.req.arrival_t) * 1e3

    def step_tokens(self) -> np.ndarray:
        tokens = np.zeros((self.cap, 1), np.int32)
        for slot, job in self.active.items():
            tokens[slot, 0] = job.next_token()
        return tokens

    def advance(self, logits: np.ndarray, step_ms: float, assembly_ms: float,
                now: float, rstats, queue) -> list[DecodeJob]:
        """Account one decode step: feed counters advance, prompt-done slots
        emit a greedy token, finished jobs release their slot. Returns the
        jobs completed this step."""
        completed = []
        share = step_ms / max(len(self.active), 1)
        asm_share = assembly_ms / max(len(self.active), 1)
        for slot, job in list(self.active.items()):
            job.fed += 1
            self.lens[slot] += 1
            job.req.compute_ms += share
            job.req.assembly_ms += asm_share
            if job.fed >= len(job.prompt):
                job.out.append(int(np.argmax(logits[slot])))
            if job.done:
                req = job.req
                req.result = np.asarray(job.out, np.int32)
                req.status = DONE
                req.complete_t = now
                req.payload = None
                queue.release(req)
                rstats.record("decode", queue_ms=req.queue_ms or 0.0,
                              assembly_ms=req.assembly_ms,
                              compute_ms=req.compute_ms,
                              latency_ms=req.latency_ms,
                              tenant=req.tenant, priority=req.priority)
                del self.active[slot]
                self.free.append(slot)   # recycled, never recompiled
                completed.append(job)
        self.steps += 1
        return completed

    def fail_active(self, err: Exception, now: float, rstats, queue):
        """A decode dispatch raised: fail every active job, recycle their KV
        slots (the free-list grows back to capacity for those slots — stale
        cache contents are harmless because a joining job resets its slot's
        length to 0), and leave waiting jobs queued for the next round."""
        msg = f"{type(err).__name__}: {err}"
        for slot, job in list(self.active.items()):
            req = job.req
            req.status = FAILED
            req.error = msg
            req.complete_t = now
            req.payload = None
            queue.release(req)
            rstats.record_failed("decode", tenant=req.tenant)
            del self.active[slot]
            self.free.append(slot)


class Scheduler:
    """Drains the admission queue into coalesced cell dispatches.

    One ``step`` handles each lane once: score and tiered requests are
    coalesced onto their cell-shape registries (in the queue's priority/EDF
    order, subject to tenant quotas and the max-wait window); every decode
    session with active slots advances one token. ``step`` returns the
    advanced ``now`` cursor so an open-loop driver can thread a virtual
    timeline through it — when a round dispatches nothing because every lane
    is holding for its coalescing window, the returned cursor jumps to the
    earliest window expiry so virtual drains terminate.
    """

    def __init__(self, engine, *, coalesce_window_ms: float = 0.0):
        if coalesce_window_ms < 0:
            raise ValueError(
                f"coalesce_window_ms must be >= 0, got {coalesce_window_ms}")
        self.engine = engine
        self.coalesce_window_ms = float(coalesce_window_ms)
        self.sessions: dict[str, DecodeSession] = {}   # arch -> session
        self._progress = False     # did this step dispatch anything?

    def add_session(self, arch: str, reg) -> DecodeSession:
        session = DecodeSession(reg)
        self.sessions[arch] = session
        return session

    @property
    def busy(self) -> bool:
        return bool(len(self.engine.queue)
                    or any(s.busy for s in self.sessions.values()))

    # -- clock helpers ------------------------------------------------------

    def _advance(self, cursor: float, elapsed_s: float, wall: bool) -> float:
        return self.engine._clock() if wall else cursor + elapsed_s

    def _next_window_expiry(self) -> float | None:
        """Earliest max-wait-window expiry across lanes with pending work."""
        if self.coalesce_window_ms <= 0:
            return None
        window_s = self.coalesce_window_ms / 1e3
        oldest = [self.engine.queue.oldest_arrival(kind)
                  for kind in SCORED_KINDS]
        expiries = [t + window_s for t in oldest if t is not None]
        return min(expiries) if expiries else None

    # -- one scheduling round ----------------------------------------------

    def step(self, *, now: float | None = None) -> float:
        wall = now is None
        cursor = self.engine._clock() if wall else float(now)
        self._progress = False
        cursor = self._dispatch_scored("score", cursor, wall)
        cursor = self._dispatch_scored("tiered", cursor, wall)
        cursor = self._dispatch_decode(cursor, wall)
        if not wall and not self._progress:
            # every lane held for its coalescing window: jump the virtual
            # cursor to the earliest expiry so drain() terminates
            expiry = self._next_window_expiry()
            if expiry is not None and expiry > cursor:
                cursor = expiry
        return cursor

    def _shed_expired(self, expired):
        for req in expired:
            self.engine.rstats.record_shed(req.kind, tenant=req.tenant)

    # -- score / tiered lanes ----------------------------------------------

    def _take(self, kind: str, cursor: float):
        """Drain one scored lane, applying the max-wait coalescing window:
        below the smallest bucket's row count the lane holds (everything
        stays queued) until the oldest pending request ages past the
        window."""
        engine = self.engine
        if self.coalesce_window_ms > 0:
            batcher = (engine._score_batcher if kind == "score"
                       else engine._tiered_batcher)
            min_rows = min(batcher.shapes.values()) if batcher.shapes else 0
            return engine.queue.take(kind, now=cursor, min_rows=min_rows,
                                     max_wait_s=self.coalesce_window_ms / 1e3)
        return engine.queue.take(kind, now=cursor)

    def _fail_chunk(self, ready, chunk, err: Exception, cursor: float,
                    kind: str):
        """Fault isolation: a dispatch raised — fail exactly the requests
        with rows in this chunk (later chunks skip their spans), release
        their quota, and keep the round going."""
        msg = f"{type(err).__name__}: {err}"
        for span in chunk.spans:
            req = ready[span.req]
            if req.status == FAILED:
                continue
            req.status = FAILED
            req.error = msg
            req.complete_t = cursor
            self.engine.queue.release(req)
            self.engine.rstats.record_failed(kind, tenant=req.tenant)

    def _dispatch_scored(self, kind: str, cursor: float, wall: bool) -> float:
        engine = self.engine
        table = engine._score if kind == "score" else engine._tiered
        ready, expired = self._take(kind, cursor)
        self._shed_expired(expired)
        if not ready:
            return cursor
        self._progress = True

        for req in ready:
            req.result = np.empty((req.n_rows,), np.float32)
        batcher = (engine._score_batcher if kind == "score"
                   else engine._tiered_batcher)
        chunks = batcher.pack([r.n_rows for r in ready])

        if kind == "tiered":
            return self._dispatch_tiered(ready, chunks, cursor, wall)

        for chunk in chunks:
            reg = table[chunk.bucket]
            try:
                t0 = engine._clock()
                rows = RequestBatcher.gather([r.payload for r in ready], chunk)
                padded, _mask = RequestBatcher.pad(rows, chunk.rows)
                # numpy straight into device_put: jnp.asarray first would
                # cost a second host->device transfer per dispatch
                x = jax.device_put(padded,
                                   reg.cell.in_shardings[len(reg.bound)])
                assembly_ms = (engine._clock() - t0) * 1e3
                self._mark_dispatch(ready, chunk, cursor)
                y, total_ms = engine._timed_call(reg, x)
                # the lookup-split companion fails its chunk like the cell
                # itself: a broken lookup executable must not pass unseen
                lookup_ms = (None if reg.lookup is None
                             else engine._timed_call(reg.lookup, x)[1])
            except Exception as err:   # fault injection: fail only this chunk
                self._fail_chunk(ready, chunk, err, cursor, kind)
                continue
            engine.stats.record(reg.celldef.name, total_ms, lookup_ms,
                                valid_rows=chunk.n_valid,
                                capacity_rows=chunk.rows)
            cursor = self._advance(cursor, (assembly_ms + total_ms) / 1e3,
                                   wall)
            self._scatter(ready, chunk, np.asarray(y), assembly_ms, total_ms,
                          cursor, kind)
        return cursor

    def _dispatch_tiered(self, ready, chunks, cursor: float,
                         wall: bool) -> float:
        """Tiered chunks stage each chunk's cold fill one chunk ahead of the
        in-flight compute (mirrors the pre-lifecycle ``score_tiered``).
        ``overlap=False`` on every coalesced request stages synchronously —
        the reference timing."""
        engine = self.engine
        overlap = all((r.meta or {}).get("overlap", True) for r in ready)
        payloads = [r.payload for r in ready]

        def stage(chunk):
            t0 = engine._clock()
            tc = engine._tiered[chunk.bucket]
            rows = RequestBatcher.gather(payloads, chunk)
            padded, mask = RequestBatcher.pad(rows, chunk.rows)
            fill = tc.store.prefetch_cold(padded + tc.offsets[None, :],
                                          valid=mask)
            x = jax.device_put(padded,
                               tc.reg.cell.in_shardings[len(tc.reg.bound)])
            return tc, x, fill, (engine._clock() - t0) * 1e3

        def safe_stage(chunk):
            try:
                return stage(chunk)
            except Exception as err:   # staged one ahead: defer to its chunk
                return err

        staged = safe_stage(chunks[0]) if overlap else None
        for k, chunk in enumerate(chunks):
            try:
                if overlap:
                    if isinstance(staged, Exception):
                        raise staged
                    tc, x, fill, assembly_ms = staged
                else:
                    tc, x, fill, assembly_ms = stage(chunk)
                self._mark_dispatch(ready, chunk, cursor)
                t0 = engine._clock()
                cold = tc.store.cold_part(fill).reshape(
                    x.shape[0], x.shape[1], -1)
                cold = jax.device_put(
                    cold, tc.reg.cell.in_shardings[len(tc.reg.bound) + 1])
                y = tc.reg.cell.compiled(*tc.reg.bound, x, cold)
                if overlap and k + 1 < len(chunks):
                    staged = safe_stage(chunks[k + 1])   # under y's compute
                # deliberate timing barrier: chunk latency feeds engine.stats
                jax.block_until_ready(y)  # staticcheck: ignore[RL403]
                total_ms = (engine._clock() - t0) * 1e3
            except Exception as err:   # fault injection: fail only this chunk
                self._fail_chunk(ready, chunk, err, cursor, "tiered")
                if overlap and k + 1 < len(chunks):
                    staged = safe_stage(chunks[k + 1])
                continue
            engine.stats.record(tc.reg.celldef.name, total_ms,
                                valid_rows=chunk.n_valid,
                                capacity_rows=chunk.rows)
            cursor = self._advance(cursor, (assembly_ms + total_ms) / 1e3,
                                   wall)
            self._scatter(ready, chunk, np.asarray(y), assembly_ms, total_ms,
                          cursor, "tiered")
        return cursor

    @staticmethod
    def _mark_dispatch(ready, chunk, cursor: float):
        for span in chunk.spans:
            req = ready[span.req]
            if req.dispatch_t is None:
                req.status = DISPATCHED
                req.dispatch_t = cursor
                req.queue_ms = (cursor - req.arrival_t) * 1e3

    def _scatter(self, ready, chunk, y: np.ndarray, assembly_ms: float,
                 compute_ms: float, cursor: float, kind: str):
        """Write a chunk's outputs back per requester and complete requests
        whose rows all arrived; assembly/compute attribute to requests in
        proportion to their rows in the chunk."""
        live = [s for s in chunk.spans if ready[s.req].status != FAILED]
        RequestBatcher.scatter(
            y, chunk._replace(spans=tuple(live)), [r.result for r in ready])
        for span in live:
            req = ready[span.req]
            frac = span.n / chunk.n_valid
            req.assembly_ms += assembly_ms * frac
            req.compute_ms += compute_ms * frac
            req.rows_done += span.n
            if req.rows_done == req.n_rows:
                req.status = DONE
                req.complete_t = cursor
                req.payload = None      # drop the ids; only the result stays
                self.engine.queue.release(req)
                self.engine.rstats.record(
                    kind, queue_ms=req.queue_ms, assembly_ms=req.assembly_ms,
                    compute_ms=req.compute_ms, latency_ms=req.latency_ms,
                    tenant=req.tenant, priority=req.priority)

    # -- decode lane (continuous batching) ----------------------------------

    def _dispatch_decode(self, cursor: float, wall: bool) -> float:
        engine = self.engine
        ready, expired = engine.queue.take("decode", now=cursor)
        self._shed_expired(expired)
        for req in ready:
            prompt, max_new, arch = req.payload
            session = self._pick_session(arch)
            session.admit(DecodeJob(req, prompt, max_new))
        for session in self.sessions.values():
            self._shed_expired_waiting(session, cursor)
            session.join_waiting(cursor)
            if not session.active:
                continue
            self._progress = True
            try:
                t0 = engine._clock()
                # fresh numpy buffers straight into device_put (one transfer
                # each); lens is copied because the session mutates it in
                # place
                tokens = jax.device_put(session.step_tokens(),
                                        session._tok_sh)
                lens = jax.device_put(session.lens.copy(), session._lens_sh)
                assembly_s = engine._clock() - t0
                (logits, new_caches), total_ms = engine._timed_call(
                    session.reg, tokens, lens, session.caches)
            except Exception as err:   # fail active jobs, recycle their slots
                session.fail_active(err, cursor, engine.rstats, engine.queue)
                session.join_waiting(cursor)
                continue
            session.caches = new_caches
            engine.stats.record(session.reg.celldef.name, total_ms,
                                valid_rows=len(session.active),
                                capacity_rows=session.cap)
            cursor = self._advance(cursor, assembly_s + total_ms / 1e3, wall)
            session.advance(np.asarray(logits), total_ms, assembly_s * 1e3,
                            cursor, engine.rstats, engine.queue)
            session.join_waiting(cursor)   # freed slots recycle immediately
        return cursor

    def _shed_expired_waiting(self, session: DecodeSession, now: float):
        """Deadlines hold while a job waits for a slot, not just while it
        sits in the admission queue: a waiting job past its deadline is shed
        before it can take a freed slot."""
        keep = []
        for job in session.waiting:
            req = job.req
            if req.deadline_t is not None and now > req.deadline_t:
                self.engine.queue.note_shed(req, now=now)
                self.engine.rstats.record_shed("decode", tenant=req.tenant)
            else:
                keep.append(job)
        session.waiting = keep

    def _pick_session(self, arch: str | None) -> DecodeSession:
        if not self.sessions:
            raise ValueError("no continuous-batching decode cell registered "
                             "(register an lm_decode_slotted_cell)")
        if arch is not None:
            return self.sessions[arch]
        if len(self.sessions) > 1:
            raise ValueError(
                f"multiple decode sessions ({sorted(self.sessions)}); "
                f"pass arch=")
        return next(iter(self.sessions.values()))
