"""Sequence batcher: stateful-model scheduling by correlation ID.

Reproduces the reference's *inference* sequence semantics (SURVEY.md §5.7):
requests carry ``sequence_id`` + ``sequence_start``/``sequence_end`` flags
(/root/reference/src/c++/library/common.h:173-184); all requests of a live
sequence route to the same model state, in order.

TPU-first state design: sequence state is an explicit JAX pytree threaded
through a pure ``apply(state, inputs) -> (state, outputs)`` function — no
hidden module state — so the whole step stays jittable and the state lives in
HBM between requests. The 'direct' strategy pins each live sequence to a
serialized execution lane (a per-sequence lock), mirroring the reference's
1-context-per-sequence concurrency rule
(concurrency_manager.cc:148-152, 302-335).

The 'oldest' strategy (Triton's oldest-sequence batcher) batches steps of
*different* live sequences into one XLA execution: sequence states live in a
fixed-capacity HBM **arena** (one pytree with leading dim = capacity + 1
dummy row), and a single jitted program gathers the batch's rows, applies
the vmapped step, and scatters the new states back — so N concurrent
sequences cost one device round trip per step wave instead of N
(:class:`OldestSequenceScheduler`).
"""

from __future__ import annotations

import queue as _queue
import threading
from client_tpu.utils import lockdep
from client_tpu import config as envcfg

import numpy as np

from client_tpu.engine.scheduler import (
    Scheduler,
    _SHUTDOWN,
    _SHUTDOWN_LEVEL,
    power_buckets,
)
from client_tpu.engine.types import (
    EngineError,
    InferRequest,
    InferResponse,
    now_ns,
)


class _SequenceSlot:
    __slots__ = ("state", "lock", "last_used_ns", "inflight")

    def __init__(self, state):
        self.state = state
        self.lock = lockdep.Lock("sequence.slot")
        self.last_used_ns = now_ns()
        # Executions holding this slot right now. last_used_ns is only
        # written AFTER a step completes, so idle-GC judging by timestamp
        # alone would evict a slot whose step merely outlasts the idle
        # window — silently resetting live sequence state. GC must skip
        # any slot with inflight > 0.
        self.inflight = 0


class _PendingGuard:
    """Queued-request counts per sequence id (mixin).

    Arrival-time refresh narrows but cannot close the idle-GC race: a
    request queued longer than the idle window (slow steps ahead of it)
    still has inflight == 0 until execution starts, so GC judged by
    timestamp alone would evict its slot mid-queue. GC must skip any
    sequence with pending > 0. The host class supplies the guarding lock
    via ``_pending_lock`` and initializes ``self._pending = {}``."""

    _pending: dict[int, int]

    def _pending_lock(self) -> threading.Lock:
        raise NotImplementedError

    def _pend_locked(self, sid: int) -> None:
        """Caller holds ``_pending_lock()``."""
        self._pending[sid] = self._pending.get(sid, 0) + 1

    def _unpend(self, sid: int) -> None:
        if not sid:
            return
        with self._pending_lock():
            n = self._pending.get(sid, 0) - 1
            if n > 0:
                self._pending[sid] = n
            else:
                self._pending.pop(sid, None)


class SequenceScheduler(_PendingGuard, Scheduler):
    """Routes requests to per-sequence state; executes via the stateful
    jitted apply."""

    def __init__(self, model, stats):
        self._slots: dict[int, _SequenceSlot] = {}
        self._slots_lock = lockdep.Lock("sequence.slots")
        self._pending: dict[int, int] = {}
        super().__init__(model, stats)

    def _pending_lock(self):
        return self._slots_lock

    def submit(self, req: InferRequest) -> None:
        # Arrival IS a use: refresh liveness at enqueue so a request waiting
        # in the queue can't watch its own sequence be idle-GC'd (queue
        # delay is engine load, not client idleness).
        if req.sequence_id:
            with self._slots_lock:
                slot = self._slots.get(req.sequence_id)
                if slot is not None:
                    slot.last_used_ns = now_ns()
                self._pend_locked(req.sequence_id)
        try:
            super().submit(req)
        except Exception:
            self._unpend(req.sequence_id)  # rejected at enqueue
            raise

    def _worker_loop(self) -> None:
        while True:
            item = self.queue.get()
            if item is _SHUTDOWN:
                return
            req: InferRequest = item
            # Unpend only after processing: with several worker instances,
            # unpending at dequeue would reopen the window (pending 0,
            # inflight 0, stale timestamp) between dequeue and the slot's
            # inflight claim in _run_one, letting a sibling worker's GC
            # evict the slot out from under this request.
            try:
                if self._check_timeout(req) or self._check_cancelled(req):
                    continue
                try:
                    self._run_one(req)
                except Exception as exc:  # noqa: BLE001
                    self._fail(req, exc)
            finally:
                self._unpend(req.sequence_id)

    def _get_slot(self, req: InferRequest) -> _SequenceSlot:
        sid = req.sequence_id
        with self._slots_lock:
            slot = self._slots.get(sid)
            if req.sequence_start or slot is None:
                if slot is None and not req.sequence_start:
                    raise EngineError(
                        f"sequence {sid}: request without start flag for an "
                        "inactive sequence", 400)
                slot = _SequenceSlot(self.model.backend.initial_state())
                self._slots[sid] = slot
            # Claim before GC runs so neither this slot nor any slot with a
            # step in flight can be evicted out from under its execution.
            slot.inflight += 1
            self._gc_idle_locked()
            return slot

    def _put_slot(self, slot: _SequenceSlot) -> None:
        with self._slots_lock:
            slot.inflight -= 1
            slot.last_used_ns = now_ns()

    def _gc_idle_locked(self) -> None:
        sb = self.model.config.sequence_batching
        if sb is None:
            return
        idle_ns = sb.max_sequence_idle_microseconds * 1000
        cutoff = now_ns() - idle_ns
        dead = [sid for sid, s in self._slots.items()
                if s.last_used_ns < cutoff and s.inflight == 0
                and self._pending.get(sid, 0) == 0]
        for sid in dead:
            del self._slots[sid]

    def _run_one(self, req: InferRequest) -> None:
        if req.sequence_id == 0:
            raise EngineError(
                f"model '{self.model.config.name}' uses sequence batching; "
                "requests must carry a non-zero sequence id", 400)
        slot = self._get_slot(req)
        start = now_ns()
        req.times.compute_start = start
        try:
            # In-order, one in-flight request per sequence: the device
            # step IS this lock's critical section (the reference's
            # 1-context-per-sequence rule), so blocking under it is the
            # design, not a bug.
            with slot.lock, lockdep.allow_blocking():
                new_state, outputs = self.model.execute_stateful(
                    slot.state, req.inputs)
                slot.state = new_state
        finally:
            self._put_slot(slot)
        if req.sequence_end:
            with self._slots_lock:
                self._slots.pop(req.sequence_id, None)
        req.times.compute_input_end = start
        req.times.compute_infer_end = now_ns()
        req.times.compute_output_end = req.times.compute_infer_end
        self.stats.record_execution(
            1, compute_ns=req.times.compute_infer_end - start)
        if req.outputs:
            requested = {o.name for o in req.outputs}
            outputs = {k: v for k, v in outputs.items() if k in requested}
        self.stats.record_request(req.times, success=True)
        self._respond(req, InferResponse(
            model_name=req.model_name,
            model_version=req.model_version or str(self.model.config.version),
            request_id=req.request_id,
            outputs=outputs,
            times=req.times,
        ))

    def active_sequences(self) -> int:
        with self._slots_lock:
            return len(self._slots)


class OldestSequenceScheduler(_PendingGuard, Scheduler):
    """Triton's OLDEST sequence-batcher strategy, TPU-first.

    Design: sequence state is a fixed-capacity arena pytree in HBM
    (leading dim ``max_candidate_sequences`` + 1; the extra row absorbs
    padded lanes so masked scatters never touch a live sequence). One
    jitted executable per batch bucket does gather(rows) → where(reset,
    initial_state, state) → vmap(apply) → scatter(rows), with the arena
    donated (``donate_argnums``) so state updates happen in place. A step
    wave over N live sequences is ONE device round trip; the reference's
    direct strategy (and ours, above) pays one per sequence.
    """

    single_instance = True  # one worker owns the arena; batching, not
    # instance replication, provides the parallelism here.

    def __init__(self, model, stats):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        sb = model.config.sequence_batching
        self._cap = max(1, sb.max_candidate_sequences)
        self._delay_ns = sb.max_queue_delay_microseconds * 1000
        init = jax.tree.map(np.asarray, model.backend.initial_state())
        self._arena = jax.tree.map(
            lambda x: jnp.zeros((self._cap + 1,) + x.shape, dtype=x.dtype),
            init)
        init_dev = jax.tree.map(jnp.asarray, init)
        vapply = jax.vmap(model.backend.make_apply())

        def step(arena, rows, reset, inputs):
            state_in = jax.tree.map(lambda a: a[rows], arena)

            def pick(s, i0):
                r = reset.reshape((-1,) + (1,) * (s.ndim - 1))
                return jnp.where(r, jnp.broadcast_to(i0, s.shape), s)

            state_in = jax.tree.map(pick, state_in, init_dev)
            new_state, outputs = vapply(state_in, inputs)
            arena = jax.tree.map(lambda a, ns: a.at[rows].set(ns),
                                 arena, new_state)
            return arena, outputs

        self._step = jax.jit(step, donate_argnums=(0,))
        self._buckets = power_buckets(self._cap)
        self._free = list(range(self._cap))
        self._rows: dict[int, int] = {}       # sequence_id -> arena row
        self._last_used: dict[int, int] = {}  # sequence_id -> ns
        # idle-GC must not evict a sequence with a request still queued
        # (`protect` only covers the wave being assembled, not
        # continuations queued behind it) — see _PendingGuard.
        self._pending: dict[int, int] = {}
        self._arena_lock = lockdep.Lock("sequence.arena")
        self._compiled_buckets: set[int] = set()
        # Pipelined waves (round 4, mirroring the generative scheduler):
        # a wave is DISPATCHED without waiting for its outputs; responses
        # go out when the async fetch completes, up to `depth` waves
        # behind. Wave k+1's inputs come from clients who already received
        # wave k's responses, so consecutive waves carry disjoint
        # sequences and the donated-arena chain keeps device-side order.
        import collections

        # Depth 2 = double buffering: one wave executing/fetching while
        # the next assembles. Deeper pipelines fragment the waves (the
        # worker dispatches whatever trickled in instead of letting the
        # queue fill during the fetch) — measured 354 steps/s at depth 4
        # with avg wave 36 vs ~1500 at depth 2 with avg wave ~100.
        self._inflight_waves: "collections.deque" = collections.deque()
        self._depth = max(1, envcfg.env_int("CLIENT_TPU_SEQ_PIPELINE"))
        super().__init__(model, stats)

    # -- slot management -----------------------------------------------------

    def _acquire_row(self, req: InferRequest,
                     protect: set[int] | None = None) -> tuple[int, bool]:
        """Returns (arena row, reset-state?) for the request's sequence.

        ``protect`` — sequence ids that have a request in the wave being
        assembled: idle-GC must not evict them even if their ``last_used``
        timestamp is stale (their step is about to run, which IS a use;
        evicting here would turn a queued request into a 400 and drop live
        arena state)."""
        sid = req.sequence_id
        if sid == 0:
            raise EngineError(
                f"model '{self.model.config.name}' uses sequence batching; "
                "requests must carry a non-zero sequence id", 400)
        with self._arena_lock:
            row = self._rows.get(sid)
            if row is None:
                if not req.sequence_start:
                    raise EngineError(
                        f"sequence {sid}: request without start flag for an "
                        "inactive sequence", 400)
                self._gc_idle_locked(protect)
                if not self._free:
                    raise EngineError(
                        f"max candidate sequences "
                        f"({self._cap}) exceeded", 429)
                row = self._free.pop()
                self._rows[sid] = row
            self._last_used[sid] = now_ns()
            return row, bool(req.sequence_start)

    def _release_row(self, sid: int) -> None:
        with self._arena_lock:
            row = self._rows.pop(sid, None)
            self._last_used.pop(sid, None)
            if row is not None:
                self._free.append(row)

    def _gc_idle_locked(self, protect: set[int] | None = None) -> None:
        sb = self.model.config.sequence_batching
        cutoff = now_ns() - sb.max_sequence_idle_microseconds * 1000
        dead = [sid for sid, ts in self._last_used.items()
                if ts < cutoff and (protect is None or sid not in protect)
                and self._pending.get(sid, 0) == 0]
        for sid in dead:
            row = self._rows.pop(sid, None)
            self._last_used.pop(sid, None)
            if row is not None:
                self._free.append(row)

    # -- scheduling ----------------------------------------------------------

    def _pending_lock(self):
        return self._arena_lock

    def submit(self, req: InferRequest) -> None:
        # Arrival refreshes liveness (see SequenceScheduler.submit): a
        # queued continuation must not lose its arena row to idle-GC while
        # waiting behind a full wave.
        if req.sequence_id:
            with self._arena_lock:
                if req.sequence_id in self._last_used:
                    self._last_used[req.sequence_id] = now_ns()
                self._pend_locked(req.sequence_id)
        try:
            super().submit(req)
        except Exception:
            self._unpend(req.sequence_id)  # rejected at enqueue
            raise

    def _worker_loop(self) -> None:
        while True:
            # Consume completed fetches first. At depth, BLOCK on the
            # oldest wave before gathering more: its responses release the
            # next round of client steps, so the queue fills while we wait
            # and the next wave stays large (dispatching eagerly here
            # fragments the waves and collapses throughput).
            self._drain_waves(force=len(self._inflight_waves) >= self._depth)
            try:
                # With waves in flight, don't park indefinitely: the queue
                # may stay empty precisely because clients are waiting for
                # responses this worker hasn't fetched yet.
                item = self.queue.get(
                    timeout=0.002 if self._inflight_waves else None)
            except _queue.Empty:
                if self._inflight_waves:
                    self._drain_waves(force=True)
                continue
            if item is _SHUTDOWN:
                self._drain_waves(flush=True)
                return
            req: InferRequest = item
            self._unpend(req.sequence_id)
            if self._check_timeout(req) or self._check_cancelled(req):
                continue
            batch = self._gather_candidates(req)
            try:
                self._dispatch_wave(batch)
            except EngineError as exc:
                for r in batch:
                    self._fail(r, exc)
            except Exception as exc:  # noqa: BLE001 — isolate worker
                for r in batch:
                    self._fail(r, exc)

    def _gather_candidates(self, first: InferRequest) -> list[InferRequest]:
        """Collect one queued request per *distinct* live-or-starting
        sequence (a second request of a sequence already in the wave goes
        back to the queue head: per-sequence order is step order)."""
        deadline = now_ns() + self._delay_ns
        batch = [first]
        seen = {first.sequence_id}
        pushback: list[InferRequest] = []
        while len(batch) < self._cap:
            timeout = max((deadline - now_ns()) / 1e9, 0.0)
            try:
                items = self.queue.get_many(self._cap - len(batch),
                                            timeout=timeout)
            except _queue.Empty:
                break
            stop = False
            for i, item in enumerate(items):
                if item is _SHUTDOWN:
                    for _ in items[i:]:
                        self.queue.put(_SHUTDOWN, _SHUTDOWN_LEVEL)
                    stop = True
                    break
                nxt: InferRequest = item
                self._unpend(nxt.sequence_id)
                if self._check_timeout(nxt) or self._check_cancelled(nxt):
                    continue
                if nxt.sequence_id in seen or not _same_signature(first, nxt):
                    pushback.append(nxt)
                    continue
                seen.add(nxt.sequence_id)
                batch.append(nxt)
            if stop:
                break
        for later in reversed(pushback):
            # Returning to the queue: the request is pending again until the
            # next gather dequeues it.
            if later.sequence_id:
                with self._arena_lock:
                    self._pend_locked(later.sequence_id)
            self.queue.put_front(later, self._priority_level(later))
        return batch

    def _dispatch_wave(self, batch: list[InferRequest]) -> None:
        """Dispatch one step wave WITHOUT waiting for its outputs: JAX
        async dispatch queues the donated-arena execution; responses go
        out in _drain_waves when the host fetch completes (up to `depth`
        waves behind, so the fetch round trip overlaps the next wave)."""
        start = now_ns()
        rows, resets, live = [], [], []
        wave_sids = {r.sequence_id for r in batch}
        for r in batch:
            r.times.compute_start = start
            try:
                row, reset = self._acquire_row(r, protect=wave_sids)
            except EngineError as exc:
                self._fail(r, exc)
                continue
            rows.append(row)
            resets.append(reset)
            live.append(r)
        if not live:
            return
        bucket = next(b for b in self._buckets if b >= len(live))
        pad = bucket - len(live)
        rows += [self._cap] * pad      # dummy row absorbs padded lanes
        resets += [True] * pad
        inputs = {}
        for name in live[0].inputs:
            arrs = [r.inputs[name] for r in live]
            arrs += [np.zeros_like(arrs[0])] * pad
            inputs[name] = np.stack(arrs)
        t_stacked = now_ns()

        first = bucket not in self._compiled_buckets
        self.model._set_state(
            f"compiling oldest-batch step (bucket={bucket}, first call)"
            if first else f"executing oldest-batch step (bucket={bucket})")
        try:
            self._arena, outputs = self._step(
                self._arena, np.asarray(rows, np.int32),
                np.asarray(resets), inputs)
            for val in outputs.values():
                if isinstance(val, self._jax.Array):
                    val.copy_to_host_async()
        except Exception:
            # Waves already dispatched executed BEFORE this failure
            # (device order): deliver their responses if their buffers
            # survived, then rebuild the arena.
            try:
                self._drain_waves(flush=True)
            # tpulint: allow[swallowed-exception] flush is best-effort here
            except Exception:  # noqa: BLE001 — flush is best-effort here
                pass
            self._reset_arena_state()
            raise
        finally:
            self.model._clear_state()
        if first:
            self._compiled_buckets.add(bucket)
        self.stats.record_execution(len(live))
        self._inflight_waves.append((live, outputs, t_stacked))

    def _drain_waves(self, force: bool = False, flush: bool = False) -> None:
        """Respond for completed waves, in dispatch order. ``force`` blocks
        on the oldest wave (progress when the queue is empty because every
        client is awaiting a response); ``flush`` drains everything."""
        while self._inflight_waves:
            live, outputs, t_stacked = self._inflight_waves[0]
            if not (force or flush):
                heads = [v for v in outputs.values()
                         if isinstance(v, self._jax.Array)]
                if heads and not all(v.is_ready() for v in heads):
                    return
            force = False
            self._inflight_waves.popleft()
            try:
                host = {name: np.asarray(val)
                        for name, val in outputs.items()}
            except Exception as exc:  # noqa: BLE001 — execution failed
                self._reset_arena_state()
                for r in live:
                    self._fail(r, EngineError(
                        f"sequence step failed: {exc}", 500))
                for later_live, _, _ in list(self._inflight_waves):
                    for r in later_live:
                        self._fail(r, EngineError(
                            f"sequence step failed: {exc}", 500))
                self._inflight_waves.clear()
                return
            t_done = now_ns()
            # Compute ns for this wave was unknown at dispatch (counted in
            # _dispatch_wave); attribute it now that the device is done.
            self.stats.add_execution_ns(len(live), t_done - t_stacked)
            # Response delivery IS liveness: with pipelined waves a
            # server-side stall (compile, slow fetch) can push delivery
            # >idle-window past the row acquire; judging idleness from the
            # acquire timestamp alone would evict clients who were never
            # idle — the server was.
            with self._arena_lock:
                for r in live:
                    if r.sequence_id in self._last_used:
                        self._last_used[r.sequence_id] = t_done
            for i, r in enumerate(live):
                if r.sequence_end:
                    self._release_row(r.sequence_id)
                outs = {k: v[i] for k, v in host.items()}
                if r.outputs:
                    requested = {o.name for o in r.outputs}
                    outs = {k: v for k, v in outs.items() if k in requested}
                r.times.compute_input_end = t_stacked
                r.times.compute_infer_end = t_done
                r.times.compute_output_end = now_ns()
                self.stats.record_request(r.times, success=True)
                self._respond(r, InferResponse(
                    model_name=r.model_name,
                    model_version=r.model_version or
                    str(self.model.config.version),
                    request_id=r.request_id,
                    outputs=outs,
                    times=r.times,
                ))

    def _reset_arena_state(self) -> None:
        """A failed donated call may have invalidated the arena buffers —
        and every wave dispatched behind it: rebuild and drop every live
        sequence rather than serving from a deleted array forever.
        Affected sequences must restart (their next request without a
        start flag gets a 400)."""
        import logging

        logging.getLogger("client_tpu").exception(
            "model '%s': oldest-batch step failed; resetting sequence "
            "arena (%d live sequences dropped)",
            self.model.config.name, len(self._rows))
        import jax.numpy as jnp

        with self._arena_lock:
            self._arena = self._jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), self._arena)
            self._rows.clear()
            self._last_used.clear()
            self._free = list(range(self._cap))

    def active_sequences(self) -> int:
        with self._arena_lock:
            return len(self._rows)


def _same_signature(a: InferRequest, b: InferRequest) -> bool:
    """Steppable in one wave: same input names, shapes, and dtypes."""
    if a.inputs.keys() != b.inputs.keys():
        return False
    for name in a.inputs:
        x, y = a.inputs[name], b.inputs[name]
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
    return True


def make_sequence_scheduler(model, stats) -> Scheduler:
    """Strategy dispatch: 'oldest' gets the arena batcher when the model is
    jittable (pure-JAX step, no BYTES state I/O); everything else — and the
    'direct' strategy — uses the slot-pinned scheduler above."""
    sb = model.config.sequence_batching
    jittable = getattr(model.backend, "jittable", True)
    has_bytes = any(t.data_type == "BYTES"
                    for t in model.config.input + model.config.output)
    if sb is not None and sb.strategy == "oldest":
        if jittable and not has_bytes:
            return OldestSequenceScheduler(model, stats)
        import logging

        logging.getLogger("client_tpu").warning(
            "model '%s': sequence strategy 'oldest' requested but the step "
            "is not arena-batchable (%s); falling back to the direct "
            "scheduler (no max_candidate_sequences cap, per-sequence "
            "executions)", model.config.name,
            "BYTES tensors" if has_bytes else "non-jittable backend")
    return SequenceScheduler(model, stats)
