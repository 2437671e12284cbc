"""Request schedulers: per-model queues, worker instances, dynamic batching.

The engine-side counterpart of Triton's rate/queue schedulers that the
reference classifies via its model parser (NONE / DYNAMIC / SEQUENCE /
ENSEMBLE, /root/reference/src/c++/perf_analyzer/model_parser.h:33-42).
TPU specifics: batches are assembled on host and padded to pre-declared
buckets so the jitted XLA executable sees only static shapes.
"""

from __future__ import annotations

import heapq
import logging
import queue
import threading
from client_tpu.utils import lockdep
import time
from typing import Callable

import numpy as np

from client_tpu import faults
from client_tpu.engine.model import Model
from client_tpu.engine.stats import ModelStats
from client_tpu.observability.costs import ledger
from client_tpu.engine.types import (
    DeadlineExpired,
    EngineError,
    InferRequest,
    InferResponse,
    now_ns,
)

_SHUTDOWN = object()
# Shutdown drains behind every queued request regardless of its priority.
_SHUTDOWN_LEVEL = 1 << 30

_log = logging.getLogger("client_tpu")


def _backpressured(req: InferRequest) -> bool:
    """True while the request's frontend reports a backlogged response
    path (InferRequest.backpressure).  Fail-open: a frontend probe that
    raises must throttle nothing — the slow-consumer shed remains the
    backstop."""
    bp = req.backpressure
    if bp is None:
        return False
    try:
        return bool(bp())
    except Exception:  # noqa: BLE001
        return False


def _wait_while_backpressured(req: InferRequest,
                              poll_s: float = 0.001,
                              max_wait_s: float = 60.0) -> None:
    """Writer-paced production for decoupled emit loops: park until the
    frontend drains (or the request is cancelled).  Bounded — after
    max_wait_s production resumes and the shed policy owns the outcome."""
    deadline = time.monotonic() + max_wait_s
    while (_backpressured(req) and not req.cancelled
           and time.monotonic() < deadline):
        time.sleep(poll_s)


def power_buckets(n: int) -> list[int]:
    """Power-of-two sizes up to and including ``n`` — the shared bucket
    ladder for wave/batch compiles (one XLA executable per bucket)."""
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    out.append(n)
    return out


class _ReqQueue:
    """Priority-ordered queue with FIFO order within a level and
    front-pushback.

    Levels follow the Triton convention (lower number = higher priority);
    FIFO-only models use a single level. Dynamic-batch gathering must be
    able to return a request that doesn't fit the current batch to the
    *head* of its level: round 1 re-queued it to the tail, which reordered
    FIFO under mixed shapes and could starve a request indefinitely with
    one worker. ``get`` blocks like ``queue.Queue.get`` and raises
    ``queue.Empty`` on timeout.
    """

    def __init__(self):
        self._h: list = []  # (level, seq, item)
        self._cv = lockdep.Condition("scheduler.queue")
        self._seq = 0        # arrival order within a level
        self._front_seq = 0  # decreasing: pushback lands ahead of arrivals
        self._level_counts: dict[int, int] = {}

    def put(self, item, level: int = 0, max_level_size: int = 0) -> bool:
        """Enqueue; with ``max_level_size`` > 0 the admission check against
        that *level's* depth happens under the queue lock (atomic — Triton's
        per-level ModelQueuePolicy.max_queue_size semantics). Returns False
        when the level is full."""
        with self._cv:
            if max_level_size > 0 and \
                    self._level_counts.get(level, 0) >= max_level_size:
                return False
            self._seq += 1
            heapq.heappush(self._h, (level, self._seq, item))
            self._level_counts[level] = self._level_counts.get(level, 0) + 1
            self._cv.notify()
            return True

    def put_front(self, item, level: int = 0) -> None:
        with self._cv:
            self._front_seq -= 1
            heapq.heappush(self._h, (level, self._front_seq, item))
            self._level_counts[level] = self._level_counts.get(level, 0) + 1
            self._cv.notify()

    def get(self, timeout: float | None = None):
        return self.get_many(1, timeout=timeout)[0]

    def get_many(self, max_items: int, timeout: float | None = None) -> list:
        """Pop up to ``max_items`` in priority/FIFO order under ONE lock
        acquisition; blocks (bounded by ``timeout``) for the first item only.
        Dynamic-batch gathering drains its backlog through this — per-item
        ``get`` costs a lock round trip each, which under a few hundred
        client threads lets the delay window expire after a handful of pops."""
        with self._cv:
            if not self._cv.wait_for(lambda: len(self._h) > 0,
                                     timeout=timeout):
                raise queue.Empty
            out = []
            while self._h and len(out) < max_items:
                level, _seq, item = heapq.heappop(self._h)
                self._level_counts[level] = \
                    self._level_counts.get(level, 1) - 1
                out.append(item)
            return out

    def qsize(self) -> int:
        with self._cv:
            return len(self._h)

    def level_qsize(self, level: int) -> int:
        with self._cv:
            return self._level_counts.get(level, 0)


class _WfqLane:
    """One QoS class's lane inside :class:`_WfqQueue`: a (level, seq)
    heap like :class:`_ReqQueue` plus the DRR deficit counter."""

    __slots__ = ("name", "weight", "preempt", "h", "deficit")

    def __init__(self, name: str, weight: float, preempt: bool):
        self.name = name
        self.weight = max(1e-6, float(weight))
        self.preempt = preempt
        self.h: list = []  # (level, seq, item)
        self.deficit = 0.0


class _WfqQueue:
    """Weighted fair queue across QoS classes: deficit round-robin over
    per-class lanes, quantum proportional to the configured weight.

    Drop-in for :class:`_ReqQueue` (same put/put_front/get/get_many/
    qsize/level_qsize surface) so every scheduler check chain, shutdown
    sentinel contract, and pushback path is untouched. Differences:

    * **Pop order** — instead of one global priority heap, each class
      owns a lane (priority/FIFO *within* the lane) and ``get_many``
      serves lanes by DRR: a lane earns ``quantum x weight`` credit per
      rotation and pops one request per credit, so under saturation the
      served mix converges to the weight ratio regardless of which
      class floods the queue.
    * **Preemption hint** — an arrival in a ``preempt`` class restarts
      the rotation at that lane (next wave leads with it) and is
      visible to in-assembly gathers via :meth:`preempt_pending`, which
      lets the dynamic batcher split a batch-lane batch instead of
      making the interactive request wait behind a full wave.
    * **Shutdown** — sentinels ride a control lane served only when
      every class lane is empty, preserving the drain-real-work-first
      contract heap order used to give.
    """

    def __init__(self, qos):
        self._qos = qos
        self._cv = lockdep.Condition("scheduler.queue")
        self._seq = 0
        self._front_seq = 0
        self._level_counts: dict[int, int] = {}
        self._lanes: dict[str, _WfqLane] = {}
        for name in qos.class_names():
            self._lanes[name] = _WfqLane(
                name, qos.weight(name), qos.is_preempt(name))
        self._default = qos.config.default_class
        self._order = list(self._lanes)
        self._rr = 0
        self._control: list = []  # shutdown sentinels / control items
        self._size = 0
        # One rotation gives the lightest lane >= 1 credit so every
        # round makes progress (classic DRR quantum >= 1 packet).
        min_w = min(lane.weight for lane in self._lanes.values())
        self._quantum = 1.0 / min_w

    def _lane_for(self, item) -> _WfqLane | None:
        if item is _SHUTDOWN or not isinstance(item, InferRequest):
            return None  # control lane
        name = getattr(item, "qos_class", "") or self._default
        lane = self._lanes.get(name)
        return lane if lane is not None else self._lanes[self._default]

    def put(self, item, level: int = 0, max_level_size: int = 0) -> bool:
        with self._cv:
            if max_level_size > 0 and \
                    self._level_counts.get(level, 0) >= max_level_size:
                return False
            lane = self._lane_for(item)
            if lane is None:
                self._control.append((level, item))
            else:
                self._seq += 1
                heapq.heappush(lane.h, (level, self._seq, item))
                if lane.preempt:
                    # Next rotation leads with the interactive lane; DRR
                    # deficits still bound its share, so this shifts
                    # latency, not throughput fairness.
                    self._rr = self._order.index(lane.name)
            self._level_counts[level] = self._level_counts.get(level, 0) + 1
            self._size += 1
            self._cv.notify()
            return True

    def put_front(self, item, level: int = 0) -> None:
        with self._cv:
            lane = self._lane_for(item)
            if lane is None:
                self._control.append((level, item))
            else:
                self._front_seq -= 1
                heapq.heappush(lane.h, (level, self._front_seq, item))
            self._level_counts[level] = self._level_counts.get(level, 0) + 1
            self._size += 1
            self._cv.notify()

    def get(self, timeout: float | None = None):
        return self.get_many(1, timeout=timeout)[0]

    def get_many(self, max_items: int, timeout: float | None = None) -> list:
        with self._cv:
            if not self._cv.wait_for(lambda: self._size > 0,
                                     timeout=timeout):
                raise queue.Empty
            out: list = []
            n = len(self._order)
            while len(out) < max_items and \
                    self._size > len(self._control):
                progressed = False
                for k in range(n):
                    i = (self._rr + k) % n
                    lane = self._lanes[self._order[i]]
                    if not lane.h:
                        lane.deficit = 0.0
                        continue
                    # Credit only at the START of a lane's turn: a turn
                    # cut short by max_items resumes on leftover deficit
                    # (crediting per visit would let one lane re-earn
                    # forever and starve the rotation).
                    if lane.deficit < 1.0:
                        lane.deficit += self._quantum * lane.weight
                    while lane.h and lane.deficit >= 1.0 \
                            and len(out) < max_items:
                        self._pop_lane(lane, out)
                        lane.deficit -= 1.0
                        progressed = True
                    if not lane.h:
                        lane.deficit = 0.0
                    if len(out) >= max_items:
                        # Mid-turn cut (credit left): the lane keeps the
                        # floor; an exhausted turn passes it on.
                        self._rr = i if lane.h and lane.deficit >= 1.0 \
                            else (i + 1) % n
                        break
                if not progressed:
                    break  # defensive: every visited lane was empty
            # Control items (shutdown sentinels) only once every class
            # lane has drained — real work first, like heap order did.
            while len(out) < max_items and self._control \
                    and self._size == len(self._control):
                level, item = self._control.pop(0)
                out.append(item)
                self._size -= 1
                self._level_counts[level] = \
                    self._level_counts.get(level, 1) - 1
            return out

    def _pop_lane(self, lane: _WfqLane, out: list) -> None:
        level, _seq, item = heapq.heappop(lane.h)
        self._level_counts[level] = self._level_counts.get(level, 1) - 1
        self._size -= 1
        out.append(item)

    def preempt_pending(self) -> str | None:
        """The name of a preempt-class lane with queued work (None when
        no interactive request is waiting)."""
        with self._cv:
            for lane in self._lanes.values():
                if lane.preempt and lane.h:
                    return lane.name
        return None

    def qsize(self) -> int:
        with self._cv:
            return self._size

    def class_qsize(self, name: str) -> int:
        lane = self._lanes.get(name)
        if lane is None:
            return 0
        with self._cv:
            return len(lane.h)

    def level_qsize(self, level: int) -> int:
        with self._cv:
            return self._level_counts.get(level, 0)


class Scheduler:
    """Base scheduler: owns the request queue and worker threads."""

    # preserve_ordering applies only to the one-response-per-request default
    # scheduler; decoupled streams and sequence slots have their own ordering
    # contracts (Triton likewise scopes it to the dynamic batcher).
    supports_preserve_ordering = False
    # Schedulers that own exclusive mutable state (the oldest-sequence
    # batcher's HBM arena) run exactly one worker regardless of
    # instance_count — their parallelism comes from batching.
    single_instance = False

    def __init__(self, model: Model, stats: ModelStats, qos=None):
        self.model = model
        self.stats = stats
        # With a QoS controller attached (CLIENT_TPU_QOS), batching
        # schedulers swap the priority heap for the weighted fair queue;
        # everything else keeps pure priority order.
        self.qos = qos if qos is not None and \
            getattr(qos, "enabled", False) else None
        self.queue = _WfqQueue(self.qos) if self.qos is not None \
            else _ReqQueue()
        self.workers: list[threading.Thread] = []
        self._stopping = False
        # Approximate in-flight batch count for the tpu_inflight_batches
        # gauge; worker threads inc/dec around device execution (races lose
        # at most a transient +-1 — acceptable for a sampled gauge).
        self.active_batches = 0
        # preserve_ordering (Triton ModelDynamicBatching): responses release
        # in arrival order even when instances complete out of order.
        dyn = model.config.dynamic_batching
        self._preserve_ordering = bool(
            dyn and dyn.preserve_ordering and self.supports_preserve_ordering
            and not model.config.decoupled)
        if self._preserve_ordering and dyn.priority_levels > 0:
            # Arrival-order release and priority overtaking contradict each
            # other (a held high-priority response would wait on every older
            # low-priority request — unbounded holds). Triton rejects the
            # combination too.
            raise EngineError(
                f"model '{model.config.name}': preserve_ordering cannot be "
                "combined with priority_levels", 400)
        # Runtime dispatch override (the self-drive tuner's actuator):
        # a single immutable dict swapped atomically, read once per
        # gather. None means "use the model config as written".
        self._dispatch_override: dict | None = None
        self._order_lock = lockdep.Lock("scheduler.order")
        self._arrival_seq = 0        # assigned at submit
        self._release_seq = 0        # next sequence allowed to respond
        self._held: dict[int, tuple] = {}  # seq -> (req, resp)
        self._draining = False       # one thread flushes ready runs at a time
        n = 1 if self.single_instance else max(1, model.config.instance_count)
        for i in range(n):
            t = threading.Thread(
                target=self._worker_loop,
                name=f"sched-{model.config.name}-{i}",
                daemon=True,
            )
            t.start()
            self.workers.append(t)

    def _priority_level(self, req: InferRequest) -> int:
        """Triton semantics: priority <= 0 means the model's default level;
        priorities beyond priority_levels clamp to the lowest level."""
        dyn = self.model.config.dynamic_batching
        if dyn is None or dyn.priority_levels <= 0:
            return 0
        level = int(req.priority)
        if level <= 0:
            level = int(dyn.default_priority_level) or \
                (dyn.priority_levels + 1) // 2
        return max(1, min(level, dyn.priority_levels))

    # -- bucket ladder (autotuner surface) ------------------------------------

    def bucket_ladder(self) -> list[int]:
        """The model's current bucket ladder along its padding axis
        (rows, or lookups for ragged models; [] for unbatched)."""
        if self.model.config.axis_capacity() <= 0:
            return []
        return self.model.config.effective_buckets()

    def swap_ladder(self, buckets: list[int]) -> list[int]:
        """Atomically replace the bucket ladder (the autotuner's
        promotion/retire path). Safe concurrent with enqueue/dequeue:
        queueing is bucket-independent and padding happens only inside
        ``execute_timed``, so queued requests simply land on the new
        ladder while in-flight batches finish on the bucket they already
        picked (its executable stays in the jit cache). Returns the
        ladder actually applied (validated/clamped)."""
        return self.model.swap_buckets(buckets)

    # -- dispatch overrides (self-drive tuner surface) ------------------------

    def set_dispatch_override(self, *, max_queue_delay_us: int | None = None,
                              max_batch: int | None = None) -> None:
        """Override the gather window and/or batch cap at runtime without
        touching the model config. Overrides only ever *tighten* (the
        effective values are min()'d against the config), so a stale or
        wild override cannot relax the operator's limits. Passing both
        as None clears the override. The dict is swapped in one atomic
        attribute store; workers read it once per gather."""
        if max_queue_delay_us is None and max_batch is None:
            self._dispatch_override = None
            return
        ovr: dict = {}
        if max_queue_delay_us is not None:
            ovr["max_queue_delay_us"] = max(0, int(max_queue_delay_us))
        if max_batch is not None:
            ovr["max_batch"] = max(1, int(max_batch))
        self._dispatch_override = ovr

    def dispatch_overrides(self) -> dict:
        """The active override (empty dict when running as configured)."""
        ovr = self._dispatch_override
        return dict(ovr) if ovr else {}

    def submit(self, req: InferRequest) -> None:
        # Chaos site: scheduler admission — an injected error here proves
        # the frontend error paths and client retry classification against
        # queue-level failures without needing a real overload.
        try:
            faults.fire("scheduler.enqueue")
        except faults.FaultInjected as exc:
            raise EngineError(str(exc), exc.status or 503) from None
        level = self._priority_level(req)
        dyn = self.model.config.dynamic_batching
        policy = dyn.policy_for(level) if dyn is not None else None
        max_size = policy.max_queue_size if policy is not None else 0
        req.times.queue_start = now_ns()
        if self._preserve_ordering:
            with self._order_lock:
                req.arrival_seq = self._arrival_seq
                self._arrival_seq += 1
        queued = self.queue.put(req, level, max_level_size=max_size)
        if queued:
            # Cost ledger: record the arrival into the model's tenant mix
            # (feeds the queue_wait interference split at dequeue).
            ledger().note_queued(self.model.config.name, req.tenant)
        else:
            self.stats.record_rejection()
            if self._preserve_ordering:
                # The rejected request's arrival slot must not dam the
                # release sequence: mark it done with a hole sentinel.
                self._release_in_order(req.arrival_seq, (None, None))
            depth = self.queue.level_qsize(level)
            raise EngineError(
                f"model '{self.model.config.name}' rejected request at "
                f"priority level {level}: current queue depth {depth} "
                f"exceeds maximum queue size ({max_size}) for that level",
                429)
        if self._stopping and not any(t.is_alive() for t in self.workers):
            # Submit raced stop() and the workers are already gone: nothing
            # will ever pop this request. Fail whatever is queued
            # (idempotent with stop()'s own drain). While workers live,
            # heap order guarantees they pop real requests ahead of the
            # shutdown sentinels, so the graceful-drain path is untouched.
            self._fail_queued("model unloaded before the request was "
                              "processed", 503)

    def stop(self, timeout_s: float = 5.0) -> None:
        """Drain and stop the workers. ``timeout_s`` bounds the TOTAL wait
        across all workers (the drain coordinator budgets one overall
        deadline, not 5s-per-thread); workers still mid-request past it are
        abandoned and their queued work failed below."""
        self._stopping = True
        deadline = time.monotonic() + max(0.0, timeout_s)
        for _ in self.workers:
            self.queue.put(_SHUTDOWN, _SHUTDOWN_LEVEL)
        for t in self.workers:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        # Workers drain real requests ahead of the shutdown sentinels (heap
        # order), but anything enqueued after the workers exited — or left
        # behind by a worker that timed out — must still get a response.
        self._fail_queued("model unloaded before the request was processed",
                          503)

    def _fail_queued(self, why: str, status: int) -> None:
        # Sentinels popped during the drain are re-put afterwards: a worker
        # that outlived stop()'s join timeout (mid-compile) still needs its
        # exit signal when it next reads the queue. Heap order pops real
        # requests first, so the drain terminates: once only sentinels
        # remain, the queue empties in one slab.
        sentinels = 0
        while True:
            try:
                items = self.queue.get_many(64, timeout=0)
            except queue.Empty:
                break
            for item in items:
                if item is _SHUTDOWN:
                    sentinels += 1
                elif isinstance(item, InferRequest):
                    self._fail(item, EngineError(why, status))
                else:
                    # Scheduler-internal control items (e.g. a warmup
                    # request) carry a `done` event a caller is waiting on;
                    # record the abort so the caller doesn't read the
                    # unprocessed item as success.
                    if hasattr(item, "error"):
                        item.error = EngineError(why, status)
                    done = getattr(item, "done", None)
                    if done is not None:
                        done.set()
        for _ in range(sentinels):
            self.queue.put(_SHUTDOWN, _SHUTDOWN_LEVEL)

    # -- subclass API --------------------------------------------------------

    def warmup(self) -> None:
        """Scheduler-owned precompilation (beyond the model's bucket
        warmup); no-op by default. The generative scheduler compiles its
        prefill/decode executables here."""

    def _worker_loop(self) -> None:
        raise NotImplementedError

    def _release_in_order(self, seq: int, entry: tuple) -> None:
        """Park (req, resp) under its arrival slot; deliver the contiguous
        run of now-unblocked responses.

        Single-drainer: exactly one thread flushes at a time, popping one
        slot per lock acquisition and invoking the callback outside the
        lock — so deliveries are globally ordered (two workers completing
        back-to-back runs cannot race each other's callbacks), a
        synchronous re-submit from a callback cannot deadlock, and one
        raising callback cannot drop the rest of the run."""
        with self._order_lock:
            self._held[seq] = entry
            if self._draining:
                return  # the active drainer will pick this up
            self._draining = True
        while True:
            with self._order_lock:
                if self._release_seq not in self._held:
                    self._draining = False
                    return
                r, rp = self._held.pop(self._release_seq)
                self._release_seq += 1
            if r is not None and r.response_callback is not None:
                try:
                    r.response_callback(rp)
                except Exception:  # noqa: BLE001 — isolate client callbacks
                    _log.exception(
                        "response callback raised (model '%s')",
                        self.model.config.name)

    def _respond(self, req: InferRequest, resp: InferResponse) -> None:
        if self._preserve_ordering and req.arrival_seq is not None:
            self._release_in_order(req.arrival_seq, (req, resp))
            return
        if req.response_callback is not None:
            try:
                req.response_callback(resp)
            except Exception:  # noqa: BLE001 — one client's broken callback
                # must not fail the batch it shares (or, for single-worker
                # schedulers, kill the worker thread).
                _log.exception(
                    "response callback raised (model '%s')",
                    self.model.config.name)

    @staticmethod
    def _trace_id(req: InferRequest):
        return req.trace.trace_id if req.trace is not None else None

    def _fail(self, req: InferRequest, exc: Exception) -> None:
        req.times.compute_output_end = now_ns()
        self.stats.record_request(req.times, success=False,
                                  trace_id=self._trace_id(req))
        self._respond(req, InferResponse.make_error(req, exc))

    def _check_cancelled(self, req: InferRequest) -> bool:
        """Client-abandoned request: fail with 499 before spending device
        time on it (frontends set `cancelled` on disconnect)."""
        if req.cancelled:
            self._fail(req, EngineError("request cancelled", 499))
            return True
        return False

    def _check_deadline(self, req: InferRequest, stage: str = "queue") -> bool:
        """End-to-end deadline propagation: the client's budget
        (``timeout-ms`` header / gRPC deadline) landed on
        ``req.deadline_ns``; past it the caller has given up, so fail
        fast with 504/DEADLINE_EXCEEDED instead of spending device time
        on a dead request. ``stage`` labels where the expiry was caught
        on tpu_deadline_expirations_total (queue | execute)."""
        if req.deadline_expired():
            waited_ms = (now_ns() - req.times.queue_start) / 1e6
            self.stats.record_deadline_expired(
                stage, trace_id=self._trace_id(req))
            self._fail(req, DeadlineExpired(
                f"end-to-end deadline expired before {stage} "
                f"(waited {waited_ms:.1f}ms in queue)"))
            return True
        return False

    def _check_dequeue_fault(self, req: InferRequest) -> bool:
        """Chaos site: scheduler dequeue — a popped request that fails
        before any batching/execution. Proves the expiry-at-dequeue and
        shed error paths (frontend translation, client classification)
        with seeded determinism."""
        try:
            faults.fire("scheduler.dequeue")
        except faults.FaultInjected as exc:
            self._fail(req, EngineError(str(exc), exc.status or 503))
            return True
        return False

    def _check_timeout(self, req: InferRequest) -> bool:
        """Server-side request timeout while queued (InferOptions
        server_timeout, reference common.h:199-204, composed with the
        model's queue policy — the `schedule_policy` extension)."""
        dyn = self.model.config.dynamic_batching
        policy = (dyn.policy_for(self._priority_level(req))
                  if dyn is not None else None)
        timeout_us = req.timeout_us
        if policy is not None:
            if timeout_us <= 0 or not policy.allow_timeout_override:
                timeout_us = policy.default_timeout_microseconds
        if timeout_us > 0:
            waited_us = (now_ns() - req.times.queue_start) // 1000
            if waited_us > timeout_us:
                if policy is not None and policy.timeout_action == "DELAY":
                    return False  # execute anyway (Triton DELAY action)
                # A timed-out REJECT is an admission failure like a full
                # queue: count it on the same rejection counter so the
                # tpu_queue_rejections_total series covers both causes.
                self.stats.record_rejection()
                self._fail(req, EngineError("request timed out in queue", 504))
                return True
        return False


class DefaultScheduler(Scheduler):
    """NONE + DYNAMIC scheduling.

    With ``dynamic_batching`` configured, each worker gathers requests up to
    ``max_batch_size`` (or a preferred size) within the queue-delay window,
    concatenates along the batch axis, pads to the shape bucket, and runs one
    XLA execution for the whole batch.
    """

    supports_preserve_ordering = True

    def _worker_loop(self) -> None:
        cfg = self.model.config
        dyn = cfg.dynamic_batching
        while True:
            item = self.queue.get()
            if item is _SHUTDOWN:
                return
            req: InferRequest = item
            if self._check_timeout(req) or self._check_cancelled(req) \
                    or self._check_deadline(req) \
                    or self._check_dequeue_fault(req):
                continue
            batch = [req]
            if dyn is not None and cfg.max_batch_size > 0:
                batch = self._gather(req, dyn)
            # Deadline backstop at dispatch: gathering may have consumed the
            # delay window, and a request popped with time left can expire
            # while the batch assembles. Expired members fail here (stage
            # "execute"); the survivors still run.
            batch = [r for r in batch
                     if not self._check_deadline(r, stage="execute")]
            if not batch:
                continue
            try:
                self._execute_batch(batch)
            except DeadlineExpired as exc:
                # model.execute_timed's pre-dispatch check fired: the whole
                # batch's budget lapsed between the filter above and device
                # dispatch (the race window the model-level check closes).
                for r in batch:
                    self.stats.record_deadline_expired(
                        "execute", trace_id=self._trace_id(r))
                    self._fail(r, exc)
            except Exception as exc:  # noqa: BLE001 — isolate worker
                for r in batch:
                    self._fail(r, exc)

    def _gather(self, first: InferRequest, dyn) -> list[InferRequest]:
        cfg = self.model.config
        max_batch = cfg.max_batch_size
        prefer = max(dyn.preferred_batch_size) if dyn.preferred_batch_size else max_batch
        delay_us = dyn.max_queue_delay_microseconds
        ovr = self._dispatch_override
        if ovr is not None:
            # Overrides tighten, never relax: min() against config keeps a
            # stale tuner decision inside the operator's envelope.
            if "max_batch" in ovr:
                max_batch = min(max_batch, ovr["max_batch"])
                prefer = min(prefer, max_batch)
            if "max_queue_delay_us" in ovr:
                delay_us = min(delay_us, ovr["max_queue_delay_us"])
        deadline_ns = now_ns() + delay_us * 1000
        batch = [first]
        total = _request_batch(first)
        # Preemption: a batch-lane gather yields to a waiting
        # interactive (preempt-class) request by splitting here instead
        # of filling the wave — the partial batch executes now and the
        # interactive request leads the next pop.
        preemptable = (
            self.qos is not None and isinstance(self.queue, _WfqQueue)
            and not self.qos.is_preempt(getattr(first, "qos_class", "")))
        while total < prefer:
            if preemptable:
                pend = self.queue.preempt_pending()
                if pend is not None:
                    self.qos.note_preemption(cfg.name, pend)
                    break
            # Within the delay window this blocks for arrivals; past it
            # (timeout 0) it only drains what is already queued — the delay
            # bounds *waiting*, not backlog draining (Triton max_queue_delay
            # semantics). One lock acquisition per slab, not per request.
            timeout = max((deadline_ns - now_ns()) / 1e9, 0.0)
            try:
                items = self.queue.get_many(prefer - total, timeout=timeout)
            except queue.Empty:
                break
            stop = False
            for idx, item in enumerate(items):
                if item is _SHUTDOWN:
                    # Heap order sorts the shutdown level behind every real
                    # request, so the slab's tail is all sentinels: re-post
                    # each one for the sibling workers.
                    for _ in items[idx:]:
                        self.queue.put(_SHUTDOWN, _SHUTDOWN_LEVEL)
                    stop = True
                    break
                nxt: InferRequest = item
                if self._check_timeout(nxt) or self._check_cancelled(nxt) \
                        or self._check_deadline(nxt) \
                        or self._check_dequeue_fault(nxt):
                    continue
                if total >= prefer \
                        or total + _request_batch(nxt) > max_batch \
                        or not _compatible(first, nxt):
                    # Batch is full (multi-element requests can reach the
                    # preferred size mid-slab) or this request doesn't fit:
                    # push it and everything behind it back to the *head* of
                    # their levels (reverse order keeps FIFO) so the next
                    # gather starts with them. A pushed-back request whose
                    # deadline already lapsed fails here as a stage=queue
                    # expiry — requeueing a dead request would only spend
                    # another pop on it next wave.
                    for later in reversed(items[idx:]):
                        if later is _SHUTDOWN:
                            self.queue.put(_SHUTDOWN, _SHUTDOWN_LEVEL)
                        elif not self._check_deadline(later):
                            self.queue.put_front(
                                later, self._priority_level(later))
                    stop = True
                    break
                batch.append(nxt)
                total += _request_batch(nxt)
            if stop:
                break
        return batch

    def _execute_batch(self, batch: list[InferRequest]) -> None:
        self.active_batches += 1
        try:
            self._execute_batch_inner(batch)
        finally:
            self.active_batches -= 1

    def _execute_batch_inner(self, batch: list[InferRequest]) -> None:
        cfg = self.model.config
        start = now_ns()
        for r in batch:
            r.times.compute_start = start
        # Whole-batch deadline for the model's pre-dispatch check: 0 (none)
        # if ANY member is deadline-free — the batch must run for that
        # member's sake — else the latest member deadline (failing the batch
        # any earlier would expire requests that still had budget).
        deadline_ns = 0 if any(r.deadline_ns == 0 for r in batch) \
            else max(r.deadline_ns for r in batch)

        if cfg.max_batch_size > 0:
            sizes = [_request_batch(r) for r in batch]
            total = sum(sizes)
            merged = {
                name: _concat_batch([r.inputs[name] for r in batch],
                                    self.model)
                for name in batch[0].inputs
            }
            # When every request in the batch directs every output into a
            # device-resident region, leave outputs in HBM. Per-request
            # windows are ZERO-DISPATCH views (engine/shm.py
            # DeviceTensorView): slicing a jax.Array here would dispatch a
            # tiny XLA execution per request per output — 2B extra device
            # round trips for a B-request batch, the round-3 small-payload
            # pathology.
            fetch = not all(r.keep_outputs_on_device for r in batch)
            outputs, phases = self.model.execute_timed(
                merged, batch_size=total, fetch_outputs=fetch,
                deadline_ns=deadline_ns)
            self.stats.record_execution(
                total, compute_ns=phases.infer_end - phases.input_end)
            if fetch:
                offset = 0
                for r, sz in zip(batch, sizes):
                    per = {k: v[offset:offset + sz]
                           for k, v in outputs.items()}
                    offset += sz
                    self._finish(r, per, phases)
            else:
                from client_tpu.engine.shm import DeviceTensorView

                offset = 0
                for r, sz in zip(batch, sizes):
                    per = {k: DeviceTensorView(v, offset, offset + sz)
                           for k, v in outputs.items()}
                    offset += sz
                    self._finish(r, per, phases)
            # Cost ledger: split the measured device time across members
            # by real rows; the padded remainder is charged to the
            # batch's dominant tenant. Same device_ns (and the same
            # cold-call exclusion) as the profiler accumulates, so
            # per-tenant sums stay conserved against its totals. Charged
            # after the response scatter so the host leg — batch wall
            # net of the device interval: input assembly, dispatch
            # overhead, response scatter — is complete.
            if not getattr(phases, "compile_ns", 0):
                bucket = self.model.pick_bucket(total)
                device_ns = max(0, phases.infer_end - phases.input_end)
                ledger().charge_batch(
                    cfg.name, str(cfg.version),
                    [(r.tenant, sz, self._trace_id(r))
                     for r, sz in zip(batch, sizes)],
                    device_ns / 1e9,
                    padded=max(0, bucket - total),
                    host_s=max(0, now_ns() - start - device_ns) / 1e9)
        else:
            outputs, phases = self.model.execute_timed(
                batch[0].inputs, batch_size=None, deadline_ns=deadline_ns)
            self.stats.record_execution(
                1, compute_ns=phases.infer_end - phases.input_end)
            self._finish(batch[0], outputs, phases)
            if not getattr(phases, "compile_ns", 0):
                device_ns = max(0, phases.infer_end - phases.input_end)
                ledger().charge_batch(
                    cfg.name, str(cfg.version),
                    [(batch[0].tenant, 1, self._trace_id(batch[0]))],
                    device_ns / 1e9,
                    host_s=max(0, now_ns() - start - device_ns) / 1e9)

    def _finish(self, req: InferRequest, outputs: dict, phases) -> None:
        # Measured phase boundaries from Model.execute_timed: host batch
        # assembly counts toward compute_input (compute_start predates
        # phases.start by the concatenate), the executable interval is
        # device-synced, and per-request response slicing lands in
        # compute_output after the shared fetch.
        req.times.compute_input_end = phases.input_end
        req.times.compute_infer_end = phases.infer_end
        req.times.compute_output_end = now_ns()
        # Cold-start attribution: every member of a batch that paid the
        # XLA compile carries it (the whole batch waited on the trace).
        req.times.compile_ns = getattr(phases, "compile_ns", 0)
        if req.outputs:
            requested = {o.name for o in req.outputs}
            outputs = {k: v for k, v in outputs.items() if k in requested}
        ledger().charge_queue(
            self.model.config.name, str(self.model.config.version),
            req.tenant, req.times.queue_ns / 1e9,
            trace_id=self._trace_id(req))
        self.stats.record_request(req.times, success=True,
                                  trace_id=self._trace_id(req),
                                  tenant=req.tenant)
        self._respond(
            req,
            InferResponse(
                model_name=req.model_name,
                model_version=req.model_version or str(self.model.config.version),
                request_id=req.request_id,
                outputs=outputs,
                times=req.times,
            ),
        )


class DecoupledScheduler(Scheduler):
    """Decoupled (streaming) models: one request → N responses.

    Each worker drives the backend's ``generate`` iterator and emits one
    response per yield; the last carries ``final=True`` (surfaced to clients
    as the ``triton_final_response`` parameter, matching how decoupled
    responses terminate in the reference's streaming examples).
    """

    # Writer-paced emit bound: how long one emit may stay parked on
    # transport backpressure before production resumes anyway and the
    # slow-consumer shed owns the outcome.  Deliberately much shorter
    # than GenerativeScheduler's: that scheduler skips throttled streams
    # NON-blockingly, while this park holds one of the model's few worker
    # threads — other requests on the instance wait behind it (head-of-
    # line).  5 s paces any healthy consumer pause; past it, the flood
    # resumes and a stalled consumer is shed by the choke within its
    # grace window, freeing the worker.
    BACKPRESSURE_TIMEOUT_S = 5.0

    def _worker_loop(self) -> None:
        while True:
            item = self.queue.get()
            if item is _SHUTDOWN:
                return
            req: InferRequest = item
            if self._check_timeout(req) or self._check_cancelled(req) \
                    or self._check_deadline(req) \
                    or self._check_dequeue_fault(req):
                continue
            req.times.compute_start = now_ns()
            self.active_batches += 1
            try:
                self._stream(req)
            except Exception as exc:  # noqa: BLE001
                self._fail(req, exc)
            finally:
                self.active_batches -= 1

    def _stream(self, req: InferRequest) -> None:
        # Each yielded response is emitted immediately (no lookahead
        # buffering); the stream terminates with an empty final-flag-only
        # response, the same convention Triton's decoupled backends use.
        gen = self.model.backend.generate(req.inputs, req.parameters)
        count = 0
        for outputs in gen:
            # Writer-paced emit: a backlogged frontend pauses production
            # here instead of flooding its queue into the shed policy.
            _wait_while_backpressured(
                req, max_wait_s=self.BACKPRESSURE_TIMEOUT_S)
            if req.cancelled:
                # Client abandoned (disconnect) or server-side shedding
                # (slow-consumer policy): stop producing mid-stream.
                gen.close()
                raise EngineError("request cancelled", 499)
            self._emit(req, outputs, final=False)
            count += 1
        req.times.compute_input_end = req.times.compute_start
        req.times.compute_infer_end = now_ns()
        req.times.compute_output_end = req.times.compute_infer_end
        self.stats.record_execution(max(1, count),
                                    compute_ns=req.times.compute_infer_ns)
        # Decoupled repeat backends run on host (no device executable),
        # so only queue wait is charged — inventing device-seconds here
        # would break conservation against the profiler.
        ledger().charge_queue(
            self.model.config.name, str(self.model.config.version),
            req.tenant, req.times.queue_ns / 1e9,
            trace_id=self._trace_id(req))
        self.stats.record_request(req.times, success=True,
                                  trace_id=self._trace_id(req),
                                  tenant=req.tenant)
        self._emit(req, {}, final=True)

    def _emit(self, req: InferRequest, outputs: dict, final: bool) -> None:
        self._respond(
            req,
            InferResponse(
                model_name=req.model_name,
                model_version=req.model_version or str(self.model.config.version),
                request_id=req.request_id,
                outputs=dict(outputs),
                parameters={"triton_final_response": final},
                final=final,
                times=req.times,
            ),
        )


def _concat_batch(arrs: list, model) -> np.ndarray:
    """Concatenate request tensors along the batch axis.

    Device-resident inputs (tpu-shm ``device`` regions are ``jax.Array``)
    concatenate ON DEVICE: ``np.concatenate`` would call ``__array__`` on
    each, paying one D2H round trip per request for data that was already
    in HBM. When the padding divides evenly, operands are repeated (the
    per-request slice discards the extra rows) up to the model's own
    batch bucket, so XLA compiles one concat per bucket — never a row
    count outside the
    configured ladder.
    """
    if len(arrs) == 1:
        return arrs[0]
    import jax

    if all(isinstance(a, jax.Array) for a in arrs) and \
            len({(a.shape, str(a.dtype)) for a in arrs}) == 1:
        import jax.numpy as jnp

        per = int(arrs[0].shape[0]) if arrs[0].ndim else 1
        total = per * len(arrs)
        if model.config.max_batch_size > 0 and per > 0:
            extra = model.pick_bucket(total) - total
            if extra > 0 and extra % per == 0:
                arrs = list(arrs) + [arrs[0]] * (extra // per)
        return jnp.concatenate(arrs, axis=0)
    return np.concatenate([np.asarray(a) for a in arrs], axis=0)


def _request_batch(req: InferRequest) -> int:
    for arr in req.inputs.values():
        return int(arr.shape[0])
    return 1


def _compatible(a: InferRequest, b: InferRequest) -> bool:
    """Batchable together: same inputs, same non-batch dims, same dtypes."""
    if a.inputs.keys() != b.inputs.keys():
        return False
    for name in a.inputs:
        x, y = a.inputs[name], b.inputs[name]
        if x.shape[1:] != y.shape[1:] or x.dtype != y.dtype:
            return False
    return True


def make_scheduler(model: Model, stats: ModelStats,
                   sequence_cls: Callable | None = None,
                   ensemble_cls: Callable | None = None,
                   qos=None, **kw) -> Scheduler:
    kind = model.config.scheduler_kind()
    if kind in ("ENSEMBLE", "ENSEMBLE_SEQUENCE"):
        if ensemble_cls is None:
            raise EngineError("ensemble scheduling not wired", 500)
        return ensemble_cls(model, stats, **kw)
    if kind == "SEQUENCE":
        if sequence_cls is None:
            raise EngineError("sequence scheduling not wired", 500)
        return sequence_cls(model, stats)
    if model.config.decoupled:
        if getattr(model.backend, "generative", False):
            # Autoregressive backends (prefill/decode over a KV arena) get
            # iteration-level batching across streams.
            from client_tpu.engine.generative import GenerativeScheduler

            return GenerativeScheduler(model, stats)
        return DecoupledScheduler(model, stats)
    if model.config.padding_axis == "lookups":
        # Ragged DLRM batching: gather by summed lookup count, not rows.
        from client_tpu.engine.ragged import RaggedScheduler

        return RaggedScheduler(model, stats, qos=qos)
    return DefaultScheduler(model, stats, qos=qos)
