"""Continuous efficiency profiler: where does the device time go?

PR-1 tracing answers "where did THIS request spend its time" and PR-4
events/SLO answer "is the server healthy"; this module answers the cost
question the ROADMAP north-star ("as fast as the hardware allows") is
ultimately judged by: which model/bucket pairs burn device seconds, how
much of every padded batch is real work, and how often XLA recompiles.

Three always-on signals, recorded from ``Model.execute_timed`` at a cost
of a few dict operations per *batch* (not per request):

- **Batch-fill cost attribution** — per (model, version, bucket): call
  counts, real vs padded rows, device/host time totals + per-call EWMA.
  Rendered as the ``tpu_batch_fill_ratio`` histogram and the
  ``tpu_padded_rows_total`` counter; the padding-waste estimate in
  :meth:`EfficiencyProfiler.snapshot` is ``device_s * padded/(real+padded)``
  — the device seconds spent multiplying zeros.
- **Compile telemetry** — one ``jax.monitoring`` listener per process
  (:func:`install_compile_listener`) hears every backend compilation of
  every ``jax.jit`` (the batcher's apply, the generative scheduler's
  prefill/decode, a backend's own helpers; a persistent-cache hit included)
  and is the one feeder of ``tpu_xla_compilations_total{model,version,
  bucket}``, ``tpu_xla_compile_seconds`` and the snapshot's ``compiles``
  object; the labels come from the thread-local scope ``Model._set_state``
  brackets each jit call site with (:func:`set_compile_scope`).
  :meth:`EfficiencyProfiler.record_compile` (``Model.execute_timed``'s
  first-call heuristic) keeps the per-bucket ``compile_s`` of the cost
  table and the ``compile.finished`` journal event. Cold executions are
  excluded from device-time accumulation so one 30 s compile doesn't
  masquerade as load.
- **The generative scheduler's clock** — loop-phase spans and lane counters
  of every ``GenerativeScheduler`` worker (vocabulary and recorder in
  :mod:`client_tpu.observability.spans`), committed per loop iteration and
  served as each model's ``generative`` object.
- **The set-up timeline** — the snapshot's ``startup`` list: the launcher's
  phases (``startup.*``: the process's own start, backend init, imports,
  model load, warm-up and what of it is no compilation, frontends) and the
  three phases of every compilation (``compile.trace``, ``compile.lower``,
  ``compile.backend``, from the same ``jax.monitoring`` listener), all on
  ``time.monotonic_ns()``, which every process of the machine shares.
- **Device duty-cycle** — a sliding window (default 60 s,
  ``CLIENT_TPU_PROFILE_WINDOW_S``) of executable-busy intervals, sampled
  at scrape time into the ``tpu_device_duty_cycle`` gauge (busy device
  time / wall time; can exceed 1.0 when model instances execute
  concurrently on multiple devices) plus the per-model
  ``tpu_device_seconds_total`` counter.

Like the fault registry and the event journal, the profiler is
process-global (:func:`profiler`) because models execute below the engine
and must not hold engine references; each engine binds its own
``MetricRegistry`` via :meth:`EfficiencyProfiler.bind_metrics` (per-registry
weakrefs — dead engines are pruned, rebinding replaces). The JSON cost
table behind ``GET /v2/profile`` / the ``Profile`` RPC comes from
:meth:`EfficiencyProfiler.snapshot`; ``tools/profile_report.py``
pretty-prints it.
"""

from __future__ import annotations

import os
from client_tpu import config as envcfg
from client_tpu.observability import roofline as _roofline
from client_tpu.observability import spans as _spans
from client_tpu.utils import lockdep
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field

# Fill ratio lives in (0, 1]; power-of-two ladders can't go below 0.5 but
# custom ladders (and max_batch_size overflow buckets) can.
FILL_RATIO_BUCKETS = (0.25, 0.5, 0.625, 0.75, 0.875, 1.0)
# First compiles run 20-40 s on TPU, sub-second on CPU tests.
COMPILE_SECONDS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                           20.0, 40.0, 80.0, 160.0)
# Decode wave steps: ~1-3 ms on TPU, tens of ms on the CPU test backend.
WAVE_SECONDS_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                        0.05, 0.1, 0.25, 1.0)

# The set-up timeline's bound.  A launch of a benchmark cell is 46-92 spans
# (13-18 programs x three compile spans and the first runs between them,
# half a dozen ``startup.*``); the rest is room for reloads and for compiles
# under traffic.  What it refuses is counted (``startup_clock.dropped``).
_STARTUP_SPANS_MAX = 1024
# A stretch of a warm-up between two compile spans shorter than this is the
# interpreter between two phases of one compilation, not a program's first
# run: it gets no span of its own.
_FIRST_RUN_MIN_NS = 1_000_000

# EWMA smoothing for per-call device/host time (~last 10 calls dominate).
_EWMA_ALPHA = 0.2

# A bucket ladder tweak is only suggested once a bucket has enough calls
# to make its fill ratio meaningful, and only when it wastes real time.
_SUGGEST_MIN_CALLS = 8
_SUGGEST_MAX_FILL = 0.85
# A bucket is "cold" (retire candidate) when its call rate over the
# profile window drops below this floor — warmup gives every ladder
# bucket one execution, so a bucket nobody uses decays to ~0 calls/min
# once the window slides past it. The autotuner applies its own floor on
# top (AutotuneConfig.retire_rate_per_min); this default keeps
# /v2/profile's suggestions aligned with what the tuner can do.
_SUGGEST_RETIRE_RATE_PER_MIN = 0.5


@dataclass
class _BucketCost:
    """Accumulated cost of one (model, version, bucket) execution shape."""

    calls: int = 0
    cold_calls: int = 0
    rows: int = 0            # real rows executed
    padded_rows: int = 0     # zero rows added to reach the bucket
    device_ns: int = 0       # executable time, warm calls only
    host_ns: int = 0         # staging + fetch host time, warm calls only
    device_ns_ewma: float = 0.0
    host_ns_ewma: float = 0.0
    compile_count: int = 0
    compile_ns: int = 0
    max_rows: int = 0
    # Which quantity the bucket pads: "rows" (default) or "lookups"
    # (ragged DLRM — ``rows`` above then counts summed lookups, and the
    # fill/suggestion math is identical; only renderers need the tag so a
    # 512-lookup bucket isn't misread as a 512-row batch).
    axis: str = "rows"
    # Recency tracking for retire suggestions: a two-window rotation gives
    # an O(1)-per-call sliding call rate (a timestamp deque would cost
    # memory proportional to call rate — thousands/s under load). The
    # current window accumulates calls since ``win_start``; when it
    # exceeds the profiler window it rotates into ``prev_*``. The rate at
    # snapshot time is (prev + current calls) / (prev + current span) —
    # a bucket that goes quiet decays toward zero as the span grows.
    first_seen: int = 0      # mono ns of first record (0 = never)
    win_start: int = 0       # current rate-window start, mono ns
    win_calls: int = 0
    prev_win_s: float = 0.0  # span of the rotated-out window, seconds
    prev_win_calls: int = 0
    # Static XLA cost model captured at compile time (record_cost_model):
    # {"available": True, "flops", "bytes_accessed", ...} or the
    # annotated absence. None until the first capture attempt.
    cost_model: dict | None = None

    def fill_ratio(self) -> float:
        total = self.rows + self.padded_rows
        return (self.rows / total) if total else 1.0

    def padding_waste_device_s(self) -> float:
        """Device seconds spent on padding rows: the executable runs the
        full bucket, so the padded fraction of its time is pure waste."""
        total = self.rows + self.padded_rows
        if not total or not self.padded_rows:
            return 0.0
        return (self.device_ns / 1e9) * (self.padded_rows / total)

    def touch(self, now: int, window_ns: int) -> None:
        """Count one call into the sliding rate window (rotate first when
        the current window has outlived the profiler window)."""
        if self.first_seen == 0:
            self.first_seen = now
        if self.win_start == 0:
            self.win_start = now
        elif now - self.win_start >= window_ns:
            self.prev_win_calls = self.win_calls
            self.prev_win_s = (now - self.win_start) / 1e9
            self.win_calls = 0
            self.win_start = now
        self.win_calls += 1

    def calls_per_min(self, now: int) -> float:
        """Sliding call rate: counted calls over the covered span (clamped
        to ≥1 s so a just-created bucket doesn't read as infinite)."""
        if self.win_start == 0:
            return 0.0
        span_s = (now - self.win_start) / 1e9 + self.prev_win_s
        return 60.0 * (self.win_calls + self.prev_win_calls) \
            / max(span_s, 1.0)


@dataclass
class _WaveCost:
    """Accumulated decode-wave timing for one (model, version, bucket,
    chunk) shape — fed by the generative scheduler at fetch time (waves
    don't pass through ``Model.execute_timed``; they are dispatched
    pipelined and their occupancy is only known when the token fetch
    lands)."""

    waves: int = 0
    dispatches: int = 0      # executable launches (waves / chunk)
    device_ns: int = 0
    wave_ns_ewma: float = 0.0
    # Per-dispatch per-wave samples for snapshot percentiles; bounded so
    # a long-running engine can't grow it.
    recent: deque = field(default_factory=lambda: deque(maxlen=512))
    # Static cost of one dispatch (the whole K-chunk, not one wave).
    cost_model: dict | None = None


class _GenTotals:
    """Committed spans and counters of one generative (model, version):
    lists indexed like ``spans.GEN_SPANS`` / ``spans.GEN_COUNTERS``."""

    __slots__ = ("ns", "n", "max", "c")

    def __init__(self):
        self.ns = [0] * len(_spans.GEN_SPANS)
        self.n = [0] * len(_spans.GEN_SPANS)
        self.max = [0] * len(_spans.GEN_SPANS)
        self.c = [0] * len(_spans.GEN_COUNTERS)

    def add(self, rec) -> None:
        """Move one finished loop iteration out of ``rec`` (zeroing it)."""
        ns, n, mx = rec.ns, rec.n, rec.max
        for i, count in enumerate(n):
            if count:
                self.n[i] += count
                self.ns[i] += ns[i]
                if mx[i] > self.max[i]:
                    self.max[i] = mx[i]
                n[i] = ns[i] = mx[i] = 0
        c = rec.c
        for i, v in enumerate(c):
            if v:
                self.c[i] += v
                c[i] = 0

    def as_dict(self) -> dict:
        return {
            "spans": {name: {"count": self.n[i], "total_ns": self.ns[i],
                             "max_ns": self.max[i]}
                      for i, name in enumerate(_spans.GEN_SPANS)},
            "counters": dict(zip(_spans.GEN_COUNTERS, self.c)),
        }


class _Bound:
    """One engine registry's instrument handles (see bind_metrics)."""

    __slots__ = ("registry_ref", "fill_ratio", "padded_rows",
                 "compilations", "compile_seconds", "device_seconds",
                 "duty_cycle", "wave_seconds", "model_flops",
                 "mfu", "mbu")

    def __init__(self, registry):
        self.registry_ref = weakref.ref(registry)
        self.fill_ratio = registry.histogram(
            "tpu_batch_fill_ratio",
            "Real rows / padded bucket rows per device execution",
            ("model", "version"), buckets=FILL_RATIO_BUCKETS)
        self.padded_rows = registry.counter(
            "tpu_padded_rows_total",
            "Zero rows added to reach the batch bucket (pure device waste)",
            ("model", "version", "bucket"))
        self.compilations = registry.counter(
            "tpu_xla_compilations_total",
            "XLA backend compilations of every jax.jit, a persistent-cache "
            "hit included (labels: the jit call site's scope, empty "
            "outside any)",
            ("model", "version", "bucket"))
        self.compile_seconds = registry.histogram(
            "tpu_xla_compile_seconds",
            "Duration of each XLA backend compilation (seconds)",
            ("model", "version"), buckets=COMPILE_SECONDS_BUCKETS)
        self.device_seconds = registry.counter(
            "tpu_device_seconds_total",
            "Cumulative executable-busy device time (warm executions)",
            ("model", "version"))
        self.duty_cycle = registry.gauge(
            "tpu_device_duty_cycle",
            "Busy device time / wall time over the profiler window "
            "(sampled at scrape; >1.0 means concurrent instances)")
        self.duty_cycle.set(0.0)
        self.wave_seconds = registry.histogram(
            "tpu_decode_wave_seconds",
            "Per-wave decode step time of the generative engine "
            "(bucket = wave lane count, chunk = waves per dispatch)",
            ("model", "version", "bucket", "chunk"),
            buckets=WAVE_SECONDS_BUCKETS)
        self.model_flops = registry.counter(
            "tpu_model_flops_total",
            "XLA cost-model FLOPs dispatched by warm executions "
            "(static flops per call, padded bucket priced in full)",
            ("model", "version", "bucket"))
        self.mfu = registry.gauge(
            "tpu_mfu",
            "Model FLOP/s utilization per bucket: cost-model flops x "
            "warm calls / device seconds, over the device-kind peak "
            "(absent when peaks or cost model are unknown)",
            ("model", "version", "bucket"))
        self.mbu = registry.gauge(
            "tpu_mbu",
            "Memory bandwidth utilization per bucket: cost-model bytes "
            "accessed x warm calls / device seconds, over the "
            "device-kind peak (absent when unknown)",
            ("model", "version", "bucket"))


class EfficiencyProfiler:
    """Low-overhead always-on cost attribution; see module docstring."""

    def __init__(self, window_s: float | None = None, now=time.monotonic_ns):
        if window_s is None:
            window_s = envcfg.env_float("CLIENT_TPU_PROFILE_WINDOW_S")
        self.window_s = max(1.0, window_s)
        self._now = now
        self._t0 = now()
        self._lock = lockdep.Lock("observability.profiler")
        self._costs: dict[tuple[str, str, int], _BucketCost] = {}
        # (model, version, wave bucket, chunk) -> _WaveCost.
        self._waves: dict[tuple[str, str, int, int], _WaveCost] = {}
        # (end_mono_ns, device_ns) of warm executions inside the window.
        self._busy: deque[tuple[int, int]] = deque()
        self._bound: dict[int, _Bound] = {}
        # (model, version) -> committed generative spans and counters
        # (lists indexed like spans.GEN_SPANS / spans.GEN_COUNTERS).
        self._gen: dict[tuple[str, str], _GenTotals] = {}
        # Every backend compilation the jax.monitoring listener heard.
        self._compile_count = 0
        self._compile_s = 0.0
        self._trace_s = 0.0
        self._lower_s = 0.0
        self._cache_hits = 0
        self._cache_misses = 0
        # scope -> [backend compiles, their seconds, trace s, lower s, hits]
        self._compile_scopes: dict[str, list] = {}
        # The set-up timeline: (name, start mono ns, end mono ns, attrs) in
        # the order recorded; ``attrs`` is None for a ``startup.*`` phase and
        # a compile span's cause, scope and program otherwise.  Served
        # relative to the launcher's entry once that is marked.
        self._startup: list[tuple[str, int, int, dict | None]] = []
        self._startup_dropped = 0
        self._startup_t0: int | None = None

    # -- metric binding ------------------------------------------------------

    def bind_metrics(self, registry) -> None:
        """Declare the profiler's metric families on an engine's
        MetricRegistry and mirror every later observation into it.
        Idempotent per registry; multiple engines may bind; dead
        registries are pruned on the next record."""
        b = _Bound(registry)
        with self._lock:
            self._bound[id(registry)] = b

    def _bindings(self) -> list[_Bound]:
        with self._lock:
            out = []
            for rid, b in list(self._bound.items()):
                if b.registry_ref() is None:
                    del self._bound[rid]
                else:
                    out.append(b)
            return out

    # -- recording (the hot path) -------------------------------------------

    def record_execution(self, model: str, version, bucket: int | None,
                         rows: int, device_ns: int, host_ns: int = 0,
                         cold: bool = False, axis: str = "rows") -> None:
        """One device execution: ``rows`` real units padded up to
        ``bucket`` (None/0 = unbatched model, no padding), taking
        ``device_ns`` in the executable and ``host_ns`` in staging+fetch.
        ``axis`` names the padded unit — batch "rows" (default) or summed
        embedding "lookups" for ragged models; the accounting is the same,
        renderers use the tag. ``cold=True`` (first call, XLA traced)
        keeps the call/row counts but excludes the interval from
        device-time accumulation — it is compile, not load, and is
        accounted by :meth:`record_compile`."""
        key = (str(model), str(version), int(bucket or 0))
        rows = max(0, int(rows))
        padded = max(0, key[2] - rows) if key[2] else 0
        end = self._now()
        with self._lock:
            c = self._costs.get(key)
            if c is None:
                c = self._costs[key] = _BucketCost()
            c.axis = axis
            c.calls += 1
            c.rows += rows
            c.padded_rows += padded
            c.max_rows = max(c.max_rows, rows)
            c.touch(end, int(self.window_s * 1e9))
            if cold:
                c.cold_calls += 1
            else:
                c.device_ns += max(0, device_ns)
                c.host_ns += max(0, host_ns)
                c.device_ns_ewma = (
                    device_ns if c.device_ns_ewma == 0.0
                    else _EWMA_ALPHA * device_ns
                    + (1 - _EWMA_ALPHA) * c.device_ns_ewma)
                c.host_ns_ewma = (
                    host_ns if c.host_ns_ewma == 0.0
                    else _EWMA_ALPHA * host_ns
                    + (1 - _EWMA_ALPHA) * c.host_ns_ewma)
                self._busy.append((end, max(0, device_ns)))
                self._prune_locked(end)
            flops = 0.0
            if not cold and c.cost_model and c.cost_model.get("available"):
                flops = float(c.cost_model.get("flops", 0.0))
        fill = (rows / key[2]) if key[2] else 1.0
        for b in self._bindings():
            b.fill_ratio.observe(fill, model=key[0], version=key[1])
            if padded:
                b.padded_rows.inc(padded, model=key[0], version=key[1],
                                  bucket=str(key[2]))
            if not cold and device_ns > 0:
                b.device_seconds.inc(device_ns / 1e9,
                                     model=key[0], version=key[1])
            if flops > 0:
                b.model_flops.inc(flops, model=key[0], version=key[1],
                                  bucket=str(key[2]))

    def record_compile(self, model: str, version, bucket: int | None,
                       compile_ns: int, trace_id: str | None = None,
                       axis: str = "rows") -> None:
        """A first-call XLA trace finished (``Model.execute_timed``'s
        heuristic: trace + compile + first run of a new input signature):
        book it on the bucket's ``compilations``/``compile_s`` and journal
        ``compile.finished``.  The process-wide compile counter is not fed
        here but by :meth:`record_backend_compile`, which hears every jit.
        ``axis`` tags the
        bucket's padded unit up front — warmup/tuner compiles are
        synthetic (no ``record_execution`` follows), so without it a
        warm-compiled lookup bucket would sit mislabelled "rows" until
        real traffic landed on it."""
        key = (str(model), str(version), int(bucket or 0))
        with self._lock:
            c = self._costs.get(key)
            if c is None:
                c = self._costs[key] = _BucketCost()
            c.axis = axis
            c.compile_count += 1
            c.compile_ns += max(0, compile_ns)
        # Lazy import: observability.metrics users must not pull in the
        # journal (and its env wiring) just by importing this module.
        from client_tpu.observability.events import journal

        journal().emit("compile", "finished", model=key[0],
                       version=key[1], trace_id=trace_id,
                       bucket=key[2], compile_s=round(compile_ns / 1e9, 3))

    def record_backend_compile(self, seconds: float,
                               scope: tuple | None = None,
                               hit: bool = False) -> None:
        """One XLA backend compilation, as ``jax.monitoring`` reported it
        (:func:`install_compile_listener`); ``scope`` is the compiling
        thread's ``(model, version, step, bucket)`` or None outside any jit
        call site the program brackets; ``hit`` where the persistent cache
        held the program and the seconds were its load."""
        model, version, _, bucket = scope or ("", "", "", "")
        with self._lock:
            self._compile_count += 1
            self._compile_s += seconds
            self._cache_hits += hit
            row = self._scope_row(scope)
            row[0] += 1
            row[1] += seconds
            row[4] += hit
        for b in self._bindings():
            b.compilations.inc(model=str(model), version=str(version),
                               bucket=str(bucket))
            b.compile_seconds.observe(seconds, model=str(model),
                                      version=str(version))

    def _scope_row(self, scope: tuple | None) -> list:
        return self._compile_scopes.setdefault(
            _scope_key(scope), [0, 0.0, 0.0, 0.0, 0])

    def record_compile_span(self, name: str, start_ns: int, end_ns: int,
                            scope: tuple | None = None, fun_name: str = "",
                            hit: bool = False,
                            retrieval_s: float | None = None) -> None:
        """One phase of one compilation (``spans.COMPILE_*``), monotonic ns,
        onto the set-up timeline and into the ``compiles`` sums.  ``hit`` and
        ``retrieval_s`` (the seconds of the span that read the persistent
        cache's entry) belong to a ``compile.backend`` span."""
        seconds = (end_ns - start_ns) / 1e9
        attrs = {"cause": None, "scope": _scope_key(scope),
                 "fun_name": fun_name}
        if name == _spans.COMPILE_BACKEND:
            attrs["cache"] = "hit" if hit else "miss"
            if retrieval_s is not None:
                attrs["retrieval_s"] = retrieval_s
            self.record_backend_compile(seconds, scope, hit)
        with self._lock:
            if name == _spans.COMPILE_TRACE:
                self._trace_s += seconds
                self._scope_row(scope)[2] += seconds
            elif name == _spans.COMPILE_LOWER:
                self._lower_s += seconds
                self._scope_row(scope)[3] += seconds
            self._append_span(name, start_ns, end_ns, attrs)

    def record_cache_miss(self) -> None:
        with self._lock:
            self._cache_misses += 1

    def compile_totals(self) -> dict:
        """The snapshot's ``compiles`` object: every compilation since
        process start (``count``, ``seconds``: the backend's, a cache load
        included), the tracing and lowering before it, and all of it by the
        scope it happened in."""
        with self._lock:
            return {
                "count": self._compile_count,
                "seconds": self._compile_s,
                "trace_seconds": self._trace_s,
                "lower_seconds": self._lower_s,
                "cache_hits": self._cache_hits,
                "cache_misses": self._cache_misses,
                "by_scope": {
                    k: {"count": n, "seconds": sec, "trace_s": trace_s,
                        "lower_s": lower_s, "hits": hits}
                    for k, (n, sec, trace_s, lower_s, hits)
                    in sorted(self._compile_scopes.items())},
            }

    def commit_generative(self, model: str, version, rec) -> None:
        """One finished loop iteration of a generative worker
        (``spans.GenRecorder.end_loop``): moved into the (model, version)
        totals, which outlive the worker."""
        key = (str(model), str(version))
        with self._lock:
            tot = self._gen.get(key)
            if tot is None:
                tot = self._gen[key] = _GenTotals()
            tot.add(rec)

    def startup_entry(self) -> None:
        """The launcher's entry: set-up spans are reported relative to it,
        and where nothing was recorded before it (no wrapper initialised the
        backend first) ``startup.process`` ends here."""
        self._startup_t0 = self._now()
        self.record_process_start(self._startup_t0)

    def record_process_start(self, until_ns: int) -> None:
        """``startup.process``: from the operating system's start of this
        process to ``until_ns``, where the first set-up phase starts: the
        interpreter and the imports before it.  Recorded once, by whichever
        comes first of the launcher's entry and ``ensure_backend``."""
        born = process_start_ns()
        if born is None or born > until_ns:
            return
        with self._lock:
            if all(attrs is not None for *_, attrs in self._startup):
                self._append_span(_spans.STARTUP_PROCESS, born, int(until_ns))

    def record_startup(self, name: str, start_ns: int, end_ns: int,
                       rest: str | None = None) -> None:
        """One set-up phase (``spans.STARTUP_*``), monotonic ns.  The compile
        spans inside it that no phase has claimed yet take it as their cause.
        Where ``rest`` names a span, every stretch of the phase under no
        compile span is recorded under that name (``startup.first_run:`` of a
        warm-up: a program's first execution and the staging for it), so the
        children partition the parent."""
        start_ns, end_ns = int(start_ns), int(end_ns)
        with self._lock:
            inside = []
            for _, a, b, attrs in self._startup:
                if attrs is not None and start_ns <= a and b <= end_ns:
                    inside.append((a, b))
                    if attrs["cause"] is None:
                        attrs["cause"] = name
            if rest:
                for a, b in _uncovered(start_ns, end_ns, inside):
                    if b - a >= _FIRST_RUN_MIN_NS:
                        self._append_span(rest, a, b)
            self._append_span(name, start_ns, end_ns)

    def record_startup_since_last(self, name: str) -> None:
        """A set-up phase that runs from the end of the latest ``startup.*``
        phase to now: what the launcher did between two recorded phases
        (``startup.imports``: the zoo's import, the arguments, the
        repository's build, and whatever a wrapper did before the entry)."""
        now = self._now()
        with self._lock:
            last = max((b for *_, b, attrs in self._startup
                        if attrs is None), default=None)
        if last is not None and last <= now:
            self.record_startup(name, last, now)

    def _append_span(self, name: str, start_ns: int, end_ns: int,
                     attrs: dict | None = None) -> None:
        # The caller holds the lock.
        if len(self._startup) < _STARTUP_SPANS_MAX:
            self._startup.append((name, start_ns, end_ns, attrs))
        else:
            self._startup_dropped += 1

    def record_cost_model(self, model: str, version, bucket: int | None,
                          cost: dict | None, axis: str = "rows") -> None:
        """Attach the static XLA cost model captured for a bucket's
        executable (:func:`client_tpu.observability.roofline.
        capture_cost_model`, called once per first-call trace alongside
        :meth:`record_compile`). An available capture always replaces a
        prior one (recompile = new executable); an *unavailable* capture
        only fills an empty slot — a bucket serving multiple signatures
        keeps its working cost model even if one exotic signature's
        analysis fails."""
        if not cost:
            return
        key = (str(model), str(version), int(bucket or 0))
        with self._lock:
            c = self._costs.get(key)
            if c is None:
                c = self._costs[key] = _BucketCost()
            c.axis = axis
            if cost.get("available") or c.cost_model is None:
                c.cost_model = dict(cost)

    def record_wave_cost_model(self, model: str, version, bucket: int,
                               chunk: int, cost: dict | None) -> None:
        """Same contract as :meth:`record_cost_model` for a decode-wave
        executable — the cost prices one *dispatch* (all ``chunk``
        scanned waves), matching _WaveCost.dispatches."""
        if not cost:
            return
        key = (str(model), str(version), int(bucket), max(1, int(chunk)))
        with self._lock:
            w = self._waves.get(key)
            if w is None:
                w = self._waves[key] = _WaveCost()
            if cost.get("available") or w.cost_model is None:
                w.cost_model = dict(cost)

    def record_wave(self, model: str, version, bucket: int, chunk: int,
                    duration_ns: int, waves: int = 1) -> None:
        """One generative decode dispatch completed: ``waves`` logical
        wave steps (``chunk`` > 1 when a scanned K-chunk) over a
        ``bucket``-lane executable took ``duration_ns`` of device
        occupancy.  Feeds ``tpu_decode_wave_seconds`` (one observation per
        logical wave, at the per-wave time), the snapshot's decode-wave
        table, and the duty-cycle window — generative waves never pass
        through ``Model.execute_timed``, so without this the busiest
        engine in the fleet read as idle."""
        key = (str(model), str(version), int(bucket), max(1, int(chunk)))
        waves = max(1, int(waves))
        duration_ns = max(0, int(duration_ns))
        per_wave_ns = duration_ns / waves
        end = self._now()
        with self._lock:
            w = self._waves.get(key)
            if w is None:
                w = self._waves[key] = _WaveCost()
            w.waves += waves
            w.dispatches += 1
            w.device_ns += duration_ns
            w.wave_ns_ewma = (
                per_wave_ns if w.wave_ns_ewma == 0.0
                else _EWMA_ALPHA * per_wave_ns
                + (1 - _EWMA_ALPHA) * w.wave_ns_ewma)
            w.recent.append(per_wave_ns)
            self._busy.append((end, duration_ns))
            self._prune_locked(end)
            flops = 0.0
            if w.cost_model and w.cost_model.get("available"):
                flops = float(w.cost_model.get("flops", 0.0))
        per_wave_s = per_wave_ns / 1e9
        for b in self._bindings():
            for _ in range(waves):
                b.wave_seconds.observe(per_wave_s, model=key[0],
                                       version=key[1], bucket=str(key[2]),
                                       chunk=str(key[3]))
            if flops > 0:
                b.model_flops.inc(flops, model=key[0], version=key[1],
                                  bucket=str(key[2]))

    # -- duty cycle ----------------------------------------------------------

    def _prune_locked(self, now: int) -> None:
        horizon = now - int(self.window_s * 1e9)
        while self._busy and self._busy[0][0] < horizon:
            self._busy.popleft()

    def duty_cycle(self) -> float:
        """Busy device time / wall time over the sliding window. Intervals
        straddling the window edge contribute their overlap only."""
        now = self._now()
        window_ns = int(self.window_s * 1e9)
        start = now - window_ns
        with self._lock:
            self._prune_locked(now)
            busy = 0
            for end, dur in self._busy:
                busy += min(end, now) - max(end - dur, start)
        wall = min(window_ns, max(1, now - self._t0))
        return busy / wall

    def update_gauges(self) -> None:
        """Refresh ``tpu_device_duty_cycle`` and the per-bucket
        ``tpu_mfu`` / ``tpu_mbu`` gauges on every bound registry; called
        at scrape time so a quiet period still reads current. MFU/MBU
        rows exist only where both the cost model and the device peaks
        are known — an unknown-peaks CPU host scrapes the (empty)
        families cleanly rather than lying with zeros."""
        duty = self.duty_cycle()
        rows = self._utilization_rows()
        for b in self._bindings():
            b.duty_cycle.set(round(duty, 6))
            for model, version, bucket, mfu, mbu in rows:
                if mfu is not None:
                    b.mfu.set(round(mfu, 6), model=model, version=version,
                              bucket=bucket)
                if mbu is not None:
                    b.mbu.set(round(mbu, 6), model=model, version=version,
                              bucket=bucket)

    def _utilization_rows(self) -> list[tuple]:
        """(model, version, bucket, mfu, mbu) for every bucket with an
        available cost model and warm device time; wave cells aggregate
        across chunks into their lane bucket. Empty when peaks are
        unknown (CPU host without a CLIENT_TPU_ROOFLINE override)."""
        peaks = _roofline.resolve_peaks()
        if peaks is None or not (peaks.flops_per_s or peaks.bytes_per_s):
            return []
        agg: dict[tuple[str, str, str], list[float]] = {}
        with self._lock:
            for (mname, version, bucket), c in self._costs.items():
                warm = c.calls - c.cold_calls
                if warm <= 0 or c.device_ns <= 0:
                    continue
                if not (c.cost_model and c.cost_model.get("available")):
                    continue
                row = agg.setdefault((mname, version, str(bucket)),
                                     [0.0, 0.0, 0.0])
                row[0] += float(c.cost_model.get("flops", 0.0)) * warm
                row[1] += float(
                    c.cost_model.get("bytes_accessed", 0.0)) * warm
                row[2] += c.device_ns / 1e9
            for (mname, version, bucket, _chunk), w in self._waves.items():
                if w.dispatches <= 0 or w.device_ns <= 0:
                    continue
                if not (w.cost_model and w.cost_model.get("available")):
                    continue
                row = agg.setdefault((mname, version, str(bucket)),
                                     [0.0, 0.0, 0.0])
                row[0] += float(
                    w.cost_model.get("flops", 0.0)) * w.dispatches
                row[1] += float(
                    w.cost_model.get("bytes_accessed", 0.0)) * w.dispatches
                row[2] += w.device_ns / 1e9
        out = []
        for (mname, version, bucket), (flops, byts, dev_s) in agg.items():
            if dev_s <= 0:
                continue
            mfu = (flops / dev_s / peaks.flops_per_s) \
                if peaks.flops_per_s else None
            mbu = (byts / dev_s / peaks.bytes_per_s) \
                if peaks.bytes_per_s else None
            if mfu is not None or mbu is not None:
                out.append((mname, version, bucket, mfu, mbu))
        return out

    # -- report ---------------------------------------------------------------

    def snapshot(self, model: str | None = None) -> dict:
        """The ``GET /v2/profile`` body: per-model/per-bucket cost table
        with padding-waste estimates and a bucket-ladder suggestion."""
        now = self._now()
        ctx = _roofline.roofline_context()
        peaks_dict = ctx.get("peaks")
        peaks = None
        if isinstance(peaks_dict, dict):
            peaks = _roofline.PeakSpec(peaks_dict.get("flops_per_s"),
                                       peaks_dict.get("bytes_per_s"),
                                       peaks_dict.get("source", "registry"))
        with self._lock:
            items = sorted(self._costs.items())
            wave_items = sorted(
                (k, (w.waves, w.device_ns, w.wave_ns_ewma,
                     sorted(w.recent), w.dispatches, w.cost_model))
                for k, w in self._waves.items())
            gen_items = sorted((k, g.as_dict()) for k, g in self._gen.items())
            startup = [(name, a, b, attrs and dict(attrs))
                       for name, a, b, attrs in self._startup]
            dropped = self._startup_dropped
        models: dict[str, dict] = {}
        # Per-model roofline accumulators: [flops, bytes, wasted_flops,
        # covered_device_s] summed over buckets+waves with cost models.
        roofline_agg: dict[str, list[float]] = {}

        def model_entry(mname: str, version: str) -> dict:
            mkey = f"{mname}:{version}"
            entry = models.get(mkey)
            if entry is None:
                entry = models[mkey] = {
                    "model": mname, "version": version,
                    "device_s": 0.0, "host_s": 0.0,
                    "padding_waste_device_s": 0.0,
                    "compilations": 0, "compile_s": 0.0,
                    "buckets": [], "suggestion": None,
                    "suggestions": [],
                }
                roofline_agg[mkey] = [0.0, 0.0, 0.0, 0.0]
            return entry

        def accumulate_roofline(mkey: str, rl: dict,
                                device_s: float) -> None:
            if rl.get("cost_model") != "xla":
                return
            agg = roofline_agg[mkey]
            agg[0] += rl["total_flops"]
            agg[1] += rl["total_bytes"]
            agg[2] += rl["padding_wasted_flops"]
            agg[3] += device_s

        for (mname, version, bucket), c in items:
            if model and mname != model:
                continue
            entry = model_entry(mname, version)
            waste = c.padding_waste_device_s()
            entry["device_s"] += c.device_ns / 1e9
            entry["host_s"] += c.host_ns / 1e9
            entry["padding_waste_device_s"] += waste
            entry["compilations"] += c.compile_count
            entry["compile_s"] += c.compile_ns / 1e9
            warm = c.calls - c.cold_calls
            total_rows = c.rows + c.padded_rows
            rl = _roofline.bucket_roofline(
                c.cost_model, warm, c.device_ns / 1e9,
                (c.padded_rows / total_rows) if total_rows else 0.0,
                peaks)
            accumulate_roofline(f"{mname}:{version}", rl,
                                c.device_ns / 1e9)
            entry["buckets"].append({
                "bucket": bucket,
                "axis": c.axis,
                "roofline": rl,
                "executions": c.calls,
                "cold_executions": c.cold_calls,
                "rows": c.rows,
                "padded_rows": c.padded_rows,
                "max_rows": c.max_rows,
                "fill_ratio": round(c.fill_ratio(), 4),
                "device_s": round(c.device_ns / 1e9, 6),
                "host_s": round(c.host_ns / 1e9, 6),
                "device_s_per_call_ewma": round(c.device_ns_ewma / 1e9, 6),
                "host_s_per_call_ewma": round(c.host_ns_ewma / 1e9, 6),
                "padding_waste_device_s": round(waste, 6),
                "compilations": c.compile_count,
                "compile_s": round(c.compile_ns / 1e9, 6),
                "calls_per_min": round(c.calls_per_min(now), 3),
                "observed_s": round(
                    (now - c.first_seen) / 1e9 if c.first_seen else 0.0, 3),
            })
        # Generative decode waves (record_wave): per (bucket, chunk) wave
        # step times.  Wave device time also counts into the model's
        # device_s total — generative engines never pass execute_timed,
        # so without this their models profile as idle.
        for (mname, version, bucket, chunk), \
                (wv, dns, ewma, recent, dispatches, wcost) in wave_items:
            if model and mname != model:
                continue
            entry = model_entry(mname, version)
            entry["device_s"] += dns / 1e9
            rl = _roofline.bucket_roofline(wcost, dispatches, dns / 1e9,
                                           0.0, peaks)
            accumulate_roofline(f"{mname}:{version}", rl, dns / 1e9)

            def pct(q: float) -> float:
                if not recent:
                    return 0.0
                return recent[min(len(recent) - 1, int(q * len(recent)))]

            entry.setdefault("decode_waves", []).append({
                "bucket": bucket,
                "chunk": chunk,
                "waves": wv,
                "dispatches": dispatches,
                "device_s": round(dns / 1e9, 6),
                "wave_ms_ewma": round(ewma / 1e6, 3),
                "wave_ms_p50": round(pct(0.5) / 1e6, 3),
                "wave_ms_p99": round(pct(0.99) / 1e6, 3),
                "roofline": rl,
            })
        for (mname, version), gen in gen_items:
            if model and mname != model:
                continue
            model_entry(mname, version)["generative"] = gen
        for mkey, entry in models.items():
            entry["device_s"] = round(entry["device_s"], 6)
            entry["host_s"] = round(entry["host_s"], 6)
            entry["compile_s"] = round(entry["compile_s"], 6)
            entry["padding_waste_device_s"] = round(
                entry["padding_waste_device_s"], 6)
            entry["suggestion"] = _suggest_bucket_tweak(entry["buckets"])
            entry["suggestions"] = _suggest_ladder_tweaks(
                entry["buckets"], self.window_s)
            entry["roofline"] = _model_roofline(
                roofline_agg[mkey], entry["device_s"], peaks)
        return {
            "window_s": self.window_s,
            "duty_cycle": round(self.duty_cycle(), 6),
            "roofline": ctx,
            "compiles": self.compile_totals(),
            **_startup_timeline(startup, self._startup_t0, dropped),
            "models": models,
        }

    def reset(self) -> None:
        """Drop accumulated costs (tests); metric bindings survive."""
        with self._lock:
            self._costs.clear()
            self._waves.clear()
            self._gen.clear()
            self._busy.clear()
            self._t0 = self._now()


def _model_roofline(agg: list[float], device_s: float, peaks) -> dict:
    """Model-level roofline rollup from the per-bucket accumulators
    (flops, bytes, padding-wasted flops, covered device seconds).
    ``cost_model_coverage`` is the fraction of the model's device time
    whose executables carry a cost model — the honesty knob: a 0.4
    coverage MFU describes 40% of the time, not the model."""
    flops, byts, wasted, covered_s = agg
    out = {
        "total_flops": flops,
        "total_bytes": byts,
        "padding_wasted_flops": wasted,
        "cost_model_coverage": round(covered_s / device_s, 4)
        if device_s > 0 else 0.0,
        "achieved_flops_per_s": None,
        "achieved_bytes_per_s": None,
        "arithmetic_intensity": None,
        "mfu": None,
        "mbu": None,
        "bound": "unknown",
    }
    if covered_s <= 0:
        return out
    achieved_f = flops / covered_s
    achieved_b = byts / covered_s
    intensity = (flops / byts) if byts > 0 else None
    out["achieved_flops_per_s"] = achieved_f
    out["achieved_bytes_per_s"] = achieved_b
    out["arithmetic_intensity"] = round(intensity, 4) \
        if intensity is not None else None
    out["bound"] = _roofline.classify_bound(intensity, peaks)
    if peaks and peaks.flops_per_s:
        out["mfu"] = round(achieved_f / peaks.flops_per_s, 6)
    if peaks and peaks.bytes_per_s:
        out["mbu"] = round(achieved_b / peaks.bytes_per_s, 6)
    return out


def _startup_timeline(spans: list, t0: int | None, dropped: int) -> dict:
    """The snapshot's ``startup`` list, ``(name, start_s, end_s)`` and a
    compile span's attributes, relative to the launcher's entry (to the
    earliest start where no launcher marked one: an embedded engine), and
    ``startup_clock``: that entry as an absolute ``time.monotonic()``, which
    is one clock for every process of the machine, and the spans the list's
    bound refused.  A phase that ran before the entry (the process's own
    start; a wrapper that initialised the backend first) starts below
    zero."""
    if t0 is None:
        t0 = min((a for _, a, _, _ in spans), default=0)
    return {
        "startup": [{"name": name, "start_s": (a - t0) / 1e9,
                     "end_s": (b - t0) / 1e9, **(attrs or {})}
                    for name, a, b, attrs in spans],
        "startup_clock": {"entry_monotonic_s": t0 / 1e9, "dropped": dropped},
    }


def _uncovered(start: int, end: int, intervals: list) -> list[tuple]:
    """The stretches of ``[start, end]`` that none of ``intervals`` covers."""
    out, at = [], start
    for a, b in sorted(intervals):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if end > at:
        out.append((at, end))
    return out


def _scope_key(scope: tuple | None) -> str:
    """``by_scope``'s key: ``model:version:step:bucket``, "" outside any."""
    return ":".join(map(str, scope)) if scope else ""


def process_start_ns() -> int | None:
    """When the operating system started this process, on
    ``time.monotonic_ns()``'s clock: the start time of ``/proc/self/stat``
    (clock ticks after boot) against the boot clock now.  None where the
    system does not say."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read()
        # Field 22, counted behind the command's closing parenthesis.
        ticks = int(stat[stat.rindex(b")") + 2:].split()[19])
        age_ns = (time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                  - ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic_ns() - age_ns


def _suggest_bucket_tweak(buckets: list[dict]) -> dict | None:
    """Greedy ladder tweak: the bucket wasting the most device time on
    padding, with enough calls to trust its fill ratio and headroom below
    it (max observed rows < bucket), suggests inserting a bucket at the
    observed row high-water mark. Returns None when the ladder looks
    right-sized."""
    worst = None
    for b in buckets:
        if b["bucket"] <= 1 or b["executions"] < _SUGGEST_MIN_CALLS:
            continue
        if b["fill_ratio"] >= _SUGGEST_MAX_FILL:
            continue
        if b["max_rows"] >= b["bucket"]:
            continue
        if worst is None or (b["padding_waste_device_s"]
                             > worst["padding_waste_device_s"]):
            worst = b
    if worst is None:
        return None
    suggested = max(1, worst["max_rows"])
    # Executable time scales ~linearly with bucket rows on TPU, so
    # re-landing these executions on the smaller bucket saves the row
    # fraction of their device time.
    saving = worst["device_s"] * (1 - suggested / worst["bucket"])
    return {
        "action": "add_bucket",
        "bucket": suggested,
        "below": worst["bucket"],
        "fill_ratio": worst["fill_ratio"],
        "est_saving_device_s": round(saving, 6),
        "reason": (f"bucket {worst['bucket']} ran {worst['executions']} "
                   f"executions at {worst['fill_ratio']:.0%} fill "
                   f"(max {worst['max_rows']} real rows); a "
                   f"{suggested}-row bucket would absorb them"),
    }


def _suggest_ladder_tweaks(buckets: list[dict],
                           window_s: float) -> list[dict]:
    """The full suggestion list the autotuner acts on: the greedy
    ``add_bucket`` (same semantics as :func:`_suggest_bucket_tweak`) plus
    one ``retire_bucket`` per cold bucket — tracked for at least a full
    profile window yet called below :data:`_SUGGEST_RETIRE_RATE_PER_MIN`.
    The largest tracked bucket is never suggested for retirement (the
    ladder must keep covering max_batch_size); the tuner re-validates
    against the actual configured ladder before acting."""
    out: list[dict] = []
    add = _suggest_bucket_tweak(buckets)
    if add is not None:
        out.append(add)
    largest = max((b["bucket"] for b in buckets), default=0)
    for b in buckets:
        if b["bucket"] < 1 or b["bucket"] >= largest:
            continue
        if b.get("observed_s", 0.0) < window_s:
            continue  # too young: absence of calls is not yet evidence
        rate = b.get("calls_per_min", 0.0)
        if rate >= _SUGGEST_RETIRE_RATE_PER_MIN:
            continue
        out.append({
            "action": "retire_bucket",
            "bucket": b["bucket"],
            "calls_per_min": rate,
            "reason": (f"bucket {b['bucket']} saw "
                       f"{rate:.2f} calls/min over the last "
                       f"{window_s:.0f}s window (floor "
                       f"{_SUGGEST_RETIRE_RATE_PER_MIN})"),
        })
    return out


# -- process-global default profiler ------------------------------------------

_default: EfficiencyProfiler | None = None
_default_lock = lockdep.Lock("observability.profiler.default")


def profiler() -> EfficiencyProfiler:
    """The process-global profiler (double-checked, like
    :func:`client_tpu.observability.events.journal`): models record into
    it from below the engine; engines bind their metric registries to it
    from above."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = EfficiencyProfiler()
    return _default


def reset_profiler() -> None:
    """Drop the global profiler (tests); the next profiler() recreates it
    with current env settings."""
    global _default
    with _default_lock:
        _default = None


# -- the compile listener -------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_COMPILE_SPAN_OF = {TRACE_EVENT: _spans.COMPILE_TRACE,
                    LOWER_EVENT: _spans.COMPILE_LOWER,
                    BACKEND_COMPILE_EVENT: _spans.COMPILE_BACKEND}

# The compiling thread's own: ``value`` (the scope), and between JAX's events
# of one compilation ``open`` (how many compile phases the thread is inside),
# ``hit`` and ``retrieval_s`` (what the persistent cache said inside the
# backend span).
_scope = threading.local()
_listener_installed = False


def set_compile_scope(model: str, version, step: str, bucket) -> None:
    """What the calling thread is about to run through a ``jax.jit``: read
    by the compile listener if that call compiles.  ``Model._set_state``
    sets it at every jit call site, ``_clear_state`` clears it."""
    _scope.value = (model, version, step, bucket)


def clear_compile_scope() -> None:
    _scope.value = None


def _on_compile_start(event: str, value: float, **_) -> None:
    # JAX reports a phase's start as a scalar.  A jitted function traced
    # inside another's trace, or inside a lowering (a Pallas kernel's body
    # traces a jnp function an operator, hundreds a program), reports a trace
    # span of its own inside the outer phase: the count of open phases tells
    # the outermost, the one the timeline keeps.
    if event in _COMPILE_SPAN_OF:
        _scope.open = getattr(_scope, "open", 0) + 1


def _on_compile_span(event: str, start_time: float, end_time: float,
                     fun_name: str = "", **_) -> None:
    name = _COMPILE_SPAN_OF.get(event)
    if name is None:
        return
    _scope.open = max(0, getattr(_scope, "open", 0) - 1)
    if name == _spans.COMPILE_TRACE and _scope.open:
        return
    hit, retrieval_s = False, None
    if name == _spans.COMPILE_BACKEND:
        hit = getattr(_scope, "hit", False)
        retrieval_s = getattr(_scope, "retrieval_s", None)
        _scope.hit, _scope.retrieval_s = False, None
    # JAX stamps time.time(); the timeline is on time.monotonic_ns().
    # tpulint: allow[wall-clock] the offset that moves JAX's wall stamps onto the monotonic clock
    to_monotonic = time.monotonic_ns() - time.time_ns()
    profiler().record_compile_span(
        name, int(start_time * 1e9) + to_monotonic,
        int(end_time * 1e9) + to_monotonic,
        getattr(_scope, "value", None), str(fun_name), hit, retrieval_s)


def _on_compile_duration(event: str, duration_secs: float, **_) -> None:
    if event == CACHE_RETRIEVAL_EVENT:
        _scope.retrieval_s = duration_secs


def _on_compile_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _scope.hit = True
    elif event == CACHE_MISS_EVENT:
        profiler().record_cache_miss()


def install_compile_listener() -> None:
    """Register the process's one ``jax.monitoring`` listener (idempotent).
    It feeds whichever profiler is the global one when a compile happens,
    so ``reset_profiler()`` needs no re-registration."""
    global _listener_installed
    with _default_lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring as monitoring

    monitoring.register_scalar_listener(_on_compile_start)
    monitoring.register_event_time_span_listener(_on_compile_span)
    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    monitoring.register_event_listener(_on_compile_event)
