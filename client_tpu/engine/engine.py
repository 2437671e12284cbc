"""TpuEngine — the in-process serving façade.

This is the API surface the reference reaches through ~45 dlsym-bound
``TRITONSERVER_*`` entry points (/root/reference/src/c++/perf_analyzer/
client_backend/triton_c_api/triton_loader.h:83-255): server metadata and
health, model metadata/config/statistics, repository control, shared-memory
registration, and inference (sync + callback async). Network frontends
(``client_tpu.server``) and the in-process perf backend both sit directly on
this class, so benchmarking without a network is first-class (the reference's
C-API mode, SURVEY.md §3.5).
"""

from __future__ import annotations

from client_tpu import config as envcfg
import threading
import time
from client_tpu.utils import lockdep
from typing import Callable

import client_tpu
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.scheduler import Scheduler, make_scheduler
from client_tpu.engine.stats import ModelStats
from client_tpu.engine.types import (
    DeadlineExpired,
    EngineError,
    InferRequest,
    InferResponse,
    now_ns,
)

SERVER_NAME = "client_tpu"
SERVER_EXTENSIONS = [
    "classification",
    "sequence",
    "model_repository",
    "model_repository(unload_dependents)",
    "schedule_policy",
    "model_configuration",
    "binary_tensor_data",
    "parameters",
    "statistics",
]


class TpuEngine:
    def __init__(self, repository: ModelRepository | None = None, *,
                 jit: bool = True, warmup: bool = False,
                 load_all: bool = True, eager_init: bool = True,
                 metrics_registry=None, admission=None, qos=None):
        if eager_init and jit:
            # Pay PjRt client creation here, on the constructing thread, with
            # progress logged — never lazily inside a scheduler worker where
            # a slow TPU attach is indistinguishable from a hang (round-1
            # failure mode: first device_put on a daemon thread → opaque 504).
            from client_tpu.engine.backend_init import ensure_backend
            from client_tpu.observability.roofline import (
                require_device_peaks,
            )

            require_device_peaks(ensure_backend())
        self.repository = repository or ModelRepository(jit=jit)
        self._schedulers: dict[str, Scheduler] = {}
        self._stats: dict[str, ModelStats] = {}
        self._lock = lockdep.RLock("engine.engine")
        self._warmup = warmup
        self._live = True
        self._draining = False
        # Shared-memory data planes (SURVEY.md §5.8); frontends reach them
        # uniformly through these attributes.
        from client_tpu.engine.shm import SystemShmManager, TpuShmManager
        from client_tpu.engine.trace import TraceManager
        from client_tpu.observability.metrics import EngineMetrics
        from client_tpu.observability.tracing import TraceStore

        self.system_shm = SystemShmManager()
        self.tpu_shm = TpuShmManager()
        self.trace = TraceManager()
        # Histogram/gauge layer; a private registry per engine by default so
        # two engines in one process (tests) don't cross-pollute. Pass
        # observability.REGISTRY for a process-wide one.
        self.metrics = EngineMetrics(metrics_registry)
        # Chaos subsystem: the process-global fault registry, with this
        # engine's metric registry bound so injection counts render in
        # prometheus_metrics() as tpu_fault_injections_total{site,kind}.
        from client_tpu import faults as _faults

        self.faults = _faults.registry()
        self.faults.bind_metrics(self.metrics.registry)
        # Operational event journal (process-global, like the fault
        # registry) and the per-model SLO tracker (CLIENT_TPU_SLO; off by
        # default). SLO burn gauges live on this engine's registry.
        from client_tpu.observability.events import journal
        from client_tpu.observability.slo import SloTracker

        self.events = journal()
        self.slo = SloTracker.from_env(registry=self.metrics.registry)
        # Third + fourth shm data planes: the zero-copy slot ring
        # (engine.shmring) and the staged-dataset segments it references
        # (engine.staged). Constructed after metrics/events so
        # tpu_shm_ring_* / tpu_shm_dataset_* / tpu_shm_reaper_* and the
        # attach/detach/overflow journal events bind to this engine; the
        # ring manager gets the dataset manager (staged descriptor
        # resolution) and async_infer (reaped-mode admission).
        from client_tpu.engine.shmring import RingShmManager
        from client_tpu.engine.staged import StagedDatasetManager

        self.staged_shm = StagedDatasetManager(
            registry=self.metrics.registry, events=self.events)
        self.ring_shm = RingShmManager(registry=self.metrics.registry,
                                       events=self.events,
                                       datasets=self.staged_shm,
                                       submit=self.async_infer)
        # Efficiency profiler (process-global, like the fault registry:
        # models record into it from below the engine). Binding exports
        # tpu_batch_fill_ratio / tpu_padded_rows_total /
        # tpu_xla_compilations_total / tpu_xla_compile_seconds /
        # tpu_device_seconds_total / tpu_device_duty_cycle here.
        from client_tpu.observability.profiler import (
            install_compile_listener,
            profiler as _profiler,
        )

        self.profiler = _profiler()
        self.profiler.bind_metrics(self.metrics.registry)
        # The one feeder of the compile counter (ensure_backend installs it
        # too; an engine built with eager_init=False still counts).
        install_compile_listener()
        # Roofline attribution config: resolved here purely so a
        # malformed CLIENT_TPU_ROOFLINE fails the boot loudly (as does a
        # TPU kind with no peaks row, above) — the capture/join paths
        # re-read it and degrade instead of raising.
        from client_tpu.observability import roofline as _roofline

        _roofline.roofline_config()
        # Cost ledger (process-global, same pattern): schedulers charge
        # tenant-tagged device/queue/HBM time into it from below; binding
        # exports tpu_cost_device_seconds_total / tpu_cost_queue_seconds_
        # total / tpu_cost_hbm_byte_seconds_total /
        # tpu_cost_interference_seconds_total here.
        from client_tpu.observability.costs import ledger as _ledger

        self.costs = _ledger()
        self.costs.bind_metrics(self.metrics.registry)
        # HBM census (process-global: load paths tag buffers from below
        # the engine) + the flight recorder. The recorder holds this
        # engine weakly and samples timeseries_sample() at 1 Hz; with
        # CLIENT_TPU_TIMESERIES=0 attach() is a no-op and the engine is
        # byte-identical to a recorder-less one.
        from client_tpu.observability.memory import hbm_census
        from client_tpu.observability.timeseries import recorder as _recorder

        self.hbm_census = hbm_census()
        self.recorder = _recorder()
        # Per-signal sampler state (fill EWMA, shed-counter deltas);
        # touched only from the recorder thread.
        self._ts_state: dict = {"fill": {}, "shed": {}, "tenant_cost": {},
                                "mono": None}
        self.recorder.attach(self)
        self._last_health: str | None = None
        # (mono_timestamp, LoadReport) pair behind load_report(): the
        # report piggybacks on every inference response, so it is cached
        # for a routing-irrelevant 50ms rather than recomputed per call.
        self._load_report_cache: tuple[float, object] | None = None
        # Admission controller: load shedding + in-flight accounting. The
        # default (CLIENT_TPU_ADMISSION unset) admits everything but still
        # counts in-flight requests — the drain coordinator depends on
        # that. (Imported here: client_tpu.admission imports engine.types,
        # whose package __init__ imports this module — top-level would be
        # circular.)
        from client_tpu.admission import AdmissionController

        self.admission = admission or AdmissionController.from_env(
            metrics=self.metrics)
        if self.admission._metrics is None:
            self.admission._metrics = self.metrics
        # Tenant QoS (CLIENT_TPU_QOS): named classes with WFQ weights,
        # per-class quotas/caps, preemption, and the SLO-burn governor.
        # Disabled (env unset, no explicit controller) everything below
        # is inert: schedulers keep their priority heap and admission
        # runs only the shared gates.
        from client_tpu.admission.qos import QosController

        self.qos = qos or QosController.from_env(metrics=self.metrics)
        if self.qos._metrics is None:
            self.qos._metrics = self.metrics
        self.admission.attach_qos(self.qos)
        self.request_traces = TraceStore(
            capacity=envcfg.env_int("CLIENT_TPU_TRACE_BUFFER"))
        # Opt-in bucket autotuner + HBM planning arena (CLIENT_TPU_AUTOTUNE;
        # see client_tpu.engine.autotune). With the env unset this stays
        # None and the engine is byte-identical to an untuned one: no
        # thread, no arena, ladders fixed at load.
        from client_tpu.engine.autotune import Autotuner, AutotuneConfig

        self.autotuner: Autotuner | None = None
        _tune_cfg = AutotuneConfig.from_env()
        if _tune_cfg is not None:
            self.autotuner = Autotuner(self, _tune_cfg,
                                       registry=self.metrics.registry)
        # Opt-in self-drive governor (CLIENT_TPU_SELFDRIVE): closes the
        # dispatch-retune and SLO-burn-tightening loops. Unset → None,
        # no thread, byte-identical engine.
        from client_tpu.engine.selfdrive import (
            SelfDriveConfig,
            SelfDriveGovernor,
        )

        self.selfdrive: SelfDriveGovernor | None = None
        _sd_cfg = SelfDriveConfig.from_env()
        if _sd_cfg is not None:
            self.selfdrive = SelfDriveGovernor(self, _sd_cfg)
        # Incident blackbox (CLIENT_TPU_BLACKBOX): journal-triggered
        # postmortem bundles on disk. Default ON with conservative
        # caps; ``0``/``off`` disables and leaves self.blackbox None.
        from client_tpu.observability.blackbox import (
            BlackboxConfig,
            BlackboxRecorder,
        )

        self.blackbox: BlackboxRecorder | None = None
        _bb_cfg = BlackboxConfig.from_env()
        if _bb_cfg.enabled:
            self.blackbox = BlackboxRecorder(
                self, _bb_cfg, registry=self.metrics.registry).install()
        self.events.emit(
            "lifecycle", "server_start",
            models=len(self.repository.names()),
            slo_enabled=self.slo.enabled,
            autotune=self.autotuner is not None,
            selfdrive=self.selfdrive is not None,
            blackbox=self.blackbox is not None)
        # name -> reason for every model load_all could not bring up
        # (build, placement or warmup compile).  The launcher turns an
        # entry for a model named on its command line into a failed start.
        self.load_errors: dict[str, str] = {}
        if load_all:
            for name in self.repository.names():
                try:
                    self.load_model(name)
                except Exception as exc:  # noqa: BLE001 — load the rest
                    # Also visible in the repository index state, but a
                    # model silently absent at startup is the kind of
                    # failure operators grep the journal for.
                    self.load_errors[name] = f"{type(exc).__name__}: {exc}"
                    self.events.emit(
                        "lifecycle", "model_load_failed",
                        severity="ERROR", model=name, error=str(exc))
        if self.autotuner is not None:
            self.autotuner.start()
        if self.selfdrive is not None:
            self.selfdrive.start()
        # The QoS governor needs both the alarm (SLO fast burn) and the
        # actuator (a throttleable class bucket); start_governor no-ops
        # without the latter.
        if self.qos.enabled and self.slo.enabled:
            self.qos.start_governor(self.slo, self.costs)

    # -- health / metadata ---------------------------------------------------

    def is_live(self) -> bool:
        return self._live

    def is_ready(self) -> bool:
        # A draining server is still LIVE (don't kill the pod early) but
        # not READY (stop routing new work here).
        return self._live and not self._draining

    def health_state(self) -> str:
        """Readiness with nuance (surfaced via ``/v2/health/ready``):
        READY — serving normally; DEGRADED — serving, but the admission
        controller shed recently (balancers should deprioritize) or a
        model is fast-burning its SLO error budget; DRAINING — refusing
        new work while in-flight requests finish."""
        fast_burn: list[str] = []
        if self._draining or not self._live:
            state = "DRAINING"
        elif self.admission.degraded():
            state = "DEGRADED"
        else:
            fast_burn = self.slo.fast_burn()
            state = "DEGRADED" if fast_burn else "READY"
        prev = self._last_health
        if state != prev:
            self._last_health = state
            detail = {"state": state}
            if prev is not None:
                detail["previous"] = prev
            if fast_burn:
                detail["slo_fast_burn"] = fast_burn
            self.events.emit(
                "lifecycle", "health",
                severity="INFO" if state == "READY" else "WARNING",
                **detail)
        return state

    def begin_drain(self) -> None:
        """Flip readiness off and start rejecting new submissions with
        503 + Retry-After pushback. In-flight and queued work continues;
        :func:`client_tpu.admission.drain.drain` owns the full sequence."""
        self._draining = True

    def server_metadata(self) -> dict:
        # shm extensions are advertised only when a manager is attached.
        extensions = list(SERVER_EXTENSIONS)
        if self.system_shm is not None:
            extensions.append("system_shared_memory")
        if self.tpu_shm is not None:
            extensions.append("tpu_shared_memory")
            extensions.append("cuda_shared_memory")  # wire-parity alias
        if self.ring_shm is not None:
            extensions.append("shm_ring")
        if self.staged_shm is not None:
            extensions.append("staged_dataset")
        return {
            "name": SERVER_NAME,
            "version": client_tpu.__version__,
            "extensions": extensions,
        }

    def model_is_ready(self, name: str, version: str = "") -> bool:
        return self.repository.is_ready(name, version)

    @staticmethod
    def _vkey(name: str, version: str | int = "") -> str:
        """Scheduler/stats key: bare name = latest; 'name:v' per version."""
        v = str(version).strip()
        return f"{name}:{int(v)}" if v else name

    def _model(self, name: str, version: str | int = ""):
        model = self.repository.get(name, version)
        if model is None:
            if name in self.repository.names():
                v = str(version).strip()
                if v and self.repository.is_ready(name):
                    raise EngineError(
                        f"model '{name}' has no version '{v}'", 404)
                raise EngineError(f"model '{name}' is not ready", 400)
            raise EngineError(f"unknown model '{name}'", 404)
        return model

    def model_metadata(self, name: str, version: str = "") -> dict:
        model = self._model(name, version)
        versions = [str(v) for v in
                    sorted(self.repository.loaded_versions(name))]
        return model.config.metadata_dict(versions=versions or None)

    def model_config(self, name: str, version: str = "") -> dict:
        return self._model(name, version).config.config_dict()

    def model_statistics(self, name: str = "", version: str = "") -> dict:
        with self._lock:
            # Versioned keys only — bare-name entries alias the latest
            # version's stats object and would double-count.
            items = sorted((k, s) for k, s in self._stats.items()
                           if ":" in k)
            if name:
                self._model(name, version)
                vfilter = str(version).strip()
                stats = [s.to_dict() for k, s in items
                         if k.rsplit(":", 1)[0] == name
                         and (not vfilter
                              or k.rsplit(":", 1)[1] == str(int(vfilter)))]
            else:
                stats = [s.to_dict() for _, s in items]
        return {"model_stats": stats}

    # -- repository control --------------------------------------------------

    def load_model(self, name: str) -> None:
        """Load (or re-load) a model. Re-loading re-polls the repository
        (Triton load semantics): schedulers are created for newly served
        versions, retired for versions no longer selected, kept untouched
        for unchanged ones, and the bare-name latest alias is refreshed."""
        from client_tpu.observability import spans

        t_load = time.monotonic_ns()
        self.repository.load(name)
        versions = self.repository.loaded_versions(name)
        retired: list[Scheduler] = []
        new_models = []
        new_scheds: list[Scheduler] = []
        with self._lock:
            from client_tpu.engine.ensemble import EnsembleScheduler
            from client_tpu.engine.sequence import make_sequence_scheduler

            for v, model in sorted(versions.items()):
                key = self._vkey(name, v)
                sched = self._schedulers.get(key)
                if sched is not None and sched.model is model:
                    continue  # unchanged version keeps its scheduler
                if sched is not None:
                    retired.append(sched)
                stats = self._stats.get(key)
                if stats is None:
                    stats = ModelStats(
                        name, str(v),
                        instruments=self.metrics.model_instruments(
                            name, str(v)),
                        slo=self.slo, events=self.events)
                    self._stats[key] = stats
                self._schedulers[key] = make_scheduler(
                    model, stats,
                    sequence_cls=make_sequence_scheduler,
                    ensemble_cls=EnsembleScheduler,
                    qos=self.qos if self.qos.enabled else None,
                    engine=self,
                )
                new_models.append(model)
                new_scheds.append(self._schedulers[key])
            valid = {self._vkey(name, v) for v in versions}
            for key in [k for k in self._schedulers
                        if ":" in k and k.rsplit(":", 1)[0] == name
                        and k not in valid]:
                retired.append(self._schedulers.pop(key))
            latest = self._vkey(name, max(versions))
            # Bare-name alias -> latest version (requests without an
            # explicit version, and the pre-versioning internal API).
            self._schedulers[name] = self._schedulers[latest]
            self._stats[name] = self._stats[latest]
            still_referenced = {id(s) for s in self._schedulers.values()}
        for sched in retired:
            if id(sched) not in still_referenced:
                sched.stop()
        # Host-table backends carry a hot-row cache: every explicit load
        # invalidates it (the repository was re-polled — weights may have
        # changed, and stale vectors are a correctness bug, not a perf
        # one); newly built backends additionally bind their tpu_emb_*
        # metrics to this engine's registry.
        for _v, model in sorted(versions.items()):
            cache = getattr(model.backend, "row_cache", None)
            if cache is not None:
                if model in new_models:
                    cache.bind_metrics(self.metrics.registry, name,
                                       model.config.version)
                cache.clear()
        for model in new_models:
            self.events.emit("model", "load", model=name,
                             version=model.config.version)
        if self.autotuner is not None:
            # Retired versions first (dropped by the re-poll or replaced
            # by a new model object): prune their cooldowns/applied marks
            # and release their arena reservations BEFORE the new
            # incarnations re-reserve — otherwise a reload inherits stale
            # cooldowns and the arena double-counts replaced buckets.
            for v in sorted({str(s.model.config.version)
                             for s in retired}):
                self.autotuner.on_version_retired(name, v)
            for model, sched in zip(new_models, new_scheds):
                self.autotuner.on_model_loaded(model, sched)
        # Set-up spans of /v2/profile: build + placement + arena, then the
        # precompile (the launcher's --warmup).
        t_loaded = time.monotonic_ns()
        self.profiler.record_startup(spans.STARTUP_MODEL_LOAD + name,
                                     t_load, t_loaded)
        if self._warmup:
            for model in new_models:
                model.warmup()
            for sched in new_scheds:
                sched.warmup()
            self.profiler.record_startup(spans.STARTUP_WARMUP + name,
                                         t_loaded, time.monotonic_ns(),
                                         rest=spans.STARTUP_FIRST_RUN + name)

    def unload_model(self, name: str, unload_dependents: bool = False) -> None:
        dependents: list[str] = []
        if unload_dependents:
            model = self.repository.get(name)
            if model is not None and model.config.ensemble_scheduling:
                dependents = [s.model_name
                              for s in model.config.ensemble_scheduling]
        with self._lock:
            keys = [k for k in self._schedulers
                    if k == name or k.rsplit(":", 1)[0] == name]
            popped = [self._schedulers.pop(k) for k in keys]
        seen: set[int] = set()
        for sched in popped:
            if id(sched) not in seen:
                seen.add(id(sched))
                sched.stop()
                cache = getattr(sched.model.backend, "row_cache", None)
                if cache is not None:
                    cache.clear()
        versions = sorted(k.rsplit(":", 1)[1] for k in keys if ":" in k)
        if popped:
            self.events.emit("model", "unload", model=name,
                             versions=versions)
        if self.autotuner is not None:
            self.autotuner.on_model_unloaded(name)
        self.repository.unload(name)
        for dep in dependents:
            if dep != name and not self._referenced_by_loaded_ensemble(dep):
                self.unload_model(dep, unload_dependents=True)

    def _referenced_by_loaded_ensemble(self, name: str) -> bool:
        """A composing model shared by several ensembles survives until its
        last referencing ensemble unloads (round-1 bug: unload_dependents
        tore shared components out from under still-loaded ensembles)."""
        with self._lock:
            scheds = list(self._schedulers.values())
        for sched in scheds:
            for step in sched.model.config.ensemble_scheduling:
                if step.model_name == name:
                    return True
        return False

    def repository_index(self) -> list[dict]:
        return self.repository.index()

    def scheduler_for(self, name: str, version: str | int = "") -> Scheduler | None:
        """The live scheduler for one model version (bare version =
        latest alias); None when not loaded. The autotuner resolves
        profiler snapshot keys through this."""
        with self._lock:
            try:
                return self._schedulers.get(self._vkey(name, version))
            except ValueError:
                return None

    def schedulers(self) -> list[Scheduler]:
        """Distinct live schedulers (the bare-name alias shares the latest
        version's object); the drain coordinator polls their queues."""
        with self._lock:
            seen: set[int] = set()
            out: list[Scheduler] = []
            for s in self._schedulers.values():
                if id(s) not in seen:
                    seen.add(id(s))
                    out.append(s)
            return out

    # -- inference -----------------------------------------------------------

    def async_infer(self, req: InferRequest,
                    callback: Callable[[InferResponse], None] | None = None) -> None:
        """Submit; responses arrive on ``req.response_callback`` (or
        ``callback``). Decoupled models may deliver several."""
        if callback is not None:
            req.response_callback = callback
        if req.response_callback is None:
            raise EngineError("async_infer requires a response callback", 400)
        req.times.received = now_ns()
        try:
            key = self._vkey(req.model_name, req.model_version)
        except (EngineError, ValueError):
            req.response_callback(InferResponse.make_error(req, EngineError(
                f"invalid model version '{req.model_version}'", 400)))
            return
        with self._lock:
            sched = self._schedulers.get(key)
        if sched is None:
            # Resolve 404-vs-not-ready and deliver as a response, matching
            # how the wire protocols surface errors. (A model can be in the
            # repository but scheduler-less mid-load.)
            try:
                self._model(req.model_name, req.model_version)
                raise EngineError(
                    f"model '{req.model_name}' is not ready", 400)
            except EngineError as exc:
                req.response_callback(InferResponse.make_error(req, exc))
                return
        model = sched.model
        try:
            if not model.config.ensemble_scheduling:
                model.validate_inputs(req.inputs,
                                      batched=model.config.max_batch_size > 0)
        except EngineError as exc:
            req.response_callback(InferResponse.make_error(req, exc))
            return
        if req.trace is not None:
            self._attach_trace_recorder(req)
        # -- overload protection gates (raise like submit's queue-full 429,
        # so sync and async frontends translate them on one path) ----------
        from client_tpu.admission import AdmissionError

        trace_id = req.trace.trace_id if req.trace is not None else None
        # Resolve the cost-ledger tenant tag before any shed can fire, so
        # rejections are attributable: untagged requests fold to the
        # admission shadow class ("shadow") or "default"; tagged ones are
        # canonicalized into the bounded label space.
        if not req.tenant:
            req.tenant = "shadow" if self.admission.is_shadow(
                req.model_name, req.priority) else "default"
        else:
            req.tenant = self.costs.canonical_tenant(req.tenant)
        # QoS classification: stamp the class (WFQ lane) from the tenant
        # table / priority band, and let a class imply a scheduler
        # priority for requests that arrived without one.
        if self.qos.enabled:
            req.qos_class = self.qos.classify(req.tenant, req.priority)
            if req.priority <= 0:
                level = self.qos.priority_level(req.qos_class)
                if level > 0:
                    req.priority = level
        if self._draining or not self._live:
            self.admission.record_rejection(
                req.model_name, req.model_version, reason="draining",
                trace_id=trace_id, tenant=req.tenant)
            raise AdmissionError(
                "server is draining; retry against another replica",
                retry_after_s=1.0, reason="draining", status=503)
        if req.deadline_expired():
            # The client's end-to-end budget lapsed in transit/parse:
            # reject before it costs a queue slot.
            sched.stats.record_deadline_expired("admission",
                                                trace_id=trace_id)
            raise DeadlineExpired(
                "end-to-end deadline expired before admission")
        class_depth = sched.queue.class_qsize(req.qos_class) \
            if req.qos_class and hasattr(sched.queue, "class_qsize") else 0
        self.admission.admit(
            req.model_name, req.model_version,
            queue_depth=sched.queue.qsize(), instances=len(sched.workers),
            trace_id=trace_id, priority=req.priority, tenant=req.tenant,
            qos_class=req.qos_class, class_queue_depth=class_depth)
        self._submit_accounted(sched, req)

    def _submit_accounted(self, sched: Scheduler, req: InferRequest) -> None:
        """Submit with exactly-once in-flight accounting: the admitted
        count increments before submit and decrements on the FINAL response
        (feeding the service-time EWMA) — or immediately on the unwind path
        when submit itself rejects (queue full / injected fault), since a
        rejected request never gets a callback-delivered response."""
        model_name = req.model_name
        shadow = self.admission.is_shadow(model_name, req.priority)
        qos_class = req.qos_class if self.qos.enabled else ""
        self.admission.on_request_start(model_name, shadow=shadow)
        if qos_class:
            self.qos.on_request_start(qos_class)
        inner = req.response_callback
        ended = [False]

        def _accounted(resp: InferResponse) -> None:
            if resp.final and not ended[0]:
                ended[0] = True
                service_s = None
                t = req.times
                if resp.error is None and t.compute_start:
                    service_s = max(
                        0.0, (t.compute_output_end - t.compute_start) / 1e9)
                self.admission.on_request_end(model_name, service_s,
                                              shadow=shadow)
                if qos_class:
                    self.qos.on_request_end(qos_class)
            inner(resp)

        req.response_callback = _accounted
        try:
            sched.submit(req)
        except BaseException:
            if not ended[0]:
                ended[0] = True
                self.admission.on_request_end(model_name, shadow=shadow)
                if qos_class:
                    self.qos.on_request_end(qos_class)
            raise

    def _attach_trace_recorder(self, req: InferRequest) -> None:
        """Wrap the response callback so the final response snapshots the
        request's span timeline into the trace ring buffer. Only requests
        that carry a TraceContext pay for this — in-process/bench callers
        with ``trace=None`` go through untouched."""
        from client_tpu.observability.tracing import (
            MAX_CHUNK_EVENTS,
            build_request_trace,
        )

        inner = req.response_callback
        chunks: list[int] = []
        if req.token_sink is not None:
            # Tokens that leave by the wave hand-off pass no callback: the
            # scheduler stamps them here (types.TokenSink).
            req.token_sink.chunk_ts_ns = chunks

        def _traced(resp: InferResponse) -> None:
            if not resp.final:
                if len(chunks) < MAX_CHUNK_EVENTS:
                    chunks.append(now_ns())
            else:
                self.request_traces.add(build_request_trace(
                    req.trace, req.model_name, req.request_id, req.times,
                    ok=resp.error is None, chunks=chunks,
                    error=str(resp.error) if resp.error is not None else ""))
            inner(resp)

        req.response_callback = _traced

    def infer(self, req: InferRequest, timeout_s: float | None = None) -> InferResponse:
        """Blocking inference; raises EngineError on failure.

        Decoupled models are rejected here (matching Triton: HTTP infer on a
        decoupled model is an error) — their N-response streams are only
        reachable via :meth:`async_infer` / the gRPC stream frontend.
        """
        try:
            model = self.repository.get(req.model_name, req.model_version)
        except EngineError:
            model = None
        if model is not None and model.config.decoupled:
            raise EngineError(
                f"model '{req.model_name}' is decoupled; use streaming "
                "(async_infer / gRPC stream) to receive its responses", 400)
        done = threading.Event()
        box: list[InferResponse] = []

        def _cb(resp: InferResponse) -> None:
            if resp.final:
                box.append(resp)
                done.set()

        self.async_infer(req, _cb)
        if not done.wait(timeout=timeout_s):
            # Attribute the timeout: a first-request XLA compile and a dead
            # backend look identical from the caller; the model's live
            # execution state distinguishes them. Ensembles execute through
            # their composing models' schedulers, so report those states.
            state = "unknown"
            with self._lock:
                sched = self._schedulers.get(req.model_name)
            if sched is not None:
                steps = sched.model.config.ensemble_scheduling
                if steps:
                    parts = []
                    for step in steps:
                        m = self.repository.get(step.model_name)
                        if m is not None and m.state != "idle":
                            parts.append(f"{step.model_name}: {m.state}")
                    state = "; ".join(parts) if parts else "idle (ensemble)"
                else:
                    state = sched.model.state
            raise EngineError(
                f"inference timed out after {timeout_s}s "
                f"(model '{req.model_name}' state: {state}; first requests "
                "pay XLA compilation — warm up with TpuEngine(warmup=True) "
                "or Model.warmup())", 504)
        resp = box[0]
        if resp.error is not None:
            raise resp.error
        return resp

    # -- shared-memory data plane --------------------------------------------

    def read_shm_tensor(self, region: str, offset: int, byte_size: int,
                        datatype: str, shape) -> "object":
        """Resolve a region-referenced input tensor (tpu regions shadow
        system regions, matching the register namespaces). Shared by every
        frontend (HTTP, gRPC, in-process C API)."""
        for mgr in (self.tpu_shm, self.system_shm):
            if mgr is not None and mgr.has_region(region):
                return mgr.read_tensor(region, offset, byte_size, datatype,
                                       shape)
        raise EngineError(
            f"shared memory region '{region}' not registered", 400)

    def write_shm_tensor(self, region: str, offset: int, byte_size: int,
                         arr) -> int:
        """Place an output tensor into a registered region; returns the
        bytes written."""
        for mgr in (self.tpu_shm, self.system_shm):
            if mgr is not None and mgr.has_region(region):
                return mgr.write_tensor(region, offset, byte_size, arr)
        raise EngineError(
            f"shared memory region '{region}' not registered", 400)

    def ring_doorbell(self, name: str, spec: dict) -> dict:
        """Admit a span of FILLED ring slots (``engine.shmring``); each
        slot becomes an ordinary async_infer submission whose outputs are
        written back into the slot's shm response region."""
        return self.ring_shm.doorbell(name, spec, self.async_infer)

    def resolve_staged_input(self, dataset: str, tensor_index: int,
                             row_start: int, row_count: int) -> "object":
        """Resolve a 24-byte staged-input descriptor to a zero-copy row
        slice of a registered staged dataset (``engine.staged``)."""
        return self.staged_shm.resolve(dataset, tensor_index, row_start,
                                       row_count)

    def prometheus_metrics(self, openmetrics: bool = False) -> str:
        """Prometheus text exposition of the per-model statistics — the
        equivalent of the metrics endpoint the Triton *server* exposes
        (the reference client stack consumes server stats; here the engine
        IS the server, so it exports both the statistics RPC and this).
        Metric names mirror Triton's nv_inference_* vocabulary with a
        tpu_ prefix.

        ``openmetrics=True`` (``Accept: application/openmetrics-text``)
        emits OpenMetrics 1.0 from the histogram/gauge registry only —
        counter ``_total`` naming, bucket exemplars linking to
        ``/v2/trace/requests``, terminal ``# EOF``. The legacy cumulative
        tpu_inference_* block is 0.0.4-only (its counter names don't meet
        OpenMetrics naming rules; the registry carries the same signal)."""
        stats = self.model_statistics()["model_stats"]
        lines: list[str] = []

        def metric(name, kind, help_text, rows):
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {kind}")
            for labels, value in rows:
                lines.append(f"{name}{{{labels}}} {value}")

        def esc(v: str) -> str:
            return str(v).replace("\\", "\\\\").replace('"', '\\"')

        def rows(getter):
            out = []
            for s in stats:
                labels = (f'model="{esc(s["name"])}",'
                          f'version="{esc(s["version"])}"')
                out.append((labels, getter(s)))
            return out

        metric("tpu_inference_request_success", "counter",
               "Successful inference requests",
               rows(lambda s: s["inference_stats"]["success"]["count"]))
        metric("tpu_inference_request_failure", "counter",
               "Failed inference requests",
               rows(lambda s: s["inference_stats"]["fail"]["count"]))
        metric("tpu_inference_count", "counter",
               "Inferences performed (batched requests count each)",
               rows(lambda s: s["inference_count"]))
        metric("tpu_inference_exec_count", "counter",
               "Model executions (batches)",
               rows(lambda s: s["execution_count"]))
        for phase, help_text in (
                ("success", "Cumulative end-to-end request duration"),
                ("queue", "Cumulative queue duration"),
                ("compute_input", "Cumulative input staging duration"),
                ("compute_infer", "Cumulative executable duration"),
                ("compute_output", "Cumulative output fetch duration")):
            name = ("tpu_inference_request_duration_us" if phase == "success"
                    else f"tpu_inference_{phase}_duration_us")
            metric(name, "counter", help_text + " (microseconds)",
                   rows(lambda s, p=phase:
                        s["inference_stats"][p]["ns"] // 1000))
        # Histogram/gauge layer: gauges are sampled at scrape time (queue
        # depth and in-flight batches are point-in-time; HBM via the JAX
        # device API), histograms accumulated on the hot path via
        # ModelStats.instruments.
        with self._lock:
            scheds = [(k, s) for k, s in self._schedulers.items()
                      if ":" in k]
        for key, sched in scheds:
            model_name, version = key.rsplit(":", 1)
            self.metrics.queue_depth.set(
                sched.queue.qsize(), model=model_name, version=version)
            self.metrics.inflight_batches.set(
                getattr(sched, "active_batches", 0),
                model=model_name, version=version)
        self.metrics.update_device_gauges(census=self.hbm_census)
        self.metrics.update_census_gauges(self.memory_census())
        # Duty-cycle and SLO burn gauges refresh at scrape time so a
        # quiet period still reads current windows.
        self.profiler.update_gauges()
        self.ring_shm.update_gauges()
        if self.slo.enabled:
            self.slo.snapshot()
        if openmetrics:
            return self.metrics.render(openmetrics=True)
        return "\n".join(lines) + "\n" + self.metrics.render()

    # -- events / SLO ---------------------------------------------------------

    def events_export(self, *, model=None, severity=None, since_seq=None,
                      since_ts=None, until_ts=None, category=None,
                      limit=None) -> dict:
        """``GET /v2/events`` body: the journal filtered by model /
        minimum severity / exclusive since cursors / category, with
        ``until_ts`` as the inclusive wall upper bound (the "window
        around this edge" read the blackbox and external scrapers use)."""
        return self.events.export(
            model=model, severity=severity, since_seq=since_seq,
            since_ts=since_ts, until_ts=until_ts, category=category,
            limit=limit)

    def slo_snapshot(self) -> dict:
        """``GET /v2/slo`` body: per-model window counts and burn rates."""
        return self.slo.snapshot()

    def costs_snapshot(self, model: str | None = None) -> dict:
        """``GET /v2/costs`` body: the per-tenant cost ledger plus a
        ``reconciliation`` section cross-checking the ledger's totals
        against the efficiency profiler (device-seconds, windowed) and
        the HBM census (live KV-arena bytes) — the independent meters
        the conservation invariant is audited against."""
        snap = self.costs.snapshot(model=model)
        prof = self.profiler.snapshot(model=model)
        prof_device = sum(e["device_s"]
                          for e in prof.get("models", {}).values())
        census = self.memory_census()
        kv_bytes = sum(o["bytes"] for o in census.get("owners", ())
                       if o.get("component") == "kv_arena"
                       and (model is None or o.get("model") == model))
        ledger_device = snap.get("totals", {}).get("device_s", 0.0)
        snap["reconciliation"] = {
            # Profiler device_s is a sliding window; the ledger is
            # cumulative — comparable only while uptime < window_s, so
            # both figures (and the window) ship and the caller decides.
            "profiler_device_s": round(prof_device, 6),
            "profiler_window_s": prof.get("window_s"),
            "ledger_device_s": round(ledger_device, 6),
            "device_s_ratio": round(ledger_device / prof_device, 4)
            if prof_device > 0 else None,
            "census_kv_arena_bytes": int(kv_bytes),
        }
        return snap

    def qos_snapshot(self, model: str | None = None) -> dict:
        """``GET /v2/qos`` body: the controller's class table (weights,
        quotas, throttle ratios, inflight, shed/preemption tallies)
        layered with per-model WFQ lane depths from the live
        schedulers."""
        snap = self.qos.snapshot()
        queues: dict[str, dict[str, int]] = {}
        if self.qos.enabled:
            with self._lock:
                scheds = dict(self._schedulers)
            seen: set[int] = set()
            for key, sched in sorted(scheds.items()):
                name = key.split(":", 1)[0]
                if model and name != model:
                    continue
                q = sched.queue
                if id(sched) in seen or not hasattr(q, "class_qsize"):
                    continue
                seen.add(id(sched))
                depths = {cls: q.class_qsize(cls)
                          for cls in self.qos.class_names()}
                prev = queues.get(name)
                if prev is None:
                    queues[name] = depths
                else:
                    for cls, d in depths.items():
                        prev[cls] = prev.get(cls, 0) + d
        snap["queues"] = queues
        return snap

    # -- flight recorder / HBM census -----------------------------------------

    def timeseries_sample(self) -> dict:
        """One flight-recorder sample (called by the recorder thread at
        1 Hz; see :mod:`client_tpu.observability.timeseries` for the
        signal vocabulary). Scalars are engine-wide; per-model signals
        ride as {model: value} maps."""
        import time as _time

        state = self._ts_state
        now = _time.monotonic()
        elapsed = (now - state["mono"]) if state["mono"] else None
        state["mono"] = now
        sample: dict = {"duty_cycle": round(self.profiler.duty_cycle(), 6)}
        queue_depth: dict[str, int] = {}
        in_flight: dict[str, int] = {}
        for sched in self.schedulers():
            name = sched.model.config.name
            queue_depth[name] = (queue_depth.get(name, 0)
                                 + sched.queue.qsize())
            in_flight[name] = (in_flight.get(name, 0)
                               + getattr(sched, "active_batches", 0))
        sample["queue_depth"] = queue_depth
        sample["in_flight"] = in_flight
        # Batch-fill EWMA per model: the cumulative fill ratio smoothed
        # across ticks (alpha 0.3 ~ a 3-sample memory at 1 Hz).
        psnap = self.profiler.snapshot()
        fill: dict[str, float] = {}
        wave: dict[str, float] = {}
        mfu: dict[str, float] = {}
        for entry in psnap.get("models", {}).values():
            name = entry["model"]
            model_mfu = (entry.get("roofline") or {}).get("mfu")
            if model_mfu is not None:
                mfu[name] = round(float(model_mfu), 6)
            rows = sum(b["rows"] for b in entry.get("buckets", ()))
            padded = sum(b["padded_rows"] for b in entry.get("buckets", ()))
            if rows + padded:
                current = rows / (rows + padded)
                prev = state["fill"].get(name)
                fill[name] = round(
                    current if prev is None
                    else 0.3 * current + 0.7 * prev, 6)
            waves_total = wave_weighted = 0.0
            for w in entry.get("decode_waves", ()):
                n = float(w.get("waves", 0) or 0)
                p50 = w.get("wave_ms_p50")
                if n > 0 and p50 is not None:
                    waves_total += n
                    wave_weighted += n * float(p50)
            if waves_total > 0:
                wave[name] = round(wave_weighted / waves_total, 3)
        state["fill"].update(fill)
        if fill:
            sample["batch_fill"] = fill
        if wave:
            sample["wave_p50_ms"] = wave
        if mfu:
            sample["mfu"] = mfu
        # Admission shed rate: per-model counter delta over the tick gap
        # (the counter sums versions and reasons).
        shed_totals: dict[str, float] = {}
        children = self.metrics.admission_rejections._children
        for values in list(children):
            shed_totals[values[0]] = (shed_totals.get(values[0], 0.0)
                                      + children[values].v)
        shed_rate: dict[str, float] = {}
        for name, total in shed_totals.items():
            prev = state["shed"].get(name, 0.0)
            if elapsed and elapsed > 0:
                shed_rate[name] = round(max(0.0, total - prev) / elapsed, 4)
        state["shed"] = shed_totals
        if shed_rate:
            sample["shed_rate"] = shed_rate
        # Per-tenant device spend rate (device-seconds per wall second =
        # that tenant's share of device occupancy), from cost-ledger
        # deltas. Keys are TENANTS, not models — the recorder's map
        # machinery doesn't care, but readers should.
        cost_rows = self.costs.snapshot().get("tenants", {})
        cost_totals = {t: row["device_s"] + row["padding_s"]
                       for t, row in cost_rows.items()}
        cost_rate: dict[str, float] = {}
        for tenant, total in cost_totals.items():
            prev = state["tenant_cost"].get(tenant, 0.0)
            if elapsed and elapsed > 0:
                cost_rate[tenant] = round(
                    max(0.0, total - prev) / elapsed, 6)
        state["tenant_cost"] = cost_totals
        if cost_rate:
            sample["tenant_cost_rate"] = cost_rate
        # HBM: census actuals (live-array bytes stand in on platforms
        # without memory stats) vs the planner arena's reservations.
        devices = self.hbm_census.device_stats()
        used = sum(d["bytes_in_use"] for d in devices)
        if used == 0:
            try:
                import jax

                from client_tpu.observability.memory import _buffer_nbytes

                used = sum(_buffer_nbytes(a) for a in jax.live_arrays())
            except Exception:  # noqa: BLE001 — no backend
                used = 0
        sample["hbm_used"] = used
        if self.autotuner is not None:
            sample["hbm_reserved"] = int(
                self.autotuner.arena.reserved_bytes())
        if self.slo.enabled:
            burn: dict[str, float] = {}
            for name, report in self.slo.snapshot()["models"].items():
                w = report.get("windows", {}).get("5m")
                if w is not None:
                    burn[name] = float(w.get("availability_burn_rate",
                                             0.0))
            if burn:
                sample["slo_burn"] = burn
        # QoS governor actuation: how many classes are currently running
        # below their configured rate (0 = loop quiescent). A nonzero
        # plateau in the flight recorder is the visual signature of the
        # SLO-burn feedback loop holding a tenant down.
        if self.qos.enabled:
            sample["qos_throttled"] = len(self.qos.throttled_classes())
        return sample

    def timeseries_export(self, *, signal=None, model=None,
                          since_seq=None, since_wall=None,
                          until_wall=None, limit=None) -> dict:
        """``GET /v2/timeseries`` body: the flight-recorder ring,
        optionally narrowed by signal / model / exclusive seq cursor /
        wall-clock window (exclusive lower, inclusive upper)."""
        return self.recorder.export(signal=signal, model=model,
                                    since_seq=since_seq,
                                    since_wall=since_wall,
                                    until_wall=until_wall, limit=limit)

    def memory_census(self) -> dict:
        """``GET /v2/memory`` body: per-owner live device-buffer bytes,
        plan-vs-actual drift against the planner arenas, per-device
        memory stats, and the unattributed remainder."""
        extra_plans: dict = {}
        for sched in self.schedulers():
            backend = sched.model.backend
            hbm = getattr(backend, "hbm_reservation_bytes", None)
            if callable(hbm):
                host_mode = bool(getattr(backend, "host_tables", False))
                if host_mode and self.autotuner is not None:
                    # The tuner arena already carries a rowcache:{name}
                    # reservation for host-mode tables; adding the
                    # backend figure again would double the plan.
                    continue
                component = "rowcache" if host_mode else "embedding"
                try:
                    extra_plans[(sched.model.config.name, component)] = \
                        int(hbm())
                # tpulint: allow[swallowed-exception] backend mid-unload
                except Exception:  # noqa: BLE001 — backend mid-unload
                    pass
        return self.hbm_census.report(extra_plans=extra_plans,
                                      events=self.events)

    # -- incident blackbox ----------------------------------------------------

    def blackbox_bundles(self, bundle_id: str | None = None) -> dict:
        """``GET /v2/debug/bundles[/{id}]`` body: the bundle-ring index,
        or one full bundle. 400 when disabled / malformed id / corrupt
        bundle file, 404 when the id is unknown — never 500."""
        if self.blackbox is None:
            raise EngineError(
                "blackbox disabled (CLIENT_TPU_BLACKBOX=off)", 400)
        try:
            return self.blackbox.bundles(bundle_id)
        except KeyError:
            raise EngineError(
                f"unknown bundle {bundle_id!r}", 404) from None
        except ValueError as exc:
            raise EngineError(str(exc), 400) from None

    def blackbox_capture(self, trigger: str = "manual", *,
                         incident: str | None = None,
                         note: str | None = None) -> dict:
        """``POST /v2/debug/capture`` body: snapshot a bundle now.
        ``manual``/``crash``/``fleet`` triggers always capture; an
        automatic trigger name (the router fan-out path) respects the
        debounce/cooldown and returns ``{"deduped": true}`` with the
        prior bundle id instead of writing a second bundle for the
        same incident."""
        if self.blackbox is None:
            raise EngineError(
                "blackbox disabled (CLIENT_TPU_BLACKBOX=off)", 400)
        try:
            return self.blackbox.capture(
                trigger, incident=incident, note=note,
                respect_cooldown=True)
        except ValueError as exc:
            raise EngineError(str(exc), 400) from None

    # Staleness bound on the cached load report: piggybacked on every
    # inference response, so it must be cheaper than a response — 50ms is
    # far below any routing-relevant signal change at serving timescales.
    LOAD_REPORT_TTL_S = 0.05

    def load_report(self, max_age_s: float | None = None):
        """The replica load report (``GET /v2/load`` + the ``X-Tpu-Load``
        response piggyback): health state, in-flight, queue depth, active
        batches, the admission EWMA wait estimate, and SLO fast-burn —
        everything :class:`client_tpu.router.Router` scores replicas by.
        Cached for :data:`LOAD_REPORT_TTL_S` (pass ``max_age_s=0`` to
        force recomputation)."""
        import time as _time

        from client_tpu.protocol.loadreport import LoadReport

        ttl = self.LOAD_REPORT_TTL_S if max_age_s is None else max_age_s
        now = _time.monotonic()
        cached = self._load_report_cache
        if cached is not None and now - cached[0] <= ttl:
            return cached[1]
        snap = self.admission.load_snapshot()
        inflight = sum(g["inflight"] for g in snap.values())
        queue_depth = 0
        active_batches = 0
        wait_s = 0.0
        models: list[str] = []
        for sched in self.schedulers():
            cfg = sched.model.config
            models.append(cfg.name)
            depth = sched.queue.qsize()
            queue_depth += depth
            active_batches += sched.active_batches
            service = snap.get(cfg.name, {}).get("ewma_service_s", 0.0)
            if depth and service > 0:
                wait_s += depth * service / max(1, cfg.instance_count)
        report = LoadReport(
            state=self.health_state(),
            inflight=inflight,
            queue_depth=queue_depth,
            active_batches=active_batches,
            wait_s=wait_s,
            slo_fast_burn=bool(self.slo.fast_burn()),
            models=tuple(sorted(models)),
        )
        self._load_report_cache = (now, report)
        return report

    def profile_snapshot(self, model: str | None = None) -> dict:
        """``GET /v2/profile`` body: per-model/per-bucket efficiency cost
        table (fill ratios, padding-waste device-seconds, compile counts,
        duty cycle) with suggested bucket-ladder tweaks. When the
        autotuner is enabled, suggestions carry ``state``
        (``applied``/``suggested``) and the snapshot gains an
        ``autotune`` section (config, arena layout, recent decisions)."""
        snap = self.profiler.snapshot(model=model)
        # Per-model memory + cache annotations: placement and capacity
        # tooling read reservations from here without loading backends.
        for entry in snap.get("models", {}).values():
            sched = self.scheduler_for(entry["model"], entry["version"])
            if sched is None:
                continue
            backend = sched.model.backend
            hbm = getattr(backend, "hbm_reservation_bytes", None)
            if callable(hbm):
                entry["hbm_bytes"] = int(hbm())
            cache = getattr(backend, "row_cache", None)
            if cache is not None:
                entry["row_cache"] = cache.snapshot()
        if self.autotuner is not None:
            self.autotuner.annotate(snap)
        if self.selfdrive is not None:
            snap["selfdrive"] = self.selfdrive.snapshot()
        rings = self.ring_shm.profile_table()
        if rings:
            snap["shm_rings"] = rings
        datasets = self.staged_shm.profile_table()
        if datasets:
            snap["shm_datasets"] = datasets
        # Census summary: the capacity headline without the full
        # per-device walk detail (that's /v2/memory's job).
        census = self.memory_census()
        snap["memory"] = {
            "bytes_limit": census["totals"].get("bytes_limit", 0),
            "bytes_in_use": census["totals"].get("bytes_in_use", 0),
            "committed_bytes": census["totals"]["committed_bytes"],
            "attributed_bytes": census["attributed_bytes"],
            "unattributed_bytes": census["unattributed_bytes"],
            "attributed_fraction": census["attributed_fraction"],
            "watermark_bytes": census["watermark_bytes"],
            "owners": census["owners"],
        }
        return snap

    # -- trace (device profiling) --------------------------------------------

    def trace_setting(self) -> dict:
        return self.trace.setting()

    def update_trace_setting(self, d: dict) -> dict:
        return self.trace.update(d or {})

    # -- trace (per-request spans) -------------------------------------------

    def request_trace_export(self, trace_id: str | None = None) -> dict:
        """Chrome trace-event JSON of recently completed traced requests
        (``GET /v2/trace/requests``); optionally filtered to one trace id."""
        return self.request_traces.to_chrome_trace(trace_id)

    # -- lifecycle -----------------------------------------------------------

    def shutdown(self) -> None:
        if self._live:
            self.events.emit("lifecycle", "server_shutdown",
                             draining=self._draining)
        self._live = False
        if getattr(self, "blackbox", None) is not None:
            # First: unsubscribe from the journal before the state the
            # capture thread snapshots starts being torn down.
            self.blackbox.close()
        if getattr(self, "qos", None) is not None:
            self.qos.stop_governor()
        if getattr(self, "recorder", None) is not None:
            self.recorder.detach(self)
        if getattr(self, "selfdrive", None) is not None:
            self.selfdrive.stop()
        if getattr(self, "autotuner", None) is not None:
            self.autotuner.stop()
        if getattr(self, "trace", None) is not None:
            self.trace.shutdown()
        with self._lock:
            scheds = list(self._schedulers.values())
            self._schedulers.clear()
        for s in scheds:
            s.stop()
        # regions are released only after in-flight work drains, so requests
        # with shm-placed outputs can still complete during shutdown
        if self.system_shm is not None:
            self.system_shm.unregister(None)
        if self.tpu_shm is not None:
            self.tpu_shm.unregister(None)
        if getattr(self, "ring_shm", None) is not None:
            # shutdown() (not unregister): the reaper thread must stop
            # before the segments unmap beneath it.
            self.ring_shm.shutdown()
        if getattr(self, "staged_shm", None) is not None:
            self.staged_shm.unregister(None)
