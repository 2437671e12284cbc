"""Benchmark entry point — prints ONE JSON line with the headline metric.

Headline: in-process engine throughput (infer/sec) on the `simple` INT32[16]
add/sub conformance model with dynamic batching (max batch 256) at client
concurrency 256 — the C-API-style no-network path (reference
perf_analyzer's TRITON_C_API mode, SURVEY.md §3.5). Also measures flagship BERT-base batch-8 step time and MFU
(achieved FLOP/s vs. chip peak) so "actually fast" has a denominator.

All progress goes to stderr: backend-init seconds, per-bucket compile times,
phase transitions. The JSON line on stdout is the only stdout output.

Measurement discipline (round-3 fix): the worker pool is started and fully
ramped BEFORE the first measurement window opens, then consecutive
fixed-length windows run until three in a row agree within ±10% on BOTH
infer/sec and p99 latency — the reference's stability criterion
(/root/reference/src/c++/perf_analyzer/inference_profiler.cc:503-547), not
best-of-N. The reported value is the mean of the stable triple and the
full per-window series is emitted so the spread is auditable.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import signal
import sys
import threading
import time

_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_RUN_TS = time.time()
_HIST_LOCK = threading.Lock()
_HIST_CTX: dict = {}  # platform/config tags stamped on every probe record


def _hist_path() -> str:
    # BENCH_HISTORY_PATH lets tests (and ad-hoc sweeps) run the bench
    # without appending to the repo's real evidence file.
    return os.environ.get("BENCH_HISTORY_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.json")


def _append_history(entry: dict) -> None:
    """Append one record to BENCH_HISTORY.json the moment a probe finishes.

    Round-5 fix (VERDICT r4 weak #2): history used to be written only at the
    very end of a full run, so a hang anywhere (round 4 lost a whole run
    to one) lost every already-completed probe's evidence.  Each probe now
    persists independently; records carry ``probe``, ``run_ts`` (groups one
    run's records), and the platform/config tags that gate vs_baseline."""
    path = _hist_path()
    entry = dict(entry)
    entry.setdefault("ts", time.time())
    entry.setdefault("run_ts", _RUN_TS)
    for k, v in _HIST_CTX.items():
        entry.setdefault(k, v)
    with _HIST_LOCK:
        try:
            with open(path) as f:
                hist = json.load(f)
            if not isinstance(hist, list):
                hist = []
        except Exception:  # noqa: BLE001 — first run
            hist = []
        hist.append(entry)
        try:
            with open(path, "w") as f:
                json.dump(hist, f, indent=1)
        except OSError:
            pass


_SECTION_NAMES = ("simple", "gen_net", "seq_streaming", "ssd_net",
                  "router", "autotune", "dlrm", "bert", "shm_ab",
                  "shm_ab_large", "shm_ring", "shm_fanin", "gauntlet",
                  "selfdriving", "seq", "gen", "device_steady")


def _sections_filter() -> set | None:
    """Parsed BENCH_SECTIONS (None = no filter).  Unknown names are a hard
    error: a typo must not silently spend chip time running nothing and
    exiting 0."""
    only = os.environ.get("BENCH_SECTIONS", "").strip()
    if not only:
        return None
    names = {s.strip() for s in only.split(",") if s.strip()}
    unknown = names - set(_SECTION_NAMES)
    if unknown or not names:
        what = (f"unknown section(s) {sorted(unknown)}" if unknown
                else "no section names parsed")
        raise SystemExit(f"BENCH_SECTIONS: {what}; "
                         f"valid: {', '.join(_SECTION_NAMES)}")
    return names


def _sections_tag() -> str:
    """Canonical string form of the filter for emits/history — one spelling
    regardless of the whitespace in the raw env value."""
    names = _sections_filter()
    return ",".join(n for n in _SECTION_NAMES if n in names) if names else ""


def _want(section: str) -> bool:
    """Section filter for targeted re-captures: BENCH_SECTIONS=gen_net,seq
    runs only the named sections (all run when unset).  Chip time is
    budgeted — a short call should be spendable on exactly the sections
    that still lack artifacts rather than a full run."""
    names = _sections_filter()
    return names is None or section in names


def _maybe_hang(section: str) -> None:
    """Test knob: BENCH_SIMULATE_HANG=<section> blocks forever at that
    section's entry, standing in for a device hang mid-run so the
    watchdog's partial emit can be exercised in CI (VERDICT r4 #7)."""
    if os.environ.get("BENCH_SIMULATE_HANG") == section:
        log(f"SIMULATING device hang at section {section!r} "
            "(BENCH_SIMULATE_HANG)")
        threading.Event().wait()


class _SectionTimeout(BaseException):
    """A bench section exceeded BENCH_SECTION_DEADLINE_S (device hang).

    BaseException, not Exception: probes have their own internal
    `except Exception` fault isolation (per-model, per-sweep-point), and
    the deadline must cut through those — observed otherwise the alarm
    gets swallowed by an inner handler and the section runs on unbounded
    with no alarm armed."""


class _NotRun(Exception):
    """A section that cannot run in this process layout (not a failure of
    the code under test): recorded with its reason under
    ``sections_not_run`` and counted as not run."""


# Sections whose probe raised (timeout or error) this run — carried on the
# final emit as `sections_failed` so a capture with a dead probe can never
# pass for a complete one (and the run exits nonzero).
_FAILED: list = []


def _note_failure(section: str, exc: BaseException) -> None:
    _FAILED.append(section)
    log(f"section {section!r} failed: {exc!r}")


@contextlib.contextmanager
def _section_guard(section: str):
    """Per-section deadline: a stall inside ONE probe must cost that
    probe, not the rest of the run (observed round 5: a device stall
    during gen_net's engine warmup hung a 40-minute capture that
    seq_streaming/ssd_net could have used — device waits raise no
    exception, so the per-section try/except alone cannot catch them).  SIGALRM aborts the section with _SectionTimeout,
    which the section's existing failure handling records, and the run
    moves on.  Sections run on the main thread; elsewhere (or with the
    knob set to 0) the guard is just the hang-simulation entry hook.
    Default 600s: above every section's honest worst case, far under the
    run watchdog (BENCH_DEADLINE_S, 1500s).

    Boundary condition, stated plainly: the handler can only raise when
    the main thread re-enters the bytecode eval loop (PEP 475), so the
    guard covers waits that poll or retry through Python — the shape of
    the round-5 hang (main thread in a nanosleep poll loop, per /proc
    wchan) and of every subprocess/sleep/lock wait in the sections.  A wait pinned
    inside a C call that never yields would ride through the alarm; the
    run-level watchdog (BENCH_DEADLINE_S) remains the backstop for that
    shape, exactly as before this guard existed."""
    secs = float(os.environ.get("BENCH_SECTION_DEADLINE_S", "600"))
    if secs <= 0 or threading.current_thread() is not threading.main_thread():
        _maybe_hang(section)
        yield
        return

    def _on_alarm(signum, frame):
        # Re-arm a grace alarm BEFORE raising: the timeout unwinds through
        # the probe's own cleanup (`finally: engine.shutdown()` etc.), and
        # on a hung device that cleanup can block in a Python-level wait
        # too — each grace firing cuts through it again until the guard's
        # finally disarms for good.
        signal.alarm(60)
        raise _SectionTimeout(
            f"section {section!r} exceeded {secs:.0f}s (device hang?)")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    # ceil, not int(): a sub-second knob value must not truncate to
    # alarm(0) == "no alarm armed".
    signal.alarm(max(1, math.ceil(secs)))
    try:
        # Inside the armed window: simulated hangs must be bounded the same
        # way real ones are (the CI test for this guard relies on it).
        _maybe_hang(section)
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# Rough worst-case section durations on the TPU (seconds), as last
# captured (2026-07-30) — feeds ONLY the time-budget skip in _run_section.  In-process sections
# calibrated from the r05 TPU capture's per-probe history timestamps
# (artifacts/r05/BENCH_HISTORY_snapshot.json: simple+preflight ~106s,
# bert 32s pre-feedback-scan, shm_ab 99s, shm_ab_large 125s, seq 7s, gen
# 92s, device_steady 379s) plus ~50% margin; net sections from the CPU
# verify drive, padded.
_SECTION_EST = {"simple": 150, "bert": 180, "shm_ab": 150,
                "shm_ab_large": 180, "shm_ring": 200,
                # two replay-fleet phases + two stable-load phases, plus
                # producer-subprocess startup x (1 + 3*producers)
                "shm_fanin": 220,
                # two engine builds (4 models each incl. gpt+dlrm
                # compiles) + four scenario phases + governor recovery
                # wait; flash retries up to 3 flood rounds
                "gauntlet": 300,
                # two engine builds + three closed-loop phases, each
                # bounded by a journal-edge wait (retune ~8s, burn
                # fire+clear ~15s, drift flag needs a full median
                # window of skew before the rebalance lands)
                "selfdriving": 240, "seq": 90, "gen": 150,
                "device_steady": 550, "gen_net": 400,
                "seq_streaming": 350, "ssd_net": 450,
                # two engine builds + two short load phases + promotion
                # wait; TPU pays two warmup compiles of the max bucket
                "autotune": 120,
                # two subprocess replica boots (~engine build each) plus
                # two stable-load phases through the router
                "router": 300}
_RUN_T0 = time.monotonic()


def _run_section(section: str, probe, record):
    """Run one bench section.  ``probe`` (no-arg) executes under the
    per-section deadline; ``record`` (result -> None) runs after the
    alarm is disarmed, so a deadline firing at a section's tail can
    never split a measured result from its _RESULT/history record — the
    two land together or the section counts as failed.  Failures
    (timeout or error) are noted centrally and the run continues.
    Returns the probe result, or None if filtered out, skipped, or
    failed.

    Time-budget skip (full runs only): with all ten sections live, a full
    TPU run can honestly outlast the watchdog (BENCH_DEADLINE_S), which
    would convert a healthy run into a partial-outage emit at the finish
    line.  If starting a section would plausibly cross the watchdog, the
    section is skipped and listed in `sections_skipped` — a clean,
    self-describing truncation instead of a partial.  Filtered runs
    (BENCH_SECTIONS) always attempt exactly what was asked."""
    if not _want(section):
        return None
    # The headline is never budget-skipped: it runs first (elapsed ~0), and
    # a deadline too short even for it means the run cannot exist at all —
    # better to attempt it and let the watchdog adjudicate.
    if section != "simple" and _sections_filter() is None:
        deadline = float(os.environ.get("BENCH_DEADLINE_S", "1500"))
        elapsed = time.monotonic() - _RUN_T0
        est = _SECTION_EST.get(section, 300)
        if elapsed + est > deadline - 90:
            _RESULT.setdefault("sections_skipped", []).append(section)
            log(f"section {section!r} skipped: time budget ({elapsed:.0f}s "
                f"elapsed + ~{est}s estimate would cross the "
                f"{deadline:.0f}s watchdog)")
            return None
    t0 = time.monotonic()
    try:
        with _section_guard(section):
            res = probe()
    except _NotRun as exc:
        _RESULT.setdefault("sections_not_run", {})[section] = str(exc)
        log(f"section {section!r} not run: {exc}")
        return None
    except (Exception, _SectionTimeout) as exc:  # noqa: BLE001 — later
        # sections still run
        _note_failure(section, exc)
        return None
    finally:
        # Per-section wall time rides every emit (including partials — the
        # watchdog copies _RESULT) so full-run duration budgeting against
        # the watchdog window is data, not guesswork.
        _RESULT.setdefault("section_s", {})[section] = round(
            time.monotonic() - t0, 1)
    try:
        record(res)
    except Exception as exc:  # noqa: BLE001 — a recorder bug (bad key,
        # unserializable value) costs this section, not the rest of the
        # run's chip time
        _note_failure(section, exc)
        return None
    return res


def peak_flops() -> float | None:
    """bf16 peak FLOP/s per chip: BENCH_PEAK_FLOPS override, else the
    running device's ``device_kind`` looked up in the shared peak-spec
    registry (client_tpu.observability.roofline — one table for bench,
    the serving profiler, and tools/mfu_diag.py).  None only off-TPU: an
    unlisted TPU kind already failed engine start-up."""
    env = os.environ.get("BENCH_PEAK_FLOPS")
    if env:
        return float(env)
    from client_tpu.observability.roofline import resolve_peaks

    spec = resolve_peaks()
    return spec.flops_per_s if spec else None


def _backend_init_abort(reason: str) -> None:
    """Fail FAST and LOUD on a backend-init outage (round-6 fix: rounds 4
    and 5 each recorded a hollow ``status:"unavailable"`` run that then
    sat in the baseline history looking like data). The emitted record
    says ``backend_init_error`` — unambiguous: no measurement happened —
    and the process exits nonzero so a driver cannot file the run as a
    green result. bench_summary skips these records entirely."""
    log(f"preflight: {reason} — emitting status=backend_init_error "
        "(no measurement happened; this is an outage, not a perf result)")
    _RESULT.update({
        "metric": "inproc_simple_ips", "value": 0.0, "unit": "infer/sec",
        "status": "backend_init_error", "reason": reason})
    _append_history({"probe": "run-status", "status": "backend_init_error",
                     "reason": reason})
    _emit(_RESULT)
    os._exit(3)


def preflight():
    """Bounded, logged backend init (round-5 fix: round 4's driver capture
    spent its entire 1500s watchdog window in "JAX backend still
    initializing" with the device unreachable and reported value 0.0 — which
    reads as a perf collapse, not an outage).  Init runs on a helper
    thread with a hard deadline (BENCH_INIT_DEADLINE_S, default 120s); on
    expiry OR an init exception the bench aborts through
    :func:`_backend_init_abort` — a clear diagnostic and a nonzero exit,
    never a hollow run recorded as if it were a measurement."""
    deadline_s = float(os.environ.get("BENCH_INIT_DEADLINE_S", "120"))
    log(f"preflight: initializing JAX backend "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', 'auto')}, "
        f"deadline {deadline_s:.0f}s)...")
    box: dict = {}

    def _init():
        try:
            if os.environ.get("BENCH_SIMULATE_HANG") == "init":
                log("SIMULATING init hang (BENCH_SIMULATE_HANG=init)")
                threading.Event().wait()  # never returns
            from client_tpu.engine.backend_init import (
                ensure_backend,
                init_seconds,
            )

            box["devices"] = ensure_backend()
            box["secs"] = init_seconds()
        except BaseException as exc:  # noqa: BLE001 — reported on caller
            box["error"] = exc

    t = threading.Thread(target=_init, name="bench-init", daemon=True)
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        _backend_init_abort(
            f"JAX backend init exceeded {deadline_s:.0f}s "
            "(device unreachable or held by another process?)")
    if "error" in box:
        exc = box["error"]
        _backend_init_abort(
            f"JAX backend init failed: {type(exc).__name__}: {exc}")
    devices = box["devices"]
    log(f"preflight: backend up in {box['secs']:.1f}s — "
        f"{len(devices)}x {devices[0].platform}")
    return devices


# Headline bench configuration — the history tag in main() derives from
# these, so changing them can never masquerade as a perf delta.
#
# Round-4 saturation sweep under the STABLE criterion, on a host whose
# per-request floor was a ~70 ms round trip to the device (throughput =
# concurrency / RTT until the client side saturates — the reference
# harness likewise sweeps concurrency to find the knee, main.cc:660).
# Sized to a transport that is gone: re-decide on the chip (ROADMAP A).
#   c256: 3148 stable | c384: 4701 unstable | c512: 5634 stable p99 162ms
#   c768: 6558 stable p99 244ms | c1024: collapses (p99 seconds, unstable)
# Instances beyond 10 and max_batch 1024 both degraded (i16: unstable;
# mb1024-i12-c1024: 5150 stable but worse than c768 at i10).
BENCH_MAX_BATCH = 512
BENCH_CONCURRENCY = 768
BENCH_INSTANCES = 10

# Smoke mode (tests/CI): tiny load so a full section finishes in seconds on
# CPU.  The config tag derives from these constants, so a smoke run tags
# itself mb8-c8-i2 and can never enter the real headline's baseline pool.
if os.environ.get("BENCH_SMOKE"):
    BENCH_MAX_BATCH, BENCH_CONCURRENCY, BENCH_INSTANCES = 8, 8, 2


def _tail_is_stable(history: list, keys: tuple, stability_pct: float,
                    stable_needed: int) -> bool:
    """The reference's stability criterion, shared by every windowed probe:
    the last `stable_needed` windows each sit within ±`stability_pct` of
    the tail mean on EVERY key (inference_profiler.cc:503-547).  One
    implementation so a criterion tweak cannot silently fork the contract
    between probes (which is exactly how the seq probe drifted out of the
    round-3 stability adoption)."""
    if len(history) < stable_needed:
        return False
    tail = history[-stable_needed:]
    for k in keys:
        avg = sum(w[k] for w in tail) / stable_needed
        if avg <= 0 or any(abs(w[k] - avg) > stability_pct * avg
                           for w in tail):
            return False
    return True


def run_stable_load(infer_fn, concurrency: int, window_s: float = 3.0,
                    ramp_s: float = 1.5, stability_pct: float = 0.10,
                    stable_needed: int = 3, max_windows: int = 12,
                    tag: str = "load"):
    """Closed-loop load with the reference's stability search.

    Starts `concurrency` persistent workers calling `infer_fn` in a loop,
    discards a ramp period, then measures consecutive `window_s` windows
    until `stable_needed` in a row each sit within ±`stability_pct` of the
    triple's mean on BOTH infer/sec and p99 latency
    (/root/reference/src/c++/perf_analyzer/inference_profiler.cc:503-547).
    Workers outlive every window boundary — no thread start/stop cost is
    ever inside a measured window (the round-2 bench measured its own
    256-thread stampede; reference: ChangeConcurrencyLevel reuses threads,
    concurrency_manager.cc:90-146).

    Returns {ips, p99_us, stable, windows: [{ips, p99_us}...]} where the
    headline pair is the mean of the final `stable_needed` windows.
    """
    stop_evt = threading.Event()
    locks = [threading.Lock() for _ in range(concurrency)]
    lat_buckets: list[list[int]] = [[] for _ in range(concurrency)]
    errs: list[str] = []

    def worker(i):
        try:
            while not stop_evt.is_set():
                t0 = time.monotonic_ns()
                infer_fn()
                dt = time.monotonic_ns() - t0
                with locks[i]:
                    lat_buckets[i].append(dt)
        except Exception as exc:  # noqa: BLE001 — surfaced after join
            errs.append(repr(exc))
            stop_evt.set()

    def swap() -> list[int]:
        taken: list[int] = []
        for i in range(concurrency):
            with locks[i]:
                if lat_buckets[i]:
                    taken.extend(lat_buckets[i])
                    lat_buckets[i] = []
        return taken

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(ramp_s)
    swap()  # discard everything completed during ramp
    history: list[dict] = []
    stable = False
    t_mark = time.monotonic()
    try:
        while len(history) < max_windows and not stop_evt.is_set():
            time.sleep(window_s)
            now = time.monotonic()
            lat = swap()
            elapsed = now - t_mark
            t_mark = now
            lat.sort()
            ips = len(lat) / elapsed
            p99 = lat[int(len(lat) * 0.99) - 1] / 1e3 if lat else 0.0
            history.append({"ips": round(ips, 1), "p99_us": round(p99, 1)})
            log(f"{tag} window {len(history)}: {len(lat)} completions in "
                f"{elapsed:.2f}s = {ips:.1f} ips, p99 {p99 / 1e3:.1f}ms")
            if _tail_is_stable(history, ("ips", "p99_us"),
                               stability_pct, stable_needed):
                stable = True
                break
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=120)
    if errs:
        raise RuntimeError(f"{tag}: worker errors: {errs[:3]}")
    if not history:
        raise RuntimeError(f"{tag}: no measurement windows completed")
    tail = history[-min(stable_needed, len(history)):]
    ips = sum(w["ips"] for w in tail) / len(tail)
    p99 = sum(w["p99_us"] for w in tail) / len(tail)
    if not stable:
        log(f"{tag}: NOT stable after {len(history)} windows "
            f"(reporting mean of final {len(tail)})")
    return {"ips": ips, "p99_us": p99, "stable": stable, "windows": history}


def _fault_profile():
    """Parsed BENCH_FAULT_PROFILE (None = chaos bench disabled).

    Same JSON shape as CLIENT_TPU_FAULTS, e.g.
    ``{"model.execute": {"probability": 0.05, "seed": 7,
    "error_status": 503}}``.  When set, bench_inproc_simple runs its load
    through a RetryPolicy + CircuitBreaker so latency percentiles are
    measured *including* the resilience layer's recovery cost, and the run
    records ``retries`` / ``breaker_open_s`` next to them.
    """
    raw = os.environ.get("BENCH_FAULT_PROFILE", "").strip()
    if not raw:
        return None
    try:
        profile = json.loads(raw)
    except ValueError as exc:
        raise SystemExit(f"BENCH_FAULT_PROFILE: invalid JSON: {exc}")
    if not isinstance(profile, dict) or not profile:
        raise SystemExit("BENCH_FAULT_PROFILE: expected a non-empty JSON "
                         "object keyed by fault site")
    return profile


def bench_inproc_simple(concurrency: int = BENCH_CONCURRENCY):
    import numpy as np

    from client_tpu.engine import InferRequest, TpuEngine
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models.simple import AddSubBackend

    log("building engine (simple model, warmup=True pre-compiles buckets)...")
    t0 = time.monotonic()
    # Bench-owned batching ceiling: every device round trip carries fixed
    # transport latency, so throughput ∝ requests per dispatch. A 256 ceiling
    # with matching client concurrency measured 1476 ips vs 356 at the zoo
    # default 64/32 on the v5e chip (the zoo default stays conservative for
    # interactive latency).
    backend = AddSubBackend(max_batch_size=BENCH_MAX_BATCH)
    backend.config.instance_count = BENCH_INSTANCES
    repo = ModelRepository()
    repo.register_backend(backend)
    engine = TpuEngine(repo, warmup=True)
    log(f"engine ready (load+warmup {time.monotonic() - t0:.1f}s)")

    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)

    def make_req():
        return InferRequest(model_name="simple",
                            inputs={"INPUT0": a, "INPUT1": b})

    log("warmup inferences (8x batch-1 through the full engine path)...")
    t0 = time.monotonic()
    for _ in range(8):
        engine.infer(make_req(), timeout_s=300)
    log(f"warmup done ({time.monotonic() - t0:.1f}s); stability search "
        f"at concurrency {concurrency}")

    # Snapshot the engine's request-duration histogram around the load run:
    # the windowed delta yields server-side p50/p99 that cross-check the
    # client-measured tail (a client-side timer also measures its own
    # thread-scheduling jitter; the histogram doesn't).
    def _hist_snapshot():
        try:
            from client_tpu.observability import scrape

            return scrape.histogram_state(engine.prometheus_metrics(),
                                          "tpu_request_duration_us")
        except Exception as exc:  # noqa: BLE001 — metrics must not sink bench
            log(f"metrics snapshot failed: {exc}")
            return None

    profile = _fault_profile()
    infer_fn = lambda: engine.infer(make_req(), timeout_s=60)  # noqa: E731
    retry_count = [0]
    breaker = None
    if profile is not None:
        from client_tpu import faults
        from client_tpu.resilience import (CircuitBreaker, RetryPolicy,
                                           run_with_resilience)

        faults.configure(profile)
        faults.registry().bind_metrics(engine.metrics.registry)
        policy = RetryPolicy(max_attempts=4, initial_backoff_s=0.002, seed=7)
        breaker = CircuitBreaker(failure_threshold=16, cooldown_s=0.25)
        retry_lock = threading.Lock()

        def _on_retry(n, exc, delay):
            with retry_lock:
                retry_count[0] += 1

        plain_fn = infer_fn

        def infer_fn():  # noqa: F811 — deliberate chaos-mode shadow
            run_with_resilience(lambda remaining_s: plain_fn(),
                                policy=policy, breaker=breaker,
                                host="inproc", on_retry=_on_retry)

        log(f"chaos profile active (BENCH_FAULT_PROFILE): "
            f"{sorted(profile)} — load runs through RetryPolicy"
            f"(max_attempts=4) + CircuitBreaker")

    before = _hist_snapshot()
    try:
        res = run_stable_load(infer_fn, concurrency, tag="simple")
    finally:
        if profile is not None:
            from client_tpu import faults

            faults.reset()
    after = _hist_snapshot()
    if profile is not None:
        res["retries"] = retry_count[0]
        res["breaker_open_s"] = round(breaker.open_seconds_total(), 3)
        log(f"simple: {res['retries']} retries, breaker open "
            f"{res['breaker_open_s']}s under fault profile")
    if before is not None and after is not None:
        from client_tpu.observability import scrape

        d = scrape.delta(after, before)
        if d["count"] > 0:
            res["hist_p50_us"] = round(scrape.quantile(d, 0.50), 1)
            res["hist_p99_us"] = round(scrape.quantile(d, 0.99), 1)
            log(f"simple: histogram-derived p50 {res['hist_p50_us']}us, "
                f"p99 {res['hist_p99_us']}us over {int(d['count'])} requests")
    # Efficiency counters from the always-on profiler: how full the padded
    # batches ran, how much device time padding wasted, and what compiling
    # cost — the context a throughput number needs to be actionable.
    try:
        psnap = engine.profile_snapshot(model="simple")
        pm = next(iter(psnap["models"].values()), None)
        if pm is not None:
            rows = sum(b["rows"] for b in pm["buckets"])
            padded = sum(b["padded_rows"] for b in pm["buckets"])
            res["fill_ratio"] = (round(rows / (rows + padded), 4)
                                 if rows + padded else 1.0)
            res["duty_cycle"] = psnap["duty_cycle"]
            res["xla_compiles"] = pm["compilations"]
            res["pad_waste_device_s"] = round(
                pm["padding_waste_device_s"], 4)
            # Roofline utilization (advisory until a TPU baseline exists:
            # null on hosts with unknown peaks, recorded either way so
            # the efficiency line carries hardware context when it can).
            rl = pm.get("roofline") or {}
            res["mfu"] = rl.get("mfu")
            res["mbu"] = rl.get("mbu")
            log(f"simple: fill_ratio {res['fill_ratio']}, duty_cycle "
                f"{res['duty_cycle']}, {res['xla_compiles']} XLA compiles, "
                f"padding waste {res['pad_waste_device_s']}s device, "
                f"mfu {res['mfu']}, mbu {res['mbu']} "
                f"(bound {rl.get('bound', 'unknown')})")
    except Exception as exc:  # noqa: BLE001 — profiler must not sink bench
        log(f"profiler snapshot unavailable: {exc}")
    # Flight-recorder and HBM-census availability: the run is only
    # observable in production if both surfaces were live during it.
    try:
        res["timeseries_samples"] = len(
            engine.timeseries_export().get("samples", []))
        res["census_attr_fraction"] = engine.memory_census().get(
            "attributed_fraction")
        log(f"simple: {res['timeseries_samples']} flight-recorder samples, "
            f"census attribution {res['census_attr_fraction']}")
    except Exception as exc:  # noqa: BLE001 — observability must not sink bench
        log(f"flight recorder / census unavailable: {exc}")
    if profile is not None:
        # Overload-protection counters + a real graceful drain instead of
        # the abrupt shutdown: chaos runs report what the admission layer
        # shed, what expired, and how long the drain took.
        from client_tpu.admission.drain import drain
        from client_tpu.observability import scrape

        try:
            samples = scrape.parse_samples(engine.prometheus_metrics())
            res["shed_total"] = int(sum(
                v for name, _labels, v in samples
                if name == "tpu_admission_rejections_total"))
            res["deadline_expired_total"] = int(sum(
                v for name, _labels, v in samples
                if name == "tpu_deadline_expirations_total"))
        except Exception as exc:  # noqa: BLE001
            log(f"overload counters unavailable: {exc}")
        report = drain(engine, deadline_s=10.0)
        res["drain_s"] = round(report["drain_s"], 3)
        log(f"simple: shed={res.get('shed_total')} "
            f"deadline_expired={res.get('deadline_expired_total')} "
            f"drain_s={res['drain_s']} (clean={report['clean']})")
    else:
        engine.shutdown()
    return res


def bench_autotune(duration_s: float = 2.0):
    """Before/after proof for the CLIENT_TPU_AUTOTUNE bucket tuner.

    The simple model is loaded with a deliberately MISFIT ladder — only
    the max bucket — and driven with batch-1 traffic, once with the
    tuner off and once with it on.  Off: every execution pads 1 row up
    to ``BENCH_MAX_BATCH`` (fill 1/max, maximal padding waste).  On: the
    background tuner should observe the waste, compile a 1-row bucket
    off the hot path, and promote it, after which the same traffic runs
    at fill 1.0.  The record carries both phases' ``fill_ratio``,
    ``pad_waste_device_s``, and ips plus the promotion count —
    ``bench_summary`` prints the delta."""
    import numpy as np

    from client_tpu.engine import InferRequest, TpuEngine
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models.simple import AddSubBackend
    from client_tpu.observability.profiler import profiler, reset_profiler

    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)

    def phase(tuned: bool) -> dict:
        backend = AddSubBackend(name="autotune_probe",
                                max_batch_size=BENCH_MAX_BATCH)
        backend.config.batch_buckets = [BENCH_MAX_BATCH]  # misfit on purpose
        backend.config.instance_count = 1  # serial: every batch is 1 row
        repo = ModelRepository()
        repo.register_backend(backend)
        prev = os.environ.get("CLIENT_TPU_AUTOTUNE")
        if tuned:
            os.environ["CLIENT_TPU_AUTOTUNE"] = json.dumps(
                {"interval_s": 0.2, "cooldown_s": 0.5})
        else:
            os.environ.pop("CLIENT_TPU_AUTOTUNE", None)
        reset_profiler()
        try:
            engine = TpuEngine(repo, warmup=True)
        finally:
            if prev is None:
                os.environ.pop("CLIENT_TPU_AUTOTUNE", None)
            else:
                os.environ["CLIENT_TPU_AUTOTUNE"] = prev
        try:
            def infer():
                engine.infer(InferRequest(
                    model_name="autotune_probe",
                    inputs={"INPUT0": a, "INPUT1": b}), timeout_s=60)

            # Evidence traffic: enough misfit batches for the tuner's
            # min_calls hysteresis, then (tuned phase) wait for the
            # background thread to journal an applied promotion.
            for _ in range(16):
                infer()
            promotions = 0
            if tuned:
                deadline = time.monotonic() + 15.0
                while time.monotonic() < deadline:
                    snap = engine.profile_snapshot()
                    promotions = sum(
                        1 for d in snap.get("autotune", {}).get(
                            "decisions", [])
                        if d["action"] == "add_bucket" and d["applied"])
                    if promotions:
                        break
                    time.sleep(0.1)
                log(f"autotune phase(on): {promotions} promotion(s) "
                    "observed" if promotions else
                    "autotune phase(on): no promotion within 15s")
            # Measurement epoch: a fresh profiler so warmup/evidence
            # traffic doesn't dilute the measured fill ratio.
            reset_profiler()
            t0 = time.monotonic()
            n = 0
            while time.monotonic() - t0 < duration_s:
                infer()
                n += 1
            elapsed = time.monotonic() - t0
            snap = profiler().snapshot(model="autotune_probe")
            pm = next(iter(snap["models"].values()), None)
            rows = sum(bk["rows"] for bk in pm["buckets"]) if pm else 0
            padded = sum(bk["padded_rows"]
                         for bk in pm["buckets"]) if pm else 0
            sched = engine.scheduler_for("autotune_probe")
            out = {
                "ips": round(n / elapsed, 2),
                "fill_ratio": (round(rows / (rows + padded), 4)
                               if rows + padded else 1.0),
                "pad_waste_device_s": round(
                    pm["padding_waste_device_s"], 6) if pm else 0.0,
                "ladder": sched.bucket_ladder() if sched else [],
            }
            if tuned:
                out["promotions"] = promotions
            return out
        finally:
            engine.shutdown()
            reset_profiler()

    log("autotune probe: tuner OFF phase (misfit ladder "
        f"[{BENCH_MAX_BATCH}], batch-1 traffic)...")
    off = phase(tuned=False)
    log(f"autotune off: {off}")
    log("autotune probe: tuner ON phase (CLIENT_TPU_AUTOTUNE, "
        "interval 0.2s)...")
    on = phase(tuned=True)
    log(f"autotune on: {on}")
    return {
        "off": off, "on": on,
        "promotions": on.get("promotions", 0),
        "delta": {
            "fill_ratio": round(on["fill_ratio"] - off["fill_ratio"], 4),
            "pad_waste_device_s": round(
                on["pad_waste_device_s"] - off["pad_waste_device_s"], 6),
            "ips": round(on["ips"] - off["ips"], 2),
        },
    }


def bench_dlrm(window_s: float = 2.0):
    """DLRM ragged-lookup probe: Zipf-skewed CSR bags through the
    lookups-axis scheduler, three configurations of one fixed-seed model:

    - ``device`` — device-resident tables (uncached): the ips/p99
      headline, plus the lookup-bucket fill ratio (nnz / padded bucket);
    - ``cached`` — host tables behind the hot-row LRU
      (``engine/rowcache.py``): Zipf traffic concentrates on a small hot
      set, so the recorded ``cache_hit_rate`` should be well above zero;
    - ``sharded`` — 4-way row-sharded tables, recorded as a
      bit-identical parity bit against the device oracle rather than
      timed (off-TPU the shard_map runs interpreted; timing it measures
      the interpreter, not the serving path).
    """
    import numpy as np

    from client_tpu.engine import InferRequest, TpuEngine
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models.dlrm import DlrmBackend
    from client_tpu.observability.profiler import reset_profiler

    TABLE_ROWS, TABLES, SEED = 256, 4, 13
    rng = np.random.default_rng(SEED)

    def zipf_csr():
        counts = rng.integers(1, 9, size=TABLES)
        nnz = int(counts.sum())
        # Zipf-skewed row ids: a few hot rows absorb most lookups, the
        # DLRM serving traffic shape the hot-row cache exists for.
        idx = ((rng.zipf(1.3, size=nnz) - 1) % TABLE_ROWS).astype(np.int32)
        off = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
        dense = rng.standard_normal((1, 8)).astype(np.float32)
        return {"DENSE": dense, "INDICES": idx, "OFFSETS": off}

    pool = [zipf_csr() for _ in range(64)]

    def phase(tag: str, **backend_kw) -> dict:
        backend = DlrmBackend(name="dlrm_bench", table_rows=TABLE_ROWS,
                              seed=SEED, max_lookups=256, **backend_kw)
        repo = ModelRepository()
        repo.register_backend(backend)
        reset_profiler()
        engine = TpuEngine(repo, warmup=True)
        try:
            cursor = [0]
            lock = threading.Lock()

            def infer():
                with lock:
                    i = cursor[0]
                    cursor[0] += 1
                engine.infer(InferRequest(
                    model_name="dlrm_bench",
                    inputs=dict(pool[i % len(pool)])), timeout_s=60)

            res = run_stable_load(infer, concurrency=4,
                                  window_s=window_s, tag=f"dlrm-{tag}")
            psnap = engine.profile_snapshot(model="dlrm_bench")
            pm = next(iter(psnap["models"].values()), None)
            if pm is not None:
                # "rows" on a lookups-axis model counts lookups; fill is
                # real nnz over padded bucket slots.
                nnz = sum(b["rows"] for b in pm["buckets"])
                padded = sum(b["padded_rows"] for b in pm["buckets"])
                res["fill_ratio"] = (round(nnz / (nnz + padded), 4)
                                     if nnz + padded else 1.0)
                res["lookup_buckets"] = [b["bucket"] for b in pm["buckets"]
                                         if b["executions"]]
                # Embedding-bag buckets lower to gathers, so expect the
                # cost model to price ~0 flops and the story to be MBU:
                # record both, advisory (null when peaks are unknown).
                rl = pm.get("roofline") or {}
                res["mfu"] = rl.get("mfu")
                res["mbu"] = rl.get("mbu")
            if backend.row_cache is not None:
                res["cache_hit_rate"] = round(
                    backend.row_cache.hit_rate(), 4)
                res["cache"] = backend.row_cache.snapshot()
            return res
        finally:
            engine.shutdown()
            reset_profiler()

    def sharded_parity():
        import jax

        if len(jax.devices()) < 4:
            return None
        from client_tpu.engine.model import Model

        kw = dict(table_rows=TABLE_ROWS, seed=SEED, max_lookups=256)
        oracle = Model(DlrmBackend(name="dlrm_oracle", **kw), jit=True)
        shard = Model(DlrmBackend(name="dlrm_shard", emb_shards=4, **kw),
                      jit=True)
        inputs = pool[0]
        nnz = int(inputs["INDICES"].shape[0])
        o0, _ = oracle.execute_timed(dict(inputs), batch_size=nnz)
        o1, _ = shard.execute_timed(dict(inputs), batch_size=nnz)
        return bool(np.array_equal(o0["OUTPUT0"], o1["OUTPUT0"]))

    log("dlrm probe: device-table phase (Zipf CSR, uncached)...")
    device = phase("device")
    log(f"dlrm device: {device['ips']} infer/s, p99 {device['p99_us']}us, "
        f"lookup fill {device.get('fill_ratio')}")
    log("dlrm probe: host-table + hot-row cache phase...")
    cached = phase("cached", host_tables=True, cache_budget_bytes=1 << 13)
    log(f"dlrm cached: {cached['ips']} infer/s, cache hit rate "
        f"{cached.get('cache_hit_rate')}")
    parity = sharded_parity()
    log(f"dlrm sharded-vs-oracle bit-identical: {parity}")
    return {
        "ips": device["ips"],
        "p99_us": device["p99_us"],
        "fill_ratio": device.get("fill_ratio"),
        "cache_hit_rate": cached.get("cache_hit_rate"),
        "sharded_parity": parity,
        "device": device,
        "cached": cached,
    }


def _shm_ab_modes(engine, model_name: str, inputs: dict, output_specs: dict,
                  concurrency: int, tag: str, window_s: float = 2.5):
    """Run the four-data-plane A/B against one engine/model: same entry
    point (capi_embed.infer, what libtpuserver.so binds), same concurrency,
    varying ONLY how tensors travel:

    - ``none``   — tensors inline in the request (wire-parity payload)
    - ``system`` — POSIX system shm regions, register-by-key
    - ``tpu``    — host-staged TPU regions, register-by-handle (the
      cross-process contract, engine/shm.py:17-29)
    - ``device`` — in-process device-resident HBM regions (true zero-copy:
      inputs live in HBM, outputs stay there; the scheduler skips the D2H
      fetch for these batches)

    `inputs`: name -> np array (batch-1 row); `output_specs`: name -> nbytes.
    This is the apples-to-apples table the reference's cudashm plane exists
    to win (load_manager.cc:287-446).
    """
    import numpy as np

    from client_tpu import capi_embed
    from client_tpu.protocol.dtypes import np_to_wire_dtype
    from client_tpu.utils import shared_memory as sshm
    from client_tpu.utils import tpu_shared_memory as tshm

    def req_json(in_regions=None, out_regions=None):
        ins = []
        for name, arr in inputs.items():
            d = {"name": name, "datatype": np_to_wire_dtype(arr.dtype),
                 "shape": list(arr.shape)}
            if in_regions:
                d["parameters"] = {
                    "shared_memory_region": in_regions[name],
                    "shared_memory_byte_size": arr.nbytes}
            ins.append(d)
        outs = []
        for name, nbytes in output_specs.items():
            d = {"name": name}
            if out_regions:
                d["parameters"] = {
                    "shared_memory_region": out_regions[name],
                    "shared_memory_byte_size": nbytes}
            outs.append(d)
        return json.dumps(
            {"model_name": model_name, "inputs": ins, "outputs": outs})

    results: dict[str, dict] = {}
    sys_regions: list = []
    tpu_regions: list = []
    try:
        # -- none: inline tensors ------------------------------------------
        raws = [arr.tobytes() for arr in inputs.values()]
        req_none = req_json()

        def infer_none():
            capi_embed.infer(engine, req_none, [memoryview(r) for r in raws])

        # -- system shm ----------------------------------------------------
        in_r, out_r = {}, {}
        for name, arr in inputs.items():
            key = f"{tag}_sys_{name}"
            r = sshm.create_shared_memory_region(key, key, arr.nbytes)
            sshm.set_shared_memory_region(r, [arr])
            capi_embed.register_system_shm(engine, key, key, arr.nbytes)
            sys_regions.append(r)
            in_r[name] = key
        for name, nbytes in output_specs.items():
            key = f"{tag}_sys_{name}"
            r = sshm.create_shared_memory_region(key, key, nbytes)
            capi_embed.register_system_shm(engine, key, key, nbytes)
            sys_regions.append(r)
            out_r[name] = key
        req_sys = req_json(in_r, out_r)

        def infer_system():
            capi_embed.infer(engine, req_sys, [None] * len(inputs))

        # -- tpu (host-staged handle) --------------------------------------
        in_r, out_r = {}, {}
        for name, arr in inputs.items():
            key = f"{tag}_tpu_{name}"
            r = tshm.create_shared_memory_region(key, arr.nbytes)
            tshm.set_shared_memory_region(r, [arr])
            capi_embed.register_tpu_shm(engine, key, tshm.get_raw_handle(r),
                                        0, arr.nbytes)
            tpu_regions.append(r)
            in_r[name] = key
        for name, nbytes in output_specs.items():
            key = f"{tag}_tpu_{name}"
            r = tshm.create_shared_memory_region(key, nbytes)
            capi_embed.register_tpu_shm(engine, key, tshm.get_raw_handle(r),
                                        0, nbytes)
            tpu_regions.append(r)
            out_r[name] = key
        req_tpu = req_json(in_r, out_r)

        def infer_tpu():
            capi_embed.infer(engine, req_tpu, [None] * len(inputs))

        # -- device-resident HBM regions (in-process zero-copy) ------------
        import jax

        in_r, out_r = {}, {}
        for name, arr in inputs.items():
            key = f"{tag}_dev_{name}"
            engine.tpu_shm.register_device_array(key, jax.device_put(arr))
            in_r[name] = key
        for name, nbytes in output_specs.items():
            key = f"{tag}_dev_{name}"
            engine.tpu_shm.register_device_array(
                key, jax.device_put(np.zeros(nbytes, np.uint8)))
            out_r[name] = key
        req_dev = req_json(in_r, out_r)

        def infer_device():
            capi_embed.infer(engine, req_dev, [None] * len(inputs))

        def warm_mode(fn):
            # Concurrent bursts of every power-of-two size up to the
            # measured concurrency: drives each wave bucket through the
            # scheduler so no XLA compile (batch apply OR device-concat)
            # lands inside a measurement window.
            k = 1
            while True:
                ts = [threading.Thread(target=fn) for _ in range(k)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if k >= concurrency:
                    break
                k = min(k * 2, concurrency)

        modes = [("none", infer_none), ("system", infer_system),
                 ("tpu", infer_tpu), ("device", infer_device)]
        for mode, fn in modes:
            warm_mode(fn)
            res = run_stable_load(fn, concurrency, window_s=window_s,
                                  max_windows=10, tag=f"{tag}-{mode}")
            results[mode] = {"ips": round(res["ips"], 1),
                             "p99_us": round(res["p99_us"], 1),
                             "stable": res["stable"]}
            log(f"{tag} A/B [{mode}]: {res['ips']:.1f} ips "
                f"p99 {res['p99_us'] / 1e3:.1f}ms at concurrency "
                f"{concurrency}")
        return results
    finally:
        for r in sys_regions:
            try:
                sshm.destroy_shared_memory_region(r)
            except Exception:  # noqa: BLE001
                pass
        for r in tpu_regions:
            try:
                tshm.destroy_shared_memory_region(r)
            except Exception:  # noqa: BLE001
                pass


def bench_shm_ab(concurrency: int = 64):
    """Data-plane A/B on `simple` (BASELINE.json config 2 — the cudashm
    add/sub client): 64 B tensors, so this measures per-request data-plane
    OVERHEAD; bench_shm_ab_large is where the planes earn their keep."""
    import numpy as np

    from client_tpu import capi_embed

    engine = capi_embed.create_engine("simple")
    try:
        return _shm_ab_modes(
            engine, "simple",
            inputs={"INPUT0": np.arange(16, dtype=np.int32).reshape(1, 16),
                    "INPUT1": np.ones((1, 16), dtype=np.int32)},
            output_specs={"OUTPUT0": 64, "OUTPUT1": 64},
            concurrency=concurrency, tag="shm")
    finally:
        capi_embed.shutdown_engine(engine)


def bench_shm_ab_large(concurrency: int = 16, dim: int = 150528):
    """Data-plane A/B where transfer dominates: ~602 KB FP32 per request
    through a passthrough model (the reference's cudashm demos move image
    tensors for the same reason — simple_grpc_cudashm_client.cc exists to
    show region I/O beating inline bytes). The `device` column is the
    north-star plane: inputs already in HBM, outputs kept there, zero host
    tensor bytes end to end."""
    import numpy as np

    from client_tpu.engine import TpuEngine
    from client_tpu.engine.scheduler import power_buckets
    from client_tpu.engine.config import (
        DynamicBatchingConfig,
        ModelConfig,
        TensorConfig,
    )
    from client_tpu.engine.model import ModelBackend
    from client_tpu.engine.repository import ModelRepository

    class BigIdentity(ModelBackend):
        def __init__(self):
            self.config = ModelConfig(
                name="big_identity", platform="jax",
                max_batch_size=concurrency,
                input=[TensorConfig("INPUT", "FP32", [dim])],
                output=[TensorConfig("OUTPUT", "FP32", [dim])],
                dynamic_batching=DynamicBatchingConfig(
                    preferred_batch_size=[concurrency],
                    max_queue_delay_microseconds=200),
                batch_buckets=power_buckets(concurrency),
                instance_count=4,
            )

        def make_apply(self):
            def apply(inputs):
                return {"OUTPUT": inputs["INPUT"] + 1.0}
            return apply

    repo = ModelRepository()
    repo.register_backend(BigIdentity())
    engine = TpuEngine(repo, warmup=True)
    try:
        rng = np.random.default_rng(0)
        arr = rng.random((1, dim), dtype=np.float32)
        return _shm_ab_modes(
            engine, "big_identity",
            inputs={"INPUT": arr},
            output_specs={"OUTPUT": arr.nbytes},
            concurrency=concurrency, tag="shmL")
    finally:
        engine.shutdown()


def bench_shm_ring(lanes: int = 4, span: int = 8, dim: int = 150528):
    """Zero-copy shm ring vs binary HTTP on a vision-sized payload
    (~602 KB FP32 per request): one co-located server, one passthrough
    model, varying ONLY the data plane.  The HTTP side pays one POST with
    the tensor inline per request; the ring side stages `span` requests
    into /dev/shm slots, rings ONE doorbell for the whole span, and polls
    the slot state words for completions — no response round trip at all.
    `lanes` SPSC rings run concurrently (slot order is per-ring, so
    parallelism comes from lanes, like independent co-located clients);
    both planes run the same max in-flight (lanes * span).

    Returns {http: {ips, p99_us, stable}, ring: {ips, p99_us, stable,
    occupancy_mean, windows}, ring_vs_http_ips, fill_ratio, duty_cycle,
    ring_rows}.
    """
    import numpy as np

    import client_tpu.http as httpclient
    from client_tpu.engine import TpuEngine
    from client_tpu.engine.config import (
        DynamicBatchingConfig,
        ModelConfig,
        TensorConfig,
    )
    from client_tpu.engine.model import ModelBackend
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.engine.scheduler import power_buckets
    from client_tpu.server import HttpInferenceServer
    from client_tpu.utils.shm_ring import RingProducer

    if os.environ.get("BENCH_SMOKE"):
        lanes, span, dim = 2, 4, 4096
    conc = lanes * span  # equal max in-flight on both planes

    class RingIdentity(ModelBackend):
        def __init__(self):
            self.config = ModelConfig(
                name="ring_identity", platform="jax",
                max_batch_size=conc,
                input=[TensorConfig("INPUT", "FP32", [dim])],
                output=[TensorConfig("OUTPUT", "FP32", [dim])],
                dynamic_batching=DynamicBatchingConfig(
                    preferred_batch_size=[conc],
                    max_queue_delay_microseconds=200),
                batch_buckets=power_buckets(conc),
                instance_count=4,
            )

        def make_apply(self):
            def apply(inputs):
                return {"OUTPUT": inputs["INPUT"] + 1.0}
            return apply

    repo = ModelRepository()
    repo.register_backend(RingIdentity())
    engine = TpuEngine(repo, warmup=True)
    srv = HttpInferenceServer(engine, port=0).start()
    rng = np.random.default_rng(0)
    arr = rng.random((1, dim), dtype=np.float32)
    out: dict = {}
    try:
        # -- binary HTTP: tensor bytes inline on the wire, one POST per
        # request — what a co-located client pays without the ring.
        client = httpclient.InferenceServerClient(srv.url, concurrency=conc)
        inp = httpclient.InferInput("INPUT", [1, dim], "FP32")
        inp.set_data_from_numpy(arr)

        def infer_http():
            client.infer("ring_identity", [inp])

        try:
            # Bursts of every power-of-two size up to the measured
            # concurrency so no wave-bucket XLA compile lands inside a
            # measurement window (same rationale as _shm_ab_modes).
            k = 1
            while True:
                ts = [threading.Thread(target=infer_http) for _ in range(k)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if k >= conc:
                    break
                k = min(k * 2, conc)
            res = run_stable_load(infer_http, conc, window_s=2.5,
                                  max_windows=10, tag="ring-http")
        finally:
            client.close()
        out["http"] = {"ips": round(res["ips"], 1),
                       "p99_us": round(res["p99_us"], 1),
                       "stable": res["stable"]}

        # -- shm ring: each lane fills a span of slots, rings one doorbell,
        # then reaps completions straight out of shm.  Per-request latency
        # is fill-to-reap (reap order == fill order on an SPSC ring).
        stop_evt = threading.Event()
        locks = [threading.Lock() for _ in range(lanes)]
        lat_buckets: list[list[int]] = [[] for _ in range(lanes)]
        occ_sum = [0] * lanes
        occ_n = [0] * lanes
        errs: list[str] = []

        def lane(i):
            # slot_count = 2*span keeps a span cooking server-side while
            # this thread reaps the previous one — fill/doorbell/reap
            # overlap instead of draining the ring to empty each cycle.
            lane_client = httpclient.InferenceServerClient(srv.url)
            try:
                with RingProducer(lane_client, f"bench_ring{i}",
                                  f"/bench_ring{i}", slot_count=2 * span,
                                  slot_bytes=arr.nbytes) as prod:
                    import collections
                    fill_ts: collections.deque = collections.deque()
                    while not stop_evt.is_set():
                        while prod.fill({"INPUT": arr}) is not None:
                            fill_ts.append(time.monotonic_ns())
                        prod.doorbell("ring_identity")
                        occ_sum[i] += prod.outstanding
                        occ_n[i] += 1
                        for _ in range(span):
                            slot, _outs, err = prod.reap(timeout_s=120,
                                                         copy=False)
                            if err is not None:
                                raise RuntimeError(
                                    f"lane {i} slot {slot}: {err}")
                            dt = time.monotonic_ns() - fill_ts.popleft()
                            with locks[i]:
                                lat_buckets[i].append(dt)
                    # Drain what is still in flight so __exit__ never
                    # detaches a ring the server is mid-write on.
                    while prod.outstanding > prod.pending:
                        prod.reap(timeout_s=120, copy=False)
            except Exception as exc:  # noqa: BLE001 — surfaced after join
                errs.append(repr(exc))
                stop_evt.set()
            finally:
                lane_client.close()

        def swap() -> list[int]:
            taken: list[int] = []
            for i in range(lanes):
                with locks[i]:
                    if lat_buckets[i]:
                        taken.extend(lat_buckets[i])
                        lat_buckets[i] = []
            return taken

        threads = [threading.Thread(target=lane, args=(i,), daemon=True)
                   for i in range(lanes)]
        for t in threads:
            t.start()
        time.sleep(1.5)
        swap()  # discard everything completed during ramp
        history: list[dict] = []
        stable = False
        t_mark = time.monotonic()
        try:
            while len(history) < 10 and not stop_evt.is_set():
                time.sleep(2.5)
                now = time.monotonic()
                lat = swap()
                elapsed = now - t_mark
                t_mark = now
                lat.sort()
                ring_ips = len(lat) / elapsed
                p99 = lat[int(len(lat) * 0.99) - 1] / 1e3 if lat else 0.0
                history.append({"ips": round(ring_ips, 1),
                                "p99_us": round(p99, 1)})
                log(f"ring-shm window {len(history)}: {len(lat)} "
                    f"completions in {elapsed:.2f}s = {ring_ips:.1f} ips, "
                    f"p99 {p99 / 1e3:.1f}ms")
                if "ring_rows" not in out:
                    # Per-ring occupancy/backpressure rows while the rings
                    # are still attached (they detach at lane exit).
                    out["ring_rows"] = engine.ring_shm.status()
                if _tail_is_stable(history, ("ips", "p99_us"), 0.10, 3):
                    stable = True
                    break
        finally:
            stop_evt.set()
            for t in threads:
                t.join(timeout=120)
        if errs:
            raise RuntimeError(f"shm_ring: lane errors: {errs[:3]}")
        if not history:
            raise RuntimeError("shm_ring: no measurement windows completed")
        tail = history[-min(3, len(history)):]
        ring_ips = sum(w["ips"] for w in tail) / len(tail)
        ring_p99 = sum(w["p99_us"] for w in tail) / len(tail)
        occ_samples = sum(occ_n)
        out["ring"] = {"ips": round(ring_ips, 1),
                       "p99_us": round(ring_p99, 1), "stable": stable,
                       "occupancy_mean": (round(sum(occ_sum) / occ_samples,
                                                2)
                                          if occ_samples else None),
                       "windows": history}
        out["lanes"], out["span"], out["dim"] = lanes, span, dim
        out["ring_vs_http_ips"] = (round(ring_ips / out["http"]["ips"], 3)
                                   if out["http"]["ips"] else None)
        try:
            psnap = engine.profile_snapshot(model="ring_identity")
            pm = next(iter(psnap["models"].values()), None)
            if pm is not None:
                rows = sum(b["rows"] for b in pm["buckets"])
                padded = sum(b["padded_rows"] for b in pm["buckets"])
                out["fill_ratio"] = (round(rows / (rows + padded), 4)
                                     if rows + padded else 1.0)
                out["duty_cycle"] = psnap["duty_cycle"]
        except Exception as exc:  # noqa: BLE001 — profiler must not sink
            log(f"profiler snapshot unavailable: {exc}")
        log(f"shm_ring: ring {ring_ips:.1f} ips (p99 "
            f"{ring_p99 / 1e3:.1f}ms) vs http {out['http']['ips']:.1f} ips "
            f"(p99 {out['http']['p99_us'] / 1e3:.1f}ms) = "
            f"{out['ring_vs_http_ips']}x")
        return out
    finally:
        srv.stop()
        engine.shutdown()


def bench_shm_fanin(producers: int = 8, rows: int = 64, dim: int = 16384,
                    replay_s: float = 8.0, live_conc: int = 16):
    """Many-producer shm fan-in + shadow-class protection, two stories:

    1. Fan-in scaling: one staged-dataset segment, N REAL producer
       processes (tools/replay.py workers) each with its own SPSC ring,
       all multiplexed through the engine-side reaper — aggregate ips at
       ``producers`` rings vs ONE producer on the same plane.  The
       acceptance bar (aggregate >= 3x single) reads off
       ``fanin_vs_single_ips``.
    2. Shadow protection: closed-loop LIVE http traffic (priority 0)
       measured with replay off, then again with the producer fleet
       replaying at the shadow priority under a QoS config (weight-8
       protected+preempting interactive class vs a weight-1 capped
       shadow class) — ``shadow_p99_ratio`` (live p99 on/off) must
       stay near 1.0 (<= 1.10 is the bar bench_summary gates).

    Returns {single: {ips}, fanin: {ips, producers, per_producer},
    fanin_vs_single_ips, live_off: {ips, p99_us, stable},
    live_shadow: {ips, p99_us, stable}, shadow: {completions, errors},
    shadow_p99_ratio, rows, dim}.
    """
    import numpy as np

    import client_tpu.http as httpclient
    from client_tpu.admission.qos import QosConfig, QosController
    from client_tpu.engine import TpuEngine
    from client_tpu.engine.config import (
        DynamicBatchingConfig,
        ModelConfig,
        TensorConfig,
    )
    from client_tpu.engine.model import ModelBackend
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.engine.scheduler import power_buckets
    from client_tpu.server import HttpInferenceServer
    from client_tpu.utils.shm_ring.staged import build_staged_dataset
    from tools.replay import collect_workers, spawn_workers

    window_s, max_windows = 2.5, 8
    if os.environ.get("BENCH_SMOKE"):
        producers, rows, dim, replay_s, live_conc = 4, 8, 1024, 2.0, 4
        window_s, max_windows = 1.0, 4
    mb = min(64, max(live_conc, producers * 4))

    class FaninIdentity(ModelBackend):
        def __init__(self):
            self.config = ModelConfig(
                name="fanin_identity", platform="jax",
                max_batch_size=mb,
                input=[TensorConfig("INPUT", "FP32", [dim])],
                output=[TensorConfig("OUTPUT", "FP32", [dim])],
                dynamic_batching=DynamicBatchingConfig(
                    preferred_batch_size=[mb],
                    max_queue_delay_microseconds=200),
                batch_buckets=power_buckets(mb),
                instance_count=4,
            )

        def make_apply(self):
            def apply(inputs):
                return {"OUTPUT": inputs["INPUT"] + 1.0}
            return apply

    repo = ModelRepository()
    repo.register_backend(FaninIdentity())
    # Shadow protection now rides the QoS system: replay traffic
    # (priority 8) lands in the shadow class' min_priority band and is
    # capped well below the live plane's concurrency, while the
    # interactive class holds an 8x WFQ share, preempts in-assembly
    # batches, and is protected from the governor — the isolation this
    # probe exists to measure.  The token bucket matters as much as the
    # WFQ weight here: WFQ is work-conserving, so on a host-saturated
    # box an uncapped shadow fleet fills every live think-time gap and
    # steals the core itself.  The quota makes shadow non-work-
    # conserving — sheds carry the bucket's refill time as Retry-After
    # and the producers sleep it off instead of hammering the reaper.
    qos = QosController(QosConfig.from_dict({
        "classes": {
            "interactive": {"weight": 8, "preempt": True,
                            "protect": True},
            "shadow": {"weight": 1, "min_priority": 8,
                       "tokens_per_s": 5.0 * producers,
                       "burst": 1.0 * producers,
                       "max_inflight": 1,
                       "max_queue_depth": producers},
        },
        "default_class": "interactive",
    }))
    engine = TpuEngine(repo, warmup=True, qos=qos)
    srv = HttpInferenceServer(engine, port=0).start()
    rng = np.random.default_rng(0)
    staged = rng.random((rows, dim), dtype=np.float32)
    ds = None
    out: dict = {}
    try:
        ds = build_staged_dataset("/bench_fanin_dset", {"INPUT": staged})
        reg_client = httpclient.InferenceServerClient(srv.url)
        reg_client.register_staged_dataset("bench_fanin", "/bench_fanin_dset")

        def replay_fleet(n, duration, priority):
            procs = spawn_workers(
                srv.url, "fanin_identity", "/bench_fanin_dset",
                "bench_fanin", n, duration=duration, priority=priority,
                slot_count=16, slot_bytes=staged[0].nbytes + 4096,
                key_prefix=f"/bench_fanin_p{priority}n{n}")
            return collect_workers(procs, timeout_s=duration * 4 + 120)

        def fleet_ips(stats):
            return round(sum(s.get("ips", 0.0) for s in stats), 1)

        # -- fan-in scaling: 1 producer, then the full fleet, priority 0
        # (no shadow gate in the way — this phase measures the reaper).
        single = replay_fleet(1, replay_s, 0)
        if any("error" in s for s in single):
            raise RuntimeError(f"shm_fanin: single producer failed: "
                               f"{single}")
        out["single"] = {"ips": fleet_ips(single)}
        fleet = replay_fleet(producers, replay_s, 0)
        bad = [s for s in fleet if "error" in s]
        if bad:
            raise RuntimeError(f"shm_fanin: producer fleet failed: {bad}")
        if sum(s.get("errors", 0) for s in fleet):
            raise RuntimeError(f"shm_fanin: fleet completions errored: "
                               f"{fleet}")
        out["fanin"] = {"ips": fleet_ips(fleet), "producers": producers,
                        "per_producer": [s.get("ips") for s in fleet]}
        out["fanin_vs_single_ips"] = (
            round(out["fanin"]["ips"] / out["single"]["ips"], 3)
            if out["single"]["ips"] else None)
        log(f"shm_fanin: {producers} producers {out['fanin']['ips']:.1f} "
            f"ips vs single {out['single']['ips']:.1f} ips = "
            f"{out['fanin_vs_single_ips']}x")

        # -- live plane: closed-loop HTTP inference at priority 0,
        # measured with replay off, then under a shadow-priority replay
        # fleet.  Same warm bucket ladder for both phases.
        client = httpclient.InferenceServerClient(srv.url,
                                                  concurrency=live_conc)
        inp = httpclient.InferInput("INPUT", [1, dim], "FP32")
        inp.set_data_from_numpy(staged[:1])

        def infer_live():
            client.infer("fanin_identity", [inp])

        try:
            k = 1
            while True:  # precompile every wave bucket outside windows
                ts = [threading.Thread(target=infer_live)
                      for _ in range(k)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if k >= live_conc:
                    break
                k = min(k * 2, live_conc)
            def live_costs():
                snap = engine.costs.snapshot().get("tenants", {})
                row = snap.get("default", {})
                inter = row.get("interference", {})
                foreign = sum(
                    t.get("device_s", 0.0) + t.get("padding_s", 0.0)
                    + t.get("host_s", 0.0)
                    for name, t in snap.items() if name != "default")
                return {"requests": row.get("requests", 0),
                        "device_s": (row.get("device_s", 0.0)
                                     + row.get("padding_s", 0.0)
                                     + row.get("host_s", 0.0)),
                        "queue_s": row.get("queue_s", 0.0),
                        "co_batch_s": inter.get("co_batch_s", 0.0),
                        "queue_wait_s": inter.get("queue_wait_s", 0.0),
                        "foreign_device_s": foreign}

            def per_req(after, before):
                d_req = max(1, after["requests"] - before["requests"])
                return {k: (after[k] - before[k]) * 1e6 / d_req
                        for k in ("device_s", "queue_s", "co_batch_s",
                                  "queue_wait_s")}

            costs_base = live_costs()
            res_off = run_stable_load(infer_live, live_conc,
                                      window_s=window_s,
                                      max_windows=max_windows,
                                      tag="fanin-live-off")
            out["live_off"] = {"ips": round(res_off["ips"], 1),
                               "p99_us": round(res_off["p99_us"], 1),
                               "stable": res_off["stable"]}
            # Shadow replay must outlive the whole measured load phase;
            # collect_workers joins the fleet afterwards.
            shadow_s = 1.5 + window_s * max_windows + 6.0

            costs_before = live_costs()
            t_before = time.monotonic()
            # Shallow rings for the shadow fleet: a shed costs a full
            # submit/reject round through the reaper, so the burst a
            # producer can land between backoffs is kept small.  The
            # 250ms backoff floor keeps the fleet's shed-retry churn
            # off the host CPU once the quota bucket drains — the
            # bucket alone only pushes back ~one token-refill at a
            # time, which a closed loop treats as an invitation.
            procs = spawn_workers(
                srv.url, "fanin_identity", "/bench_fanin_dset",
                "bench_fanin", producers, duration=shadow_s, priority=8,
                slot_count=4, slot_bytes=staged[0].nbytes + 4096,
                shed_backoff=0.5, reap_poll=0.005,
                key_prefix="/bench_fanin_shadow")
            try:
                res_on = run_stable_load(infer_live, live_conc,
                                         window_s=window_s,
                                         max_windows=max_windows,
                                         tag="fanin-live-shadow")
                # Sample inside the measured phase: collect_workers
                # below waits out the shadow fleet's tail, where the
                # live plane is idle and foreign occupancy is unloaded.
                costs_after = live_costs()
                t_after = time.monotonic()
            finally:
                shadow_stats = collect_workers(
                    procs, timeout_s=shadow_s * 4 + 120)
            out["live_shadow"] = {"ips": round(res_on["ips"], 1),
                                  "p99_us": round(res_on["p99_us"], 1),
                                  "stable": res_on["stable"]}
            # Bracket the shadow window with a second off measurement
            # and take the WORSE of the two offs as the isolation
            # baseline.  On a host-saturated box a single off window
            # can draw 20% low on p99 purely from scheduler noise,
            # which would then read as shadow-induced inflation; the
            # bracket attributes only what exceeds *both* quiet
            # neighbours to the shadow fleet.
            res_off2 = run_stable_load(infer_live, live_conc,
                                       window_s=window_s,
                                       max_windows=max_windows,
                                       tag="fanin-live-off2")
            out["live_off_after"] = {"ips": round(res_off2["ips"], 1),
                                     "p99_us": round(res_off2["p99_us"], 1),
                                     "stable": res_off2["stable"]}
            base_p99 = max(res_off["p99_us"], res_off2["p99_us"])
            # Interference attribution from ledger deltas. Direct legs
            # the ledger tags per request: device time diluted by
            # co-batched shadow rows; queue wait behind shadow
            # arrivals; growth in the live tenant's own per-request
            # device seconds (execute wall dilated by contention —
            # charged to the live tenant, so invisible to the tagged
            # legs). The dominant effect in a closed loop, though, is
            # capacity sharing: the serving pipeline spends fraction
            # rho of its wall time on foreign (shadow-tenant) work —
            # device execute plus the host seconds the ledger meters
            # around it (assembly, dispatch, scatter) — so live
            # throughput scales by (1 - rho) and latency dilates by
            # 1/(1 - rho). rho comes straight from the ledger — the
            # foreign tenants' device+host seconds over the phase wall
            # — making the dilation leg p99_off * rho/(1-rho). The
            # queue legs (arrival-mix estimate, clock growth, occupancy
            # dilation) all price the same congestion from different
            # angles, so the max is taken, not the sum; explained
            # fraction caps at 1 (mean interference can exceed the p99
            # delta — every request waits, only the tail defines p99).
            off = per_req(costs_before, costs_base)
            on = per_req(costs_after, costs_before)
            co_us = on["co_batch_s"]
            qw_us = on["queue_wait_s"]
            contention_us = max(0.0, on["device_s"] - off["device_s"])
            queue_growth_us = max(0.0, on["queue_s"] - off["queue_s"])
            rho_f = (costs_after["foreign_device_s"]
                     - costs_before["foreign_device_s"]) \
                / max(1e-9, t_after - t_before)
            rho_f = max(0.0, min(0.9, rho_f))
            dilation_us = base_p99 * rho_f / (1.0 - rho_f)
            explained_us = (co_us + contention_us
                            + max(qw_us, queue_growth_us, dilation_us))
            inflation_us = max(0.0, res_on["p99_us"] - base_p99)
            if inflation_us <= 0.05 * base_p99:
                # No meaningful inflation: nothing to explain (the
                # shadow class held — that IS the full explanation).
                explained = 1.0
            else:
                explained = min(1.0, explained_us / inflation_us)
            out["interference"] = {
                "co_batch_us_per_req": round(co_us, 1),
                "queue_wait_us_per_req": round(qw_us, 1),
                "device_contention_us_per_req": round(contention_us, 1),
                "queue_growth_us_per_req": round(queue_growth_us, 1),
                "foreign_occupancy": round(rho_f, 3),
                "occupancy_dilation_us": round(dilation_us, 1),
                "p99_inflation_us": round(inflation_us, 1),
                "explained_fraction": round(explained, 3),
            }
            # Shed shadow submissions surface as reap errors in the
            # workers — expected under the cap, recorded, not fatal.
            out["shadow"] = {
                "completions": sum(s.get("completions", 0)
                                   for s in shadow_stats),
                "errors": sum(s.get("errors", 0) for s in shadow_stats),
            }
            qsnap = engine.qos_snapshot().get("classes", {})
            out["qos"] = {
                "shadow_sheds": qsnap.get("shadow", {}).get("sheds", 0),
                "interactive_preemptions": qsnap.get(
                    "interactive", {}).get("preemptions", 0),
            }
        finally:
            client.close()
        off_p99s = [out["live_off"]["p99_us"],
                    out.get("live_off_after", {}).get("p99_us", 0.0)]
        base = max(off_p99s)
        out["shadow_p99_ratio"] = (
            round(out["live_shadow"]["p99_us"] / base, 3) if base else None)
        out["rows"], out["dim"] = rows, dim
        reg_client.unregister_staged_dataset("bench_fanin")
        reg_client.close()
        log(f"shm_fanin: live p99 {base / 1e3:.1f}ms off (worse of "
            f"bracket) -> {out['live_shadow']['p99_us'] / 1e3:.1f}ms under "
            f"shadow replay = {out['shadow_p99_ratio']}x "
            f"(shadow {out['shadow']['completions']} completions, "
            f"{out['shadow']['errors']} shed)")
        inter = out.get("interference")
        if inter:
            log(f"shm_fanin: interference co_batch "
                f"{inter['co_batch_us_per_req']}us + contention "
                f"{inter['device_contention_us_per_req']}us + "
                f"foreign occupancy {inter['foreign_occupancy']:.0%} "
                f"(dilation {inter['occupancy_dilation_us']}us, queue "
                f"{max(inter['queue_wait_us_per_req'], inter['queue_growth_us_per_req'])}us) "
                f"explains {inter['explained_fraction']:.0%} of the p99 "
                f"inflation")
        return out
    finally:
        if ds is not None:
            ds.close(unlink=True)
        srv.stop()
        engine.shutdown()


def bench_gauntlet(replicas: int = 2, conc: int = 4, phase_s: float = 6.0,
                   flood_producers: int = 3):
    """Production scenario gauntlet: the QoS system under the load
    shapes that break naive admission, on a routed 2-replica fleet.

    Every replica is an in-process engine whose models share ONE
    device lock with a fixed per-batch service time (8 ms), so
    capacity, queueing, and cross-model contention are deterministic
    in seconds rather than host-dependent — the scenario outcomes are
    about scheduling policy, not machine speed.

    Phases (shapes shared with ``tools/replay.py``):

    * **baseline** — interactive tenant alone, closed loop through the
      router: the p99 yardstick.
    * **diurnal** — a batch tenant sweeps a raised-cosine load on the
      SAME model while interactive is re-measured: WFQ (8:2) must keep
      interactive p99 inside the SLO through the peak.
    * **flash_crowd** — a flood tenant's shm replay fleet (per-replica
      rings, ``--shape flash_crowd``) slams a batch model sharing the
      device: the SLO fast-burn must fire, the governor must throttle
      the batch class (journal ``qos.throttle``), shed producers must
      back off per the slot Retry-After, and once recovery traffic
      dilutes the burn the class must restore (``qos.restore``).
      Interactive p99, measured through the event, must hold its SLO.
    * **adversarial_mix** — DLRM + generative + vision tenants run
      concurrently; every class must make progress and interactive
      p99 must stay inside the SLO.

    Gated by ``bench_summary --check``: slo_pass AND throttle fired
    AND cleared (the journal evidence, not just the ratios).
    """
    import numpy as np

    import client_tpu.http as httpclient
    from client_tpu.admission.qos import QosConfig, QosController
    from client_tpu.engine import TpuEngine
    from client_tpu.engine.config import (
        DynamicBatchingConfig,
        ModelConfig,
        TensorConfig,
    )
    from client_tpu.engine.model import ModelBackend
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.engine.types import InferRequest
    from client_tpu.models.dlrm import DlrmBackend
    from client_tpu.models.generate import TinyGptBackend
    from client_tpu.observability.events import journal
    from client_tpu.router import Replica, Router, RouterHttpServer
    from client_tpu.server import HttpInferenceServer
    from client_tpu.utils.shm_ring.staged import build_staged_dataset
    from tools.replay import collect_workers, shape_rate, spawn_workers

    if os.environ.get("BENCH_SMOKE"):
        replicas, phase_s, flood_producers = 2, 4.0, 4

    dim, service_s, mb = 16, 0.008, 4
    slo_threshold_us = 120_000.0

    class SleepIdentity(ModelBackend):
        """Identity with a fixed service time under a shared 'device'
        lock — one engine's models serialize on it exactly like
        co-located workloads on one chip."""

        jittable = False  # time.sleep must run per call, not per trace

        def __init__(self, name: str, device: threading.Lock):
            self._device = device
            self.config = ModelConfig(
                name=name, platform="jax", max_batch_size=mb,
                input=[TensorConfig("INPUT", "FP32", [dim])],
                output=[TensorConfig("OUTPUT", "FP32", [dim])],
                dynamic_batching=DynamicBatchingConfig(
                    preferred_batch_size=[mb],
                    max_queue_delay_microseconds=200),
                instance_count=1,
            )

        def make_apply(self):
            def apply(inputs):
                with self._device:
                    time.sleep(service_s)
                return {"OUTPUT": np.asarray(inputs["INPUT"])}
            return apply

    # One QoS policy for the whole fleet (each engine gets its own
    # controller instance — runtime state is per-replica).  The batch
    # bucket is sized ABOVE the flood's attempt rate so congestion
    # reaches the queue and the SLO: the gauntlet proves the governor
    # closes the loop, not that a static cap was guessed right.
    qos_spec = {
        "classes": {
            "interactive": {"weight": 8, "preempt": True, "protect": True},
            "batch": {"weight": 2, "priority_level": 4,
                      "tokens_per_s": 600.0, "burst": 60.0,
                      "max_queue_depth": 64},
        },
        "tenants": {"live": "interactive", "etl": "batch",
                    "flood": "batch"},
        "default_class": "interactive",
        "restore_hold_s": 1.0,
        "governor_interval_s": 0.25,
    }
    # Per-model SLO: the flood's model burns on its own latency
    # objective — anything over 60 ms is slow for an 8 ms-service
    # batch job, and latency_target 0.5 + threshold 1.2 means the
    # governor fires once >60% of its window completions are slow.
    # That is unreachable for the base-rate trickle (which completes
    # in ~8 ms) but certain for a flash crowd queued behind its own
    # backlog; the interactive model's thresholds are deliberately
    # unreachable so the governor only ever acts on the class that is
    # actually drowning.
    slo_spec = json.dumps({
        "availability": 0.999,
        "latency_threshold_us": slo_threshold_us,
        "latency_target": 0.9,
        "fast_burn_threshold": 14.4,
        "models": {"batch_net": {"latency_threshold_us": 60_000.0,
                                 "latency_target": 0.5,
                                 "fast_burn_threshold": 1.2}},
    })

    def build_replica():
        device = threading.Lock()
        repo = ModelRepository()
        repo.register_backend(SleepIdentity("gauntlet_net", device))
        repo.register_backend(SleepIdentity("batch_net", device))
        repo.register_backend(DlrmBackend(
            name="dlrm_g", host_tables=True, cache_budget_bytes=4096,
            lookup_buckets=[32]))
        repo.register_backend(TinyGptBackend(
            name="gpt_g", n_layers=2, d_model=64, n_heads=2, d_ff=128,
            vocab=128, max_seq_len=32, max_streams=4))
        qos = QosController(QosConfig.from_dict(qos_spec))
        engine = TpuEngine(repo, warmup=True, qos=qos)
        srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
        return engine, srv

    old_slo = os.environ.get("CLIENT_TPU_SLO")
    os.environ["CLIENT_TPU_SLO"] = slo_spec
    fleet = []
    router_srv = None
    ds = None
    out: dict = {"replicas": replicas, "phase_s": phase_s}
    jrnl = journal()
    try:
        try:
            fleet = [build_replica() for _ in range(replicas)]
        finally:
            if old_slo is None:
                os.environ.pop("CLIENT_TPU_SLO", None)
            else:
                os.environ["CLIENT_TPU_SLO"] = old_slo
        router = Router([Replica(srv.url) for _, srv in fleet], seed=99)
        router_srv = RouterHttpServer(router, port=0).start()
        client = httpclient.InferenceServerClient(
            router_srv.url, concurrency=conc + 8)
        inp = httpclient.InferInput("INPUT", [1, dim], "FP32")
        inp.set_data_from_numpy(np.ones((1, dim), np.float32))

        def infer(model, tenant):
            client.infer(model, [inp],
                         headers={"x-tpu-tenant": tenant})

        def measure(tag):
            return run_stable_load(
                lambda: infer("gauntlet_net", "live"), conc,
                window_s=1.0, ramp_s=0.5, max_windows=4,
                tag=f"gauntlet-{tag}")

        def paced_load(model, tenant, rate_fn, duration, threads=4):
            """Open-loop-ish paced senders — demand follows
            ``rate_fn(t)`` (total across threads); a slow server lowers
            the achieved rate, which is the point: shapes model
            arrivals, the engine owns service."""
            counts = {"ok": 0, "err": 0}
            lock = threading.Lock()
            stop = threading.Event()

            def run():
                t0 = time.monotonic()
                next_at = t0
                while not stop.is_set():
                    now = time.monotonic()
                    if now - t0 >= duration:
                        return
                    r = max(rate_fn(now - t0) / threads, 1e-6)
                    if now < next_at:
                        time.sleep(min(next_at - now, 0.02))
                        continue
                    try:
                        infer(model, tenant)
                        with lock:
                            counts["ok"] += 1
                    except Exception:  # noqa: BLE001 — sheds expected
                        with lock:
                            counts["err"] += 1
                    next_at = max(next_at, now - 1.0 / r) + 1.0 / r

            ts = [threading.Thread(target=run, daemon=True)
                  for _ in range(threads)]
            for t in ts:
                t.start()
            return ts, counts, stop

        def qos_events(name, since):
            return [e for e in jrnl.snapshot(category="qos")
                    if e.name == name and e.seq > since]

        # -- phase 1: baseline ------------------------------------------------
        base = measure("baseline")
        out["baseline"] = {"ips": round(base["ips"], 1),
                           "p99_us": round(base["p99_us"], 1),
                           "stable": base["stable"]}
        log(f"gauntlet baseline: {base['ips']:.1f} infer/s, "
            f"p99 {base['p99_us'] / 1e3:.1f}ms")

        # -- phase 2: diurnal batch sweep on the SAME model -------------------
        ts, etl, _stop = paced_load(
            "gauntlet_net", "etl",
            lambda t: shape_rate("diurnal", t, phase_s, 30.0, 120.0),
            phase_s + 2.0)
        diur = measure("diurnal")
        for t in ts:
            t.join()
        out["diurnal"] = {
            "ips": round(diur["ips"], 1),
            "p99_us": round(diur["p99_us"], 1),
            "stable": diur["stable"],
            "batch_ok": etl["ok"], "batch_shed": etl["err"],
            "p99_ratio": (round(diur["p99_us"] / base["p99_us"], 3)
                          if base["p99_us"] else None),
        }
        log(f"gauntlet diurnal: live p99 {diur['p99_us'] / 1e3:.1f}ms "
            f"({out['diurnal']['p99_ratio']}x base), batch "
            f"{etl['ok']} ok / {etl['err']} shed")

        # -- phase 3: flash crowd over shm replay -----------------------------
        rng = np.random.default_rng(7)
        ds = build_staged_dataset(
            "/bench_gauntlet_dset",
            {"INPUT": rng.random((8, dim), dtype=np.float32)})
        reg_clients = []
        for _, srv in fleet:
            rc = httpclient.InferenceServerClient(srv.url)
            rc.register_staged_dataset("bench_gauntlet",
                                       "/bench_gauntlet_dset")
            reg_clients.append(rc)

        throttle_seq = jrnl.export(limit=0)["next_seq"]
        flash = None
        flood_stats = []
        for attempt in range(3):
            procs = []
            for ri, (_, srv) in enumerate(fleet):
                procs += spawn_workers(
                    srv.url, "batch_net", "/bench_gauntlet_dset",
                    "bench_gauntlet", flood_producers,
                    duration=phase_s, tenant="flood",
                    slot_count=48, slot_bytes=dim * 4 + 4096,
                    rate=0.5, peak_rate=400.0, shape="flash_crowd",
                    shape_period=phase_s,
                    key_prefix=f"/bgnt_a{attempt}r{ri}")
            flash = measure("flash")
            flood_stats = collect_workers(procs,
                                          timeout_s=phase_s * 4 + 120)
            if qos_events("throttle", throttle_seq):
                break
            log(f"gauntlet flash: no qos.throttle after round "
                f"{attempt + 1}, retrying")
        throttled = qos_events("throttle", throttle_seq)
        # Recovery: a modest batch trickle (admitted under the
        # throttled floor) supplies the fast completions that dilute
        # the burn windows so the governor can walk the rate back up.
        restored = qos_events("restore", throttle_seq)
        if throttled and not restored:
            ts, _rec, stop = paced_load("batch_net", "etl",
                                        lambda t: 40.0, 30.0)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                restored = qos_events("restore", throttle_seq)
                if restored and not any(
                        eng.qos.throttled_classes()
                        for eng, _ in fleet):
                    break
                time.sleep(0.25)
            stop.set()
            for t in ts:
                t.join()
        out["flash"] = {
            "ips": round(flash["ips"], 1),
            "p99_us": round(flash["p99_us"], 1),
            "stable": flash["stable"],
            "p99_ratio": (round(flash["p99_us"] / base["p99_us"], 3)
                          if base["p99_us"] else None),
            "flood_completions": sum(s.get("completions", 0)
                                     for s in flood_stats),
            "flood_sheds": sum(s.get("sheds", 0) for s in flood_stats),
            "throttle_fired": len(throttled),
            "throttle_cleared": bool(restored) and not any(
                eng.qos.throttled_classes() for eng, _ in fleet),
        }
        log(f"gauntlet flash: live p99 {flash['p99_us'] / 1e3:.1f}ms, "
            f"throttle x{len(throttled)}, restored={bool(restored)}, "
            f"flood {out['flash']['flood_completions']} done / "
            f"{out['flash']['flood_sheds']} shed")

        # -- phase 4: adversarial mix (vision + dlrm + generative) ------------
        mix_s = min(phase_s, 4.0)
        stop_at = time.monotonic() + mix_s
        mix_counts = {"dlrm": 0, "gpt": 0}
        mix_errs: list = []
        mix_lock = threading.Lock()

        def dlrm_loop():
            r = np.random.default_rng(3)
            while time.monotonic() < stop_at:
                counts = r.integers(1, 3, size=4)
                idx = r.integers(0, 64, size=int(counts.sum()))
                off = np.concatenate([[0], np.cumsum(counts)])
                i_d = httpclient.InferInput("DENSE", [1, 8], "FP32")
                i_d.set_data_from_numpy(
                    r.standard_normal((1, 8)).astype(np.float32))
                i_i = httpclient.InferInput(
                    "INDICES", [int(counts.sum())], "INT32")
                i_i.set_data_from_numpy(idx.astype(np.int32))
                i_o = httpclient.InferInput("OFFSETS", [5], "INT32")
                i_o.set_data_from_numpy(off.astype(np.int32))
                try:
                    client.infer("dlrm_g", [i_d, i_i, i_o],
                                 headers={"x-tpu-tenant": "etl"})
                    with mix_lock:
                        mix_counts["dlrm"] += 1
                except Exception as exc:  # noqa: BLE001
                    with mix_lock:
                        mix_errs.append(f"dlrm: {exc}")
                    return

        def gpt_loop(eng):
            while time.monotonic() < stop_at:
                done = threading.Event()

                def cb(resp):
                    if resp.error is not None:
                        with mix_lock:
                            mix_errs.append(f"gpt: {resp.error}")
                        done.set()
                    elif resp.final:
                        with mix_lock:
                            mix_counts["gpt"] += 1
                        done.set()

                eng.async_infer(InferRequest(
                    model_name="gpt_g", tenant="live",
                    inputs={"INPUT_IDS": np.asarray([1, 2, 3],
                                                    np.int32)},
                    parameters={"max_tokens": 6}), cb)
                if not done.wait(60):
                    with mix_lock:
                        mix_errs.append("gpt: generation stalled")
                    return

        mix_threads = [threading.Thread(target=dlrm_loop, daemon=True)
                       for _ in range(2)]
        mix_threads += [threading.Thread(target=gpt_loop, args=(eng,),
                                         daemon=True)
                        for eng, _ in fleet]
        for t in mix_threads:
            t.start()
        mix = run_stable_load(
            lambda: infer("gauntlet_net", "live"), 2,
            window_s=1.0, ramp_s=0.5, max_windows=int(mix_s) - 1,
            tag="gauntlet-mix")
        for t in mix_threads:
            t.join(timeout=60)
        if mix_errs:
            raise RuntimeError(f"gauntlet adversarial mix failed: "
                               f"{mix_errs[:3]}")
        out["adversarial_mix"] = {
            "vision_p99_us": round(mix["p99_us"], 1),
            "vision_ips": round(mix["ips"], 1),
            "dlrm_ok": mix_counts["dlrm"],
            "gpt_ok": mix_counts["gpt"],
        }
        log(f"gauntlet mix: vision p99 {mix['p99_us'] / 1e3:.1f}ms, "
            f"dlrm {mix_counts['dlrm']}, gpt {mix_counts['gpt']}")

        # -- verdict ----------------------------------------------------------
        preemptions = sum(
            cls.get("preemptions", 0)
            for eng, _ in fleet
            for cls in eng.qos_snapshot()["classes"].values())
        out["preemptions"] = preemptions
        out["slo_threshold_us"] = slo_threshold_us
        out["slo_pass"] = bool(
            base["p99_us"] < slo_threshold_us
            and diur["p99_us"] < slo_threshold_us
            and flash["p99_us"] < slo_threshold_us
            and mix["p99_us"] < slo_threshold_us
            and mix_counts["dlrm"] > 0 and mix_counts["gpt"] > 0
            and etl["ok"] > 0
            and out["flash"]["flood_completions"] > 0)
        log(f"gauntlet verdict: slo_pass={out['slo_pass']} "
            f"throttle_fired={out['flash']['throttle_fired']} "
            f"cleared={out['flash']['throttle_cleared']} "
            f"preemptions={preemptions}")
        for rc in reg_clients:
            try:
                rc.unregister_staged_dataset("bench_gauntlet")
            # tpulint: allow[swallowed-exception] reviewed fail-open
            except Exception:  # noqa: BLE001
                pass
            rc.close()
        client.close()
        return out
    finally:
        if ds is not None:
            ds.close(unlink=True)
        if router_srv is not None:
            router_srv.stop()
        for eng, srv in fleet:
            srv.stop()
            eng.shutdown()


def bench_selfdriving(replicas: int = 2, phase_s: float = 6.0):
    """Self-driving chaos probe: every closed loop must fire AND clear
    with zero operator input, on a routed 2-replica fleet under the
    arrival shapes that trip each sensor.

    Same deterministic substrate as the gauntlet — in-process engines
    whose models share one device lock with fixed service times — but
    the subject here is the control loops themselves
    (``CLIENT_TPU_SELFDRIVE``), not the QoS policy:

    * **dispatch retune** — a diurnal stream of staggered 3-row bursts
      against an 8-wide preferred batch pads every dispatch to the
      next bucket (fill 0.75 < fill_low): the tuner must cut the
      dispatch deadline and cap max-batch (journal
      ``autotune.dispatch_tighten``), after which the shorter window
      splits the stagger into exact power-of-two batches and fill
      recovers above the floor; when the bursts stop, quiet windows
      must walk the override back out (``autotune.dispatch_restore``).
    * **SLO-burn admission tightening** — a flash flood queues a slow
      model past its latency objective: fast burn must progressively
      cut its admitted rate (``admission.tighten``), and a fast
      recovery trickle that dilutes the burn windows must restore it
      stepwise (``admission.restore``).
    * **drift re-placement** — hot-replica skew (one replica hammered
      directly while its peer idles) must flag drift
      (``fleet.drift``) and promote the LPT plan to executed rolling
      moves (``fleet.rebalance`` ... ``fleet.rebalance_done``), after
      which every model must still serve somewhere on the fleet and
      the cooldown must hold the loop to exactly one rebalance.

    Every assertion reads journal cursors (the edges, not the
    internal state), and every loop's actuation count is bounded —
    a flapping loop fails the probe even if it eventually converges.
    Gated by ``bench_summary --check``: loops_closed AND
    fill_recovered AND bounded AND blackbox one-bundle-per-incident.

    The incident blackbox rides the same probe: `admission.tighten`
    and `fleet.rebalance` are trigger edges, so each induced incident
    must yield exactly one bundle per engine plus one router bundle —
    a storm of bundles from a single incident is the debounce/cooldown
    failing, zero bundles is the trigger path failing.
    """
    import tempfile

    import numpy as np

    import client_tpu.http as httpclient
    from client_tpu.engine import TpuEngine
    from client_tpu.engine.config import (
        DynamicBatchingConfig,
        ModelConfig,
        TensorConfig,
    )
    from client_tpu.engine.model import ModelBackend
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.engine.types import InferRequest
    from client_tpu.observability.events import journal
    from client_tpu.observability.fleet import FleetMonitorConfig
    from client_tpu.router import Replica, Router, RouterHttpServer
    from client_tpu.server import HttpInferenceServer
    from tools.replay import shape_rate

    if os.environ.get("BENCH_SMOKE"):
        phase_s = 4.0

    dim = 16

    class SleepIdentity(ModelBackend):
        """Identity with a fixed service time under a shared 'device'
        lock (the gauntlet's determinism idiom)."""

        jittable = False  # time.sleep must run per call, not per trace

        def __init__(self, name: str, device: threading.Lock,
                     service_s: float, max_batch: int, delay_us: int):
            self._device = device
            self._service_s = service_s
            self.config = ModelConfig(
                name=name, platform="jax", max_batch_size=max_batch,
                input=[TensorConfig("INPUT", "FP32", [dim])],
                output=[TensorConfig("OUTPUT", "FP32", [dim])],
                dynamic_batching=DynamicBatchingConfig(
                    preferred_batch_size=[max_batch],
                    max_queue_delay_microseconds=delay_us),
                instance_count=1,
            )

        def make_apply(self):
            def apply(inputs):
                with self._device:
                    time.sleep(self._service_s)
                return {"OUTPUT": np.asarray(inputs["INPUT"])}
            return apply

    # Fast loop knobs: seconds-scale cooldowns/holds so fire->clear fits
    # a bench phase; restore_hold_s stays above the post-retune measure
    # window so healthy-fill ticks don't start loosening mid-measure
    # (the flap the unit tests prove the hysteresis against).
    selfdrive_spec = json.dumps({
        "interval_s": 0.25, "min_calls": 4, "fill_low": 0.8,
        "wait_high_s": 5.0, "cooldown_s": 2.0, "restore_hold_s": 4.0,
        "burn_factor": 0.5, "burn_min_ratio": 0.25,
        "burn_restore_step": 4.0, "burn_restore_hold_s": 1.0,
        "burn_cooldown_s": 2.0, "rebalance_cooldown_s": 120.0,
        "max_moves_per_window": 4, "rebalance_window_s": 300.0,
        "quiesce_wait_s": 2.0})
    # burn_net: anything past 30 ms is slow for an 8 ms-service model,
    # and threshold 1.9 with target 0.5 means fast burn needs >95% of
    # window completions slow — certain for a queued flood, cleared by
    # a small fast trickle. The interactive model inherits objectives
    # it cannot trip.
    slo_spec = json.dumps({
        "availability": 0.999,
        "models": {"burn_net": {"latency_threshold_us": 30_000.0,
                                "latency_target": 0.5,
                                "fast_burn_threshold": 1.9}},
    })

    def build_replica():
        device = threading.Lock()
        repo = ModelRepository()
        repo.register_backend(SleepIdentity(
            "sd_net", device, 0.002, max_batch=8, delay_us=4000))
        repo.register_backend(SleepIdentity(
            "burn_net", device, 0.008, max_batch=4, delay_us=200))
        # skew_net exists for the drift phase: 50 ms unbatched service,
        # so a handful of queued calls puts ~0.25 s of queue wait on one
        # replica. Queue wait is the one drift signal that stays
        # per-replica in this in-process fleet — the profiler and the
        # flight recorder are process-global singletons, so N in-process
        # engines serve identical duty/fill timeseries and only the
        # router's own load view can tell them apart. No SLO objective
        # on it, so the admission loop cannot drain the queue out from
        # under the drift signal.
        repo.register_backend(SleepIdentity(
            "skew_net", device, 0.05, max_batch=1, delay_us=200))
        engine = TpuEngine(repo, warmup=True)
        srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
        return engine, srv

    # Blackbox armed on exactly the two incident edges this probe
    # induces; the long cooldown means each trigger may capture only
    # once per engine for the whole run — the one-bundle-per-incident
    # invariant falls straight out of the config under test.
    blackbox_dir = tempfile.mkdtemp(prefix="bench_blackbox_")
    blackbox_spec = json.dumps({
        "dir": blackbox_dir,
        "triggers": ["admission.tighten", "fleet.rebalance"],
        "debounce_s": 1.0, "cooldown_s": 600.0,
        "window_s": 30.0, "post_window_s": 0.2})
    saved = {k: os.environ.get(k)
             for k in ("CLIENT_TPU_SELFDRIVE", "CLIENT_TPU_SLO",
                       "CLIENT_TPU_BLACKBOX")}
    os.environ["CLIENT_TPU_SELFDRIVE"] = selfdrive_spec
    os.environ["CLIENT_TPU_SLO"] = slo_spec
    os.environ["CLIENT_TPU_BLACKBOX"] = blackbox_spec
    fleet = []
    router_srv = None
    client = None
    out: dict = {"replicas": replicas, "phase_s": phase_s}
    jrnl = journal()
    probe_seq = jrnl.export(limit=0)["next_seq"]
    try:
        try:
            fleet = [build_replica() for _ in range(replicas)]
            router = Router([Replica(srv.url) for _, srv in fleet],
                            seed=101)
            # The rebalancer arms only when a monitor exists AND
            # CLIENT_TPU_SELFDRIVE is set at construction.
            router_srv = RouterHttpServer(
                router, port=0,
                monitor_config=FleetMonitorConfig(
                    interval_s=0.5, threshold=0.8, min_replicas=2,
                    window_s=6.0)).start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if any(eng.selfdrive is None for eng, _ in fleet):
            raise RuntimeError("selfdriving: engine governor not armed")
        if router_srv.rebalancer is None:
            raise RuntimeError("selfdriving: fleet rebalancer not armed")
        if any(eng.blackbox is None for eng, _ in fleet) \
                or router_srv.blackbox is None:
            raise RuntimeError("selfdriving: incident blackbox not armed")

        client = httpclient.InferenceServerClient(
            router_srv.url, concurrency=56)
        inp = httpclient.InferInput("INPUT", [1, dim], "FP32")
        inp.set_data_from_numpy(np.ones((1, dim), np.float32))

        def infer(model, tenant):
            client.infer(model, [inp],
                         headers={"x-tpu-tenant": tenant})

        def edges(category, name, since):
            return [e for e in jrnl.snapshot(category=category,
                                             since_seq=since)
                    if e.name == name]

        def wait_edges(category, name, since, deadline_s, n=1):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                got = edges(category, name, since)
                if len(got) >= n:
                    return got
                time.sleep(0.1)
            return edges(category, name, since)

        def direct_infer(eng, model):
            """Async infer straight into one engine (bypassing the
            router — the skew/burst phases need per-replica aim)."""
            done, errs = threading.Event(), []

            def cb(resp):
                if resp.error is not None:
                    errs.append(str(resp.error))
                done.set()

            eng.async_infer(InferRequest(
                model_name=model,
                inputs={"INPUT": np.ones((1, dim), np.float32)}), cb)
            return done, errs

        def sd_fill_counts():
            rows = padded = 0.0
            for eng, _ in fleet:
                snap = eng.profiler.snapshot()
                for m in snap.get("models", {}).values():
                    if m.get("model") != "sd_net":
                        continue
                    for b in m.get("buckets", ()):
                        rows += float(b.get("rows", 0) or 0)
                        padded += float(b.get("padded_rows", 0) or 0)
            return rows, padded

        def fill_between(a, b):
            dr, dp = b[0] - a[0], b[1] - a[1]
            return round(dr / (dr + dp), 4) if (dr + dp) > 0 else None

        # -- phase 0: baseline through the router -----------------------------
        base = run_stable_load(
            lambda: infer("sd_net", "live"), 2,
            window_s=1.0, ramp_s=0.5, max_windows=3,
            tag="selfdrive-base")
        out["baseline"] = {"ips": round(base["ips"], 1),
                           "p99_us": round(base["p99_us"], 1),
                           "stable": base["stable"]}
        log(f"selfdriving base: {base['ips']:.0f} ips, "
            f"p99 {base['p99_us'] / 1e3:.1f}ms")

        # -- phase 1: diurnal low-fill bursts -> dispatch retune --------------
        # 3 rows staggered 1.5ms apart inside a 4ms dispatch window pad
        # every batch to the 4-bucket (fill 0.75). After the tuner cuts
        # the deadline, the same stagger splits into exact 2+1 batches.
        c1 = jrnl.export(limit=0)["next_seq"]
        f0 = sd_fill_counts()
        stop_bursts = threading.Event()

        def burst_loop(eng):
            t0 = time.monotonic()
            while not stop_bursts.is_set():
                pending = []
                for i in range(3):
                    try:
                        pending.append(direct_infer(eng, "sd_net"))
                    except Exception:  # noqa: BLE001 — chaos tolerant
                        break
                    if i < 2:
                        time.sleep(0.0015)
                for done, _ in pending:
                    done.wait(10)
                rate = shape_rate("diurnal", time.monotonic() - t0,
                                  phase_s, 25.0, 60.0)
                stop_bursts.wait(1.0 / max(1.0, rate))

        burst_threads = [threading.Thread(target=burst_loop, args=(eng,),
                                          daemon=True)
                         for eng, _ in fleet]
        for t in burst_threads:
            t.start()
        tightens = wait_edges("autotune", "dispatch_tighten", c1,
                              phase_s * 2, n=replicas)
        f1 = sd_fill_counts()
        time.sleep(1.5)  # post-retune window under the same bursts
        f2 = sd_fill_counts()
        stop_bursts.set()
        for t in burst_threads:
            t.join(timeout=20)
        if not tightens:
            raise RuntimeError(
                "selfdriving: dispatch loop never tightened under "
                "sustained 0.75-fill bursts")
        fill_before = fill_between(f0, f1)
        fill_after = fill_between(f1, f2)
        # Quiet: the delta classifier must see the idle model and walk
        # the override back out (the full-restore journal edge).
        restores = wait_edges("autotune", "dispatch_restore", c1, 30.0)
        if not restores:
            raise RuntimeError(
                "selfdriving: dispatch override never restored on quiet")
        out["dispatch"] = {
            "tighten_fired": len(tightens),
            "restore_fired": len(restores),
            "fill_before": fill_before,
            "fill_after": fill_after,
            "fill_recovered": bool(
                fill_before is not None and fill_after is not None
                and fill_after >= 0.8 and fill_after > fill_before),
            "action_count": sum(
                eng.selfdrive.snapshot()["dispatch"].get(
                    "action_count", 0) for eng, _ in fleet),
        }
        log(f"selfdriving retune: tighten x{len(tightens)}, fill "
            f"{fill_before} -> {fill_after}, restore x{len(restores)}")

        # -- phase 2: flash flood -> SLO-burn admission tightening ------------
        c2 = jrnl.export(limit=0)["next_seq"]
        flood_counts = {"ok": 0, "shed": 0}
        flood_lock = threading.Lock()
        stop_flood = threading.Event()

        def flood_loop():
            while not stop_flood.is_set():
                try:
                    infer("burn_net", "flood")
                    with flood_lock:
                        flood_counts["ok"] += 1
                except Exception:  # noqa: BLE001 — sheds are the point
                    with flood_lock:
                        flood_counts["shed"] += 1
                    stop_flood.wait(0.05)

        # 48 closed-loop senders -> ~24 queued per replica -> ~6 batch
        # waves of 8ms behind each request: comfortably past the 30ms
        # objective, while the sequential recovery trickle stays under.
        flood_threads = [threading.Thread(target=flood_loop, daemon=True)
                         for _ in range(48)]
        for t in flood_threads:
            t.start()
        adm_tightens = wait_edges("admission", "tighten", c2, phase_s * 3)
        stop_flood.set()
        for t in flood_threads:
            t.join(timeout=30)
        if not adm_tightens:
            raise RuntimeError(
                "selfdriving: admission loop never tightened under burn")
        # Recovery: fast sequential completions dilute the burn windows
        # under the tightened rate floor, so the governor restores.
        stop_trickle = threading.Event()

        def trickle_loop():
            while not stop_trickle.is_set():
                try:
                    infer("burn_net", "etl")
                # tpulint: allow[swallowed-exception] paced best-effort
                except Exception:  # noqa: BLE001
                    pass
                stop_trickle.wait(0.08)

        trickle_threads = [threading.Thread(target=trickle_loop,
                                            daemon=True)
                           for _ in range(4)]
        for t in trickle_threads:
            t.start()
        deadline = time.monotonic() + 45.0
        adm_restores: list = []
        while time.monotonic() < deadline:
            adm_restores = edges("admission", "restore", c2)
            if adm_restores and not any(
                    eng.admission.tightened_models()
                    for eng, _ in fleet):
                break
            time.sleep(0.2)
        stop_trickle.set()
        for t in trickle_threads:
            t.join(timeout=10)
        adm_cleared = bool(adm_restores) and not any(
            eng.admission.tightened_models() for eng, _ in fleet)
        out["admission"] = {
            "tighten_fired": len(adm_tightens),
            "restore_fired": len(adm_restores),
            "cleared": adm_cleared,
            "flood_ok": flood_counts["ok"],
            "flood_shed": flood_counts["shed"],
        }
        log(f"selfdriving burn: tighten x{len(adm_tightens)}, flood "
            f"{flood_counts['ok']} ok / {flood_counts['shed']} shed, "
            f"cleared={adm_cleared}")

        # -- phase 3: hot-replica skew -> drift re-placement ------------------
        spurious = edges("fleet", "rebalance", probe_seq)
        if spurious:
            drifts = [{k: e.detail.get(k) for k in ("replica", "signals")}
                      for e in edges("fleet", "drift", probe_seq)]
            raise RuntimeError(
                "selfdriving: rebalance fired before the skew phase "
                f"(symmetric load misread as drift): {drifts}")
        c3 = jrnl.export(limit=0)["next_seq"]
        hot_counts = {"ok": 0, "err": 0}
        stop_hot = threading.Event()
        hot_eng = fleet[0][0]
        cold_eng = fleet[1][0]

        def hot_loop():
            # Six closed-loop callers on a 50 ms serial model keep ~5
            # calls queued: ~0.25 s of queue wait on the hot replica vs
            # ~0 on its peer. The router's background load poller picks
            # the skew up without any routed traffic, and the monitor's
            # damped wait median crosses threshold only once the skew
            # has persisted — exactly the hysteresis under test.
            while not stop_hot.is_set():
                try:
                    done, errs = direct_infer(hot_eng, "skew_net")
                    ok = done.wait(10) and not errs
                except Exception:  # noqa: BLE001 — unload races are fine
                    ok = False
                with flood_lock:
                    hot_counts["ok" if ok else "err"] += 1
                if not ok:
                    stop_hot.wait(0.05)

        def keeper_loop():
            # A light pulse keeps the idle replica genuinely serving
            # (not just idle-by-omission) through the skew phase.
            while not stop_hot.is_set():
                try:
                    done, _ = direct_infer(cold_eng, "skew_net")
                    done.wait(10)
                # tpulint: allow[swallowed-exception] pulse best-effort
                except Exception:  # noqa: BLE001
                    pass
                stop_hot.wait(0.1)

        hot_threads = [threading.Thread(target=hot_loop, daemon=True)
                       for _ in range(6)]
        hot_threads.append(threading.Thread(target=keeper_loop,
                                            daemon=True))
        for t in hot_threads:
            t.start()
        reb = wait_edges("fleet", "rebalance", c3, max(30.0, phase_s * 4))
        reb_done = wait_edges("fleet", "rebalance_done", c3, 30.0)
        stop_hot.set()
        for t in hot_threads:
            t.join(timeout=30)
        drift_events = edges("fleet", "drift", c3)
        if not reb or not reb_done:
            raise RuntimeError(
                f"selfdriving: drift loop incomplete (drift x"
                f"{len(drift_events)}, rebalance x{len(reb)}, done x"
                f"{len(reb_done)})")
        # Flap check: two more monitor windows — the cooldown must hold
        # the loop to the single rebalance it already executed.
        time.sleep(2.0)
        reb_all = edges("fleet", "rebalance", c3)
        last = router_srv.rebalancer.snapshot().get("last") or {}
        # Post-move serving: every model must still answer somewhere.
        hosting: dict = {}
        for model in ("sd_net", "burn_net", "skew_net"):
            ok_on = []
            for idx, (eng, _) in enumerate(fleet):
                try:
                    done, errs = direct_infer(eng, model)
                    if done.wait(10) and not errs:
                        ok_on.append(f"r{idx}")
                except Exception:  # noqa: BLE001 — unloaded is expected
                    pass
            hosting[model] = ok_on
        serving_after = all(hosting.values())
        out["rebalance"] = {
            "drift_events": len(drift_events),
            "fired": len(reb_all),
            "done": len(edges("fleet", "rebalance_done", c3)),
            "moves": last.get("moves"),
            "outcome": last.get("outcome"),
            "hosting": hosting,
            "serving_after": serving_after,
            "flap_free": len(reb_all) == 1,
            "hot_ok": hot_counts["ok"],
            "hot_err": hot_counts["err"],
        }
        log(f"selfdriving drift: drift x{len(drift_events)}, rebalance "
            f"x{len(reb_all)} ({last.get('moves')} moves, "
            f"{last.get('outcome')}), hosting {hosting}")

        # -- blackbox audit: exactly one bundle per induced incident ----------
        # Two incidents were induced (admission.tighten, fleet.rebalance);
        # each must yield one bundle per engine + one router bundle, and
        # the router fan-out must have deduped against the local captures
        # (shared journal) instead of double-writing.
        expect = 2 * (replicas + 1)
        bb_edges = wait_edges("blackbox", "captured", probe_seq, 20.0,
                              n=expect)
        # The in-process engines share one bundle directory (the ring IS
        # the directory), so count bundles by trigger across the ring:
        # exactly one per engine per incident, plus one router bundle
        # per incident in the router/ subring.
        ring = fleet[0][0].blackbox.store
        trig_counts: dict = {}
        for meta in ring.list():
            trig = ring.load(meta["id"]).get("trigger")
            trig_counts[trig] = trig_counts.get(trig, 0) + 1
        router_triggers = sorted(
            router_srv.blackbox.store.load(m["id"]).get("trigger")
            for m in router_srv.blackbox.store.list())
        capture_ms = [eng.blackbox.last_capture_ms for eng, _ in fleet
                      if eng.blackbox.last_capture_ms is not None]
        if router_srv.blackbox.last_capture_ms is not None:
            capture_ms.append(router_srv.blackbox.last_capture_ms)
        want = ["admission.tighten", "fleet.rebalance"]
        one_per_incident = (
            all(trig_counts.get(t) == replicas for t in want)
            and sum(trig_counts.values()) == 2 * replicas
            and router_triggers == want
            and len(bb_edges) == expect)
        if not one_per_incident:
            raise RuntimeError(
                "selfdriving: blackbox bundle audit failed — want "
                f"{replicas} engine bundle(s) per incident {want} plus "
                f"one router bundle each, got engines={trig_counts} "
                f"router={router_triggers} "
                f"captured_edges={len(bb_edges)}/{expect}")
        out["blackbox_bundles"] = (
            sum(trig_counts.values()) + len(router_triggers))
        out["blackbox_capture_ms"] = round(max(capture_ms), 3) \
            if capture_ms else None
        out["blackbox"] = {
            "engine_bundles": trig_counts,
            "router": router_triggers,
            "captured_edges": len(bb_edges),
            "one_per_incident": one_per_incident,
        }
        log(f"selfdriving blackbox: {out['blackbox_bundles']} bundles "
            f"({len(bb_edges)} captured edges, max capture "
            f"{out['blackbox_capture_ms']}ms)")

        # -- verdict ----------------------------------------------------------
        out["loops_closed"] = bool(
            tightens and restores
            and adm_tightens and adm_cleared
            and reb and reb_done and last.get("outcome") == "ok"
            and serving_after)
        out["fill_recovered"] = out["dispatch"]["fill_recovered"]
        out["bounded"] = bool(
            len(tightens) <= 2 * replicas
            and len(adm_tightens) <= 2 * replicas
            and len(reb_all) == 1
            and (last.get("moves") or 0) <= 4)
        log(f"selfdriving verdict: loops_closed={out['loops_closed']} "
            f"fill_recovered={out['fill_recovered']} "
            f"bounded={out['bounded']}")
        client.close()
        return out
    finally:
        if client is not None:
            try:
                client.close()
            # tpulint: allow[swallowed-exception] close is idempotent
            except Exception:  # noqa: BLE001
                pass
        if router_srv is not None:
            router_srv.stop()
        for eng, srv in fleet:
            srv.stop()
            eng.shutdown()
        import shutil
        shutil.rmtree(blackbox_dir, ignore_errors=True)


def bench_sequence_oldest(n_seq: int = 128, window_s: float = 3.0,
                          stability_pct: float = 0.10,
                          stable_needed: int = 3, max_windows: int = 10):
    """Stateful sequence stepping through the oldest-sequence arena batcher:
    steps of distinct live sequences share one XLA execution (state arena in
    HBM, gather->vmap(step)->scatter). Direct strategy measured 14 steps/s
    on the same workload; the wave batcher is the TPU answer to Triton's
    OLDEST strategy.

    Round-5 rework: this probe used to report a SINGLE post-warmup window,
    which is why its round-over-round record swung 372-1123 steps/s on
    unchanged code — the one probe still exempt from the stability
    criterion the rest of the bench adopted in round 3.  It now measures
    consecutive windows (statistics-delta per window) until `stable_needed`
    in a row agree within ±`stability_pct` on steps/s, same reference
    anchor as run_stable_load (inference_profiler.cc:503-547).

    Returns {steps_s, stable, avg_wave, windows: [...]}.
    """
    import numpy as np

    from client_tpu.engine import InferRequest, TpuEngine
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models.simple import SequenceAccumulateBackend

    backend = SequenceAccumulateBackend(
        name="seq_oldest", strategy="oldest",
        max_candidate_sequences=n_seq)
    repo = ModelRepository()
    repo.register_backend(backend)
    engine = TpuEngine(repo)

    def step(sid, v, **kw):
        return engine.infer(InferRequest(
            model_name="seq_oldest",
            inputs={"INPUT": np.array([v], np.int32)},
            sequence_id=sid, **kw), timeout_s=300)

    step(999_999, 0, sequence_start=True, sequence_end=True)  # compile b=1
    warm_s = 1.5  # ramping sequences compile the larger wave buckets here
    stop_evt = threading.Event()
    errs: list = []

    def worker(i):
        sid = 1 + i
        started = False
        try:
            while not stop_evt.is_set():
                step(sid, 1, sequence_start=not started)
                started = True
        except Exception as exc:  # noqa: BLE001
            errs.append(repr(exc))
            stop_evt.set()

    def snapshot():
        s = engine.model_statistics("seq_oldest")["model_stats"][0]
        return s["inference_count"], s["execution_count"]

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n_seq)]
    for t in threads:
        t.start()
    time.sleep(warm_s)
    windows: list[dict] = []
    stable = False
    steps_prev, waves_prev = snapshot()
    t_mark = time.monotonic()
    try:
        while len(windows) < max_windows and not stop_evt.is_set():
            time.sleep(window_s)
            now = time.monotonic()
            steps_now, waves_now = snapshot()
            elapsed = now - t_mark
            t_mark = now
            steps = steps_now - steps_prev
            waves = max(waves_now - waves_prev, 1)
            steps_prev, waves_prev = steps_now, waves_now
            rate = steps / elapsed
            windows.append({"steps_s": round(rate, 1),
                            "avg_wave": round(steps / waves, 1)})
            log(f"seq-oldest window {len(windows)}: {steps} steps in "
                f"{elapsed:.2f}s = {rate:.0f} steps/s, "
                f"avg wave {steps / waves:.1f}")
            if _tail_is_stable(windows, ("steps_s",),
                               stability_pct, stable_needed):
                stable = True
                break
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=120)
        engine.shutdown()
    if errs:
        raise RuntimeError(f"{len(errs)} sequence errors: {errs[:2]}")
    if not windows:
        raise RuntimeError("seq-oldest: no measurement windows completed")
    tail = windows[-min(stable_needed, len(windows)):]
    rate = sum(w["steps_s"] for w in tail) / len(tail)
    avg_wave = sum(w["avg_wave"] for w in tail) / len(tail)
    if not stable:
        log(f"seq-oldest: NOT stable after {len(windows)} windows "
            f"(reporting mean of final {len(tail)})")
    log(f"sequence-oldest: {rate:.0f} steps/s stable={stable} over "
        f"{n_seq} live sequences, avg wave {avg_wave:.1f}")
    return {"steps_s": rate, "stable": stable,
            "avg_wave": round(avg_wave, 1), "windows": windows}


@contextlib.contextmanager
def _gen_chunk_env(k: int):
    """Scope CLIENT_TPU_GEN_CHUNK around an engine build (the scheduler
    reads it at construction)."""
    saved = os.environ.get("CLIENT_TPU_GEN_CHUNK")
    os.environ["CLIENT_TPU_GEN_CHUNK"] = str(k)
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("CLIENT_TPU_GEN_CHUNK", None)
        else:
            os.environ["CLIENT_TPU_GEN_CHUNK"] = saved


def bench_generative(n_streams: int = 64, tokens: int = 32):
    """Continuous-batching generation (tiny_gpt) measured at BOTH decode
    dispatch modes — per-wave (chunk 1) and scanned 4-wave chunks — in one
    probe, so the chunking A/B is self-documenting (a dispatch-mode change
    can never masquerade as a perf delta).  The headline ``gen`` result is
    the FIXED chunked (production-posture) mode, labeled — not
    max-of-modes (best-of headlines were formally retired in round 4).
    Reports tok/s plus TTFT and inter-token latency
    percentiles, the streaming vocabulary the reference's profiler lacks
    (VERDICT r2 #4; schema extends
    /root/reference/src/c++/perf_analyzer/inference_profiler.h:71-118)."""
    out = {}
    for chunk in (1, 4):
        with _gen_chunk_env(chunk):
            res = _bench_generative_once(n_streams, tokens)
        res["chunk"] = chunk
        out[f"chunk{chunk}"] = res
    return {**out["chunk4"], **out}


def _bench_generative_once(n_streams: int, tokens: int):
    import numpy as np

    from client_tpu.engine import InferRequest, TpuEngine
    from client_tpu.models import build_repository
    from client_tpu.observability.profiler import profiler, reset_profiler

    # Fresh profiler epoch per dispatch mode BEFORE the engine builds (the
    # engine caches the instance at construction): the wave stats below
    # must describe THIS mode's decode waves, not the previous chunk
    # setting's.
    reset_profiler()
    # warmup=True: the generative scheduler precompiles every (prompt
    # bucket, wave bucket) executable up front — round 3 measured ~1-1.5s
    # XLA compiles landing mid-burst as the TTFT p99.
    engine = TpuEngine(build_repository(["tiny_gpt"]), warmup=True)

    def gen(prompt, n, counts, i, errs, ttft_ms, itl_ms):
        done = threading.Event()
        t_submit = time.monotonic_ns()
        t_last = [None]

        def cb(resp):
            now = time.monotonic_ns()
            if resp.error is not None:
                errs.append(str(resp.error))
                done.set()
            elif resp.final:
                done.set()
            else:
                if t_last[0] is None:
                    ttft_ms.append((now - t_submit) / 1e6)
                else:
                    itl_ms.append((now - t_last[0]) / 1e6)
                t_last[0] = now
                counts[i] += 1

        engine.async_infer(InferRequest(
            model_name="tiny_gpt",
            inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
            parameters={"max_tokens": n}), cb)
        if not done.wait(300):
            errs.append(f"stream {i} stalled")

    def burst(count, toks):
        counts = [0] * count
        errs: list[str] = []
        ttft_ms: list[float] = []
        itl_ms: list[float] = []
        threads = [threading.Thread(
            target=gen,
            args=([1 + i % 100] * 4, toks, counts, i, errs, ttft_ms, itl_ms))
            for i in range(count)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.monotonic() - t0
        if errs:
            raise RuntimeError(
                f"{len(errs)} generation streams failed: {errs[:2]}")
        # actual tokens delivered, not credit
        return sum(counts) / elapsed, sorted(ttft_ms), sorted(itl_ms)

    def pct(sorted_vals, q):
        if not sorted_vals:
            return None
        return sorted_vals[min(len(sorted_vals) - 1,
                               int(len(sorted_vals) * q))]

    burst(n_streams, 8)  # warmup: compiles prefill + wave buckets
    reset_profiler()  # measurement epoch: drop warmup-burst waves
    # (record_wave resolves the global dynamically, so post-reset waves
    # land in the fresh instance even though the engine cached the old
    # one at construction — snapshot below reads the fresh global too.)
    rate, ttft, itl = burst(n_streams, tokens)
    out = {
        "tok_s": round(rate, 1),
        "ttft_ms_p50": round(pct(ttft, 0.50), 1) if ttft else None,
        "ttft_ms_p99": round(pct(ttft, 0.99), 1) if ttft else None,
        "itl_ms_p50": round(pct(itl, 0.50), 2) if itl else None,
        "itl_ms_p99": round(pct(itl, 0.99), 2) if itl else None,
    }
    # Device-side decode-wave stats from the always-on profiler
    # (record_wave in engine/generative.py): duty cycle answers "was the
    # chip busy", wave_step_ms answers "what did one decode step cost" —
    # the pair that turns a tok/s delta into a diagnosis.  The p50 is
    # taken from the busiest (bucket, chunk) cell so a handful of ragged
    # tail waves can't speak for the steady state.
    try:
        psnap = profiler().snapshot(model="tiny_gpt")
        pm = next(iter(psnap["models"].values()), None)
        waves = (pm or {}).get("decode_waves") or []
        if waves:
            top = max(waves, key=lambda w: w["waves"])
            out["wave_step_ms_p50"] = top["wave_ms_p50"]
            out["wave_step_ms_p99"] = top["wave_ms_p99"]
            out["wave_bucket"] = top["bucket"]
        out["duty_cycle"] = psnap["duty_cycle"]
        rl = (pm or {}).get("roofline") or {}
        out["mfu"] = rl.get("mfu")
        out["mbu"] = rl.get("mbu")
    except Exception as exc:  # noqa: BLE001 — profiler must not sink bench
        log(f"generative wave stats unavailable: {exc}")
    engine.shutdown()
    log(f"generative: {n_streams} concurrent streams x {tokens} tokens = "
        f"{rate:.0f} tok/s, TTFT p50/p99 {out['ttft_ms_p50']}/"
        f"{out['ttft_ms_p99']}ms, ITL p50/p99 {out['itl_ms_p50']}/"
        f"{out['itl_ms_p99']}ms (continuous batching over the KV arena)")
    return out


def _native_pa() -> str:
    """Build ``tpu_perf_analyzer`` from the tracked ``native/`` sources
    (ninja: a no-op when up to date) and return its path.  ``native/build``
    is git-ignored, so a binary merely found there may belong to any
    commit; the harness a section runs is the one this command built.
    Raises — the section is recorded as failed — when it cannot build."""
    import subprocess

    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "native")
    for cmd in (["cmake", "-B", "build", "-G", "Ninja",
                 "-DCMAKE_BUILD_TYPE=Release"],
                ["ninja", "-C", "build", "tpu_perf_analyzer"]):
        try:
            proc = subprocess.run(cmd, cwd=native, capture_output=True,
                                  text=True, timeout=900)
        except (OSError, subprocess.TimeoutExpired) as exc:
            raise RuntimeError(
                f"native tpu_perf_analyzer not built: {exc}") from None
        if proc.returncode:
            raise RuntimeError(
                "native tpu_perf_analyzer not built: "
                f"`{' '.join(cmd)}` rc={proc.returncode}: "
                f"{(proc.stderr or proc.stdout)[-300:]}")
    return os.path.join(native, "build", "tpu_perf_analyzer")


def bench_gen_net(n_streams: int = 64, tokens: int = 32):
    """Generative serving through the FULL networked stack: native client
    (own gRPC over own HTTP/2) -> grpcio server -> engine, measured by
    tpu_perf_analyzer --generative.  Two points: coalesced (production
    posture — the writer merges a backlogged stream's tokens into one
    [k]-shaped message) and uncoalesced (one proto per token), so the
    served-path tax is captured A/B in the same run (VERDICT r4 weak #3:
    the reference exists to measure the served path, main.cc:645 onward;
    a served stack far under its engine is that metric failing).

    Writer ceiling measured on this host (simple_repeat flood, the pure
    writer path): ~8.8k msg/s uncoalesced vs ~96k rows/s coalesced (11x);
    coalescing self-throttles, merging only what has already queued."""
    import subprocess

    pa = _native_pa()

    from client_tpu.engine import TpuEngine
    from client_tpu.models import build_repository
    from client_tpu.server.grpc_server import GrpcInferenceServer

    # Served engine runs the chunked production posture (matches the
    # in-process probe's headline mode; labeled in the result).
    with _gen_chunk_env(4):
        engine = TpuEngine(build_repository(["tiny_gpt"]), warmup=True)
    srv = GrpcInferenceServer(engine, port=0).start()
    out: dict = {"chunk": 4}
    try:
        for label, extra in (("coalesced", []),
                             ("per_token", ["--generative-no-coalesce"])):
            # Per-point fault isolation (same contract as the seq_streaming
            # and ssd_net sweeps): one failed/hung point is recorded in-row
            # and must not erase the sibling point's evidence.
            cmd = [pa, "-m", "tiny_gpt", "-u", f"127.0.0.1:{srv.port}",
                   "-i", "grpc", "--generative",
                   "--generative-max-tokens", str(tokens),
                   "--shape", "INPUT_IDS:4",
                   "--concurrency-range", f"{n_streams}:{n_streams}",
                   "-p", "10000"]
            try:
                proc = subprocess.run(cmd + extra, capture_output=True,
                                      text=True, timeout=180)
            except subprocess.TimeoutExpired:
                out[label] = {"error": "timeout (180s)"}
                log(f"gen-net [{label}]: TIMEOUT — point recorded as "
                    "failed, probe continues")
                continue
            if proc.returncode != 0:
                out[label] = {
                    "error": f"rc={proc.returncode}: {proc.stderr[-200:]}"}
                log(f"gen-net [{label}]: rc={proc.returncode} — point "
                    "recorded as failed, probe continues")
                continue
            parsed = None
            for ln in proc.stdout.splitlines():
                ln = ln.strip()
                if ln.startswith("{"):
                    try:
                        parsed = json.loads(ln)
                    except json.JSONDecodeError:
                        continue  # brace-prefixed diagnostic, not the result
            if parsed is None:
                out[label] = {
                    "error": f"no JSON line in output: {proc.stdout[-200:]}"}
                log(f"gen-net [{label}]: no JSON result — point recorded "
                    "as failed, probe continues")
                continue
            out[label] = parsed
            log(f"gen-net [{label}]: {parsed['tok_s']} tok/s, TTFT p50 "
                f"{parsed['ttft_us_p50'] / 1e3:.0f}ms, ITL p50 "
                f"{parsed['itl_us_p50'] / 1e3:.2f}ms "
                f"({n_streams} streams x {tokens} tokens, native client)")
        if all(isinstance(v, dict) and "error" in v
               for k, v in out.items() if k != "chunk"):
            raise RuntimeError(f"every gen-net point failed: {out}")
        return out
    finally:
        srv.stop()
        engine.shutdown()


def bench_seq_streaming(concurrencies=(16, 32, 64, 128)):
    """Sequence stepping through the harness's --streaming mode, swept over
    concurrency to find the knee (VERDICT r4 #6): per point, stable
    steps/s plus wave batching efficiency (steps/execution) from the
    server-side statistics delta.  Serves the OLDEST-strategy variant —
    the arena wave batcher the in-process seq_oldest headline measures —
    so the networked-vs-in-process comparison is one variable (the wire),
    not two.  Reference driving loop:
    /root/reference/src/c++/perf_analyzer/main.cc:610-748."""
    import re
    import subprocess

    pa = _native_pa()

    from client_tpu.engine import TpuEngine
    from client_tpu.server.grpc_server import GrpcInferenceServer

    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models.simple import SequenceAccumulateBackend

    # Arena capacity: 2x the sweep's top concurrency.  At cap == conc the
    # top point fails on sequence ROLLOVER — the harness ends a sequence
    # (16 steps) and immediately starts its replacement id, so for a
    # moment conc+1 candidates are live and the oldest gets evicted
    # mid-flight ("request without start flag for an inactive sequence").
    # The registry default of 64 would 429 the upper points outright and
    # change two variables at once.
    model = "simple_sequence_oldest"
    backend = SequenceAccumulateBackend(
        name=model, strategy="oldest",
        max_candidate_sequences=max(2 * max(concurrencies), 128))
    repo = ModelRepository()
    repo.register_backend(backend)
    engine = TpuEngine(repo)
    # Every streaming RPC holds a grpcio handler-pool thread for its
    # lifetime; the default pool (64) deadlocks the c64/c128 sweep points
    # (observed round 5: the c64 point hung its full 300 s timeout).
    srv = GrpcInferenceServer(engine, port=0,
                              max_workers=max(concurrencies) + 32).start()
    out: dict = {}
    try:
        for conc in concurrencies:
            def stats():
                s = engine.model_statistics(model)["model_stats"][0]
                return s["inference_count"], s["execution_count"]

            s0, w0 = stats()
            cmd = [pa, "-m", model,
                   "-u", f"127.0.0.1:{srv.port}",
                   "--service-kind", "tpu_grpc", "--streaming",
                   "-p", "4000", "-r", "8", "-s", "70",
                   "--sequence-length", "16",
                   "--max-threads", str(max(conc, 16)),
                   "--concurrency-range", f"{conc}:{conc}"]
            # Per-point fault isolation: one hung/failed sweep point must
            # not erase the points already measured (round-5: the c64
            # point hit a pool deadlock and took the whole sweep's
            # evidence with it).  The failure is recorded in-row instead.
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=300)
            except subprocess.TimeoutExpired:
                out[f"c{conc}"] = {"error": "timeout (300s)"}
                log(f"seq-streaming c{conc}: TIMEOUT — point recorded as "
                    "failed, sweep continues")
                continue
            if proc.returncode != 0:
                out[f"c{conc}"] = {
                    "error": f"rc={proc.returncode}: {proc.stderr[-200:]}"}
                log(f"seq-streaming c{conc}: rc={proc.returncode} — point "
                    "recorded as failed, sweep continues")
                continue
            s1, w1 = stats()
            m = re.findall(r"Throughput:\s*([\d.]+)", proc.stdout)
            ips = float(m[-1]) if m else None
            waves = max(w1 - w0, 1)
            out[f"c{conc}"] = {
                "steps_s": ips,
                "steps_per_execution": round((s1 - s0) / waves, 1)}
            log(f"seq-streaming c{conc}: {ips} steps/s, "
                f"{(s1 - s0) / waves:.1f} steps/execution")
        return out
    finally:
        srv.stop()
        engine.shutdown()


def bench_ssd_net(concurrency: int = 64, window_ms: int = 5000):
    """THE north-star measurement (BASELINE.json, driver-provided):
    perf_analyzer inferences/sec + p99 latency on ssd_mobilenet_v2 with
    tpu-shm tensor I/O, against the networked gRPC endpoint — the exact
    config the reference measures with cudashm on H100
    (load_manager.cc:287-446).  Until round 5 this existed only as an
    in-process capi A/B (shm_ab) and a raw device step (device_steady);
    this probe runs the reference's own harness shape: native client,
    real wire, shm regions registered over the control plane, pa's
    3-window stability criterion doing the stabilizing (-s, p99-gated).

    Two points, one variable (the data plane): ``--shared-memory tpu``
    vs inline ``none`` — same model, same concurrency, same windows.
    """
    import csv as _csv
    import subprocess
    import tempfile

    pa = _native_pa()

    from client_tpu.engine import TpuEngine
    from client_tpu.models import build_repository
    from client_tpu.server.grpc_server import GrpcInferenceServer

    engine = TpuEngine(build_repository(["ssd_mobilenet_v2_tpu"]),
                       warmup=True)
    srv = GrpcInferenceServer(engine, port=0,
                              max_workers=concurrency + 32).start()
    out: dict = {}
    try:
        for plane in ("tpu", "none"):
            with tempfile.NamedTemporaryFile(
                    mode="r", suffix=".csv", delete=False) as tf:
                csv_path = tf.name
            cmd = [pa, "-m", "ssd_mobilenet_v2_tpu",
                   "-u", f"127.0.0.1:{srv.port}", "-i", "grpc",
                   "-p", str(window_ms), "-r", "10", "-s", "25",
                   "--percentile", "99",
                   "--concurrency-range", f"{concurrency}:{concurrency}",
                   "-f", csv_path]
            if plane != "none":
                cmd += ["--shared-memory", plane]
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=600)
            except subprocess.TimeoutExpired:
                out[plane] = {"error": "timeout (600s)"}
                log(f"ssd-net [{plane}]: TIMEOUT — point recorded as "
                    "failed, probe continues")
                continue
            if proc.returncode != 0:
                out[plane] = {
                    "error": f"rc={proc.returncode}: {proc.stderr[-200:]}"}
                log(f"ssd-net [{plane}]: rc={proc.returncode} — point "
                    "recorded as failed, probe continues")
                continue
            with open(csv_path) as f:
                rows = list(_csv.reader(f))
            header, row = rows[0], rows[1]
            ips = float(row[header.index("Inferences/Second")])
            p99_us = float(row[header.index("p99 latency")])
            out[plane] = {"ips": round(ips, 1), "p99_us": round(p99_us, 1)}
            os.unlink(csv_path)
            log(f"ssd-net [{plane}]: {ips:.1f} infer/s, p99 "
                f"{p99_us / 1e3:.0f} ms (conc {concurrency}, b16 dynamic "
                "batching, native grpc client)")
        return out
    finally:
        srv.stop()
        engine.shutdown()


def bench_router(concurrency: int = 32):
    """Router scale-out probe: aggregate infer/sec + p99 through the
    standalone L7 router at replica count 1 vs 2.

    Replicas are real ``python -m client_tpu.server`` subprocesses —
    separate processes, separate GILs, separate engines — so the
    2-replica point measures genuine scale-out, not thread interleaving.
    BOTH points run through the router (same proxy hop, same client), so
    the 2v1 ratio isolates exactly one variable: the replica count.
    Acceptance: 2-replica ips >= 1.6x 1-replica with p99 no worse.

    The record carries ``host_cpus``: on a host with too few cores for
    two replicas + router + client (e.g. a 1-core CI container) the 2v1
    ratio measures core contention, not scale-out — the >=1.6x bar only
    means something when each replica gets its own compute.
    """
    import subprocess

    import numpy as np

    import client_tpu.http as httpclient
    from client_tpu.engine.backend_init import platform
    from client_tpu.router import Replica, Router, RouterHttpServer

    # A chip belongs to one process, and this parent holds it (preflight
    # touched JAX): a replica subprocess would fail or hang on the TPU, or
    # worse come up on the CPU and be measured as a replica.  Until the
    # replicas can each own a chip the section does not run there.
    if platform() == "tpu":
        raise _NotRun("needs one chip per replica")

    def spawn():
        proc = subprocess.Popen(
            [sys.executable, "-m", "client_tpu.server", "--zoo", "simple",
             "--http-port", "0", "--no-grpc"],
            # The replicas run where the parent runs, by name.
            env=dict(os.environ, JAX_PLATFORMS=platform()),
            stderr=subprocess.PIPE, text=True)
        url = None
        deadline = time.monotonic() + 120
        lines = []
        for line in proc.stderr:
            lines.append(line)
            if line.startswith("serving http at "):
                url = line.split("serving http at ", 1)[1].strip()
                break
            if time.monotonic() > deadline:
                break
        if url is None:
            proc.kill()
            raise RuntimeError("router bench: replica never came up:\n"
                               + "".join(lines[-20:]))
        # Drain remaining stderr so the pipe never fills and blocks the
        # replica mid-benchmark.
        threading.Thread(target=proc.stderr.read, daemon=True).start()
        return proc, url

    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)

    procs = []
    out: dict = {}
    try:
        procs = [spawn(), spawn()]
        for count in (1, 2):
            router = Router([Replica(url) for _, url in procs[:count]],
                            seed=1234)
            srv = RouterHttpServer(router, port=0).start()
            client = httpclient.InferenceServerClient(
                srv.url, concurrency=concurrency)
            i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
            i0.set_data_from_numpy(a)
            i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
            i1.set_data_from_numpy(b)

            def infer_fn():
                client.infer("simple", [i0, i1])

            try:
                res = run_stable_load(infer_fn, concurrency,
                                      tag=f"router-x{count}")
            finally:
                client.close()
                srv.stop()
            ok_counts = router.metrics.requests._children
            spread = {
                r.id: int(child.v)
                for r in router.replicas
                if (child := ok_counts.get((r.id, "ok"))) is not None
            } if count == 2 else None
            out[f"x{count}"] = {
                "ips": round(res["ips"], 1),
                "p99_us": round(res["p99_us"], 1),
                "stable": res["stable"],
                **({"spread": spread} if spread else {}),
            }
            log(f"router x{count}: {res['ips']:.1f} infer/s, "
                f"p99 {res['p99_us'] / 1e3:.1f}ms"
                + (f", spread {spread}" if spread else ""))
        out["scale_2v1"] = round(out["x2"]["ips"]
                                 / max(out["x1"]["ips"], 1e-9), 3)
        out["host_cpus"] = len(os.sched_getaffinity(0))
        log(f"router scale-out 2v1: {out['scale_2v1']:.2f}x "
            f"(host_cpus={out['host_cpus']})")
        if out["host_cpus"] < 4:
            log("router: host has too few cores for 4 processes — "
                "scale_2v1 reflects core contention, not scale-out")
        return out
    finally:
        for proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)


def bench_device_steady():
    """Steady-state device throughput for the flagship vision models
    (BASELINE.json configs 1/3/4) — pipelined device step via back-to-back
    dispatch, same methodology as the BERT MFU probe, emitted here so the
    driver-captured BENCH json carries them (VERDICT r2 #10)."""
    import jax
    import numpy as np

    from client_tpu.engine.model import Model
    from client_tpu.models import _import_all, _REGISTRY

    from client_tpu.protocol.dtypes import wire_to_np_dtype

    _import_all()
    specs = [("ssd_mobilenet_v2_tpu", 16), ("resnet50", 32),
             ("densenet_onnx", 16)]
    out = {}
    for name, batch in specs:
        try:
            backend = _REGISTRY[name]()
            backend.config.batch_buckets = [batch]
            model = Model(backend)
            inputs = {}
            for spec in backend.config.input:
                shape = (batch,) + tuple(int(d) for d in spec.dims)
                dt = wire_to_np_dtype(spec.data_type)
                if np.issubdtype(dt, np.integer):
                    arr = np.random.randint(0, 255, size=shape).astype(dt)
                else:
                    arr = np.random.rand(*shape).astype(dt)
                inputs[spec.name] = arr
            model.execute(inputs, batch_size=batch)  # compile
            apply_j = model.raw_apply()
            staged = {k: jax.device_put(v) for k, v in inputs.items()}
            first_out = apply_j(staged)
            jax.block_until_ready(first_out)
            step = None
            n = 50
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(apply_j(staged))
                t_one = time.perf_counter() - t0
                t0 = time.perf_counter()
                r = None
                for _ in range(n):
                    r = apply_j(staged)
                jax.block_until_ready(r)
                t_total = time.perf_counter() - t0
                cand = max(t_total - t_one, 1e-9) / max(n - 1, 1)
                step = cand if step is None else min(step, cand)
            img_s = batch / step
            out[name] = {"batch": batch, "step_ms": round(step * 1e3, 3),
                         "img_s": round(img_s, 1)}
            log(f"device-steady {name}: b{batch} step {step * 1e3:.2f}ms = "
                f"{img_s:.0f} img/s")
        except Exception as exc:  # noqa: BLE001 — report the rest
            log(f"device-steady {name} failed: {exc!r}")
            out[name] = None
    return out


# Shared analytic denominator — the definition lives in the roofline
# module (one source for bench, the profiler plane, and mfu_diag); the
# re-export keeps `from bench import bert_flops_per_example` working.
from client_tpu.observability.roofline import (  # noqa: E402
    bert_flops_per_example,
)


# bench_bert_mfu probe state, keyed by batch size (see the cache note in
# its body).
_BERT_PROBE_CACHE: dict = {}


def make_bert_feedback_scan(apply_fn, mask_dev, vocab: int = 30522,
                            length: int = 100):
    """THE dependent-feedback scan construction (single source — bench's
    MFU probe and tools/mfu_diag.py's validator import this same builder,
    so the construction the diag validates is the construction the
    headline trusts).

    The next step's ids derive from a full-tensor reduction of this
    step's logits: iterations serialize on a real data dependence, and
    XLA can neither pipeline them apart nor slice/DCE any of the forward
    pass.  The per-step overhead added by the feedback itself is one
    reduce + one broadcast-add over int32 ids — nanoseconds against a
    ms-scale step.  Returns (jitted_fn(ids0), length).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def feed(ids0):
        def body(ids_c, _):
            o = apply_fn({"input_ids": ids_c, "attention_mask": mask_dev})
            sig = jnp.sum(o["logits"].astype(jnp.float32))
            bump = jnp.clip(sig, 0.0, 1.0).astype(jnp.int32)
            return (ids_c + bump) % vocab, None

        out, _ = lax.scan(body, ids0, None, length=length)
        return out

    return feed, length


def bench_bert_mfu(batch: int = 8, iters: int = 30, pipeline_n: int = 100,
                   trace_dir: str | None = None):
    """Flagship BERT-base batch-8 at the Model level (no scheduler).

    Two numbers with different denominators:

    - **device step** (the MFU numerator): a dependent-feedback
      ``lax.scan`` inside ONE jitted executable — the next step's ids
      derive from a full-tensor reduction of this step's logits, so
      iterations serialize on a real data dependence and XLA can neither
      pipeline them apart nor slice/DCE any of the forward pass.  The
      construction is validated by the matmul-chain control in
      ``tools/mfu_diag.py`` (167 TFLOP/s sustained, 85% of the v5e peak,
      on an op whose cost is independently known); the
      optimization-barrier scan variant FAILED that control (5x peak —
      XLA slices the probe signal) and is not used anywhere.
    - **dispatch step**: N jitted executions dispatched back-to-back with
      one final host fetch, total/N.  Each dispatch pays a command
      round trip (0.8-1.5 ms measured 2026-07-31), so this is the
      transport-inclusive upper bound — what THIS host can drive, not what
      the chip can do.  Round-5 diag decomposition: dispatch 1.9-2.8 ms =
      feedback step 1.38 ms + per-dispatch overhead.
    - **e2e step**: one stage+execute+fetch round trip per call, the
      per-request serving latency on this transport.

    Returns a dict; ``step_s`` (and the MFU derived from it) is the
    feedback-scan step when measured, else the dispatch step (smoke mode
    skips the scan compile), with ``step_method`` naming which.
    """
    import numpy as np

    # Probe state (model, staged inputs, jitted fns) is cached per batch
    # size: mfu_study calls this 5+ times and every rebuild re-traces (and
    # on an unwarmed XLA cache recompiles) both the forward and the
    # 100-step scan — minutes of chip time for zero
    # measurement value.
    cached = _BERT_PROBE_CACHE.get(batch)
    if cached is None:
        from client_tpu.engine.model import Model
        from client_tpu.models.bert import BertBackend

        log("building BERT-base (random weights, bf16)...")
        backend = BertBackend(max_batch_size=batch)
        backend.config.batch_buckets = [batch]  # compile only this bucket
        model = Model(backend)
        ids = np.random.randint(0, 30522, size=(batch, 128), dtype=np.int32)
        mask = np.ones((batch, 128), dtype=np.int32)
        inputs = {"input_ids": ids, "attention_mask": mask}
        cached = _BERT_PROBE_CACHE[batch] = {
            "model": model, "inputs": inputs, "feed": None}
        t0 = time.monotonic()
        model.execute(inputs, batch_size=batch)  # compile
        log(f"bert: bucket={batch} compiled+run in "
            f"{time.monotonic() - t0:.1f}s")
    model = cached["model"]
    inputs = cached["inputs"]

    times = []
    for _ in range(iters):
        _, phases = model.execute_timed(inputs, batch_size=batch)
        times.append((phases.output_end - phases.start) / 1e9)
    times.sort()
    # median end-to-end (stage+infer+fetch) — what serving actually gets
    e2e_step = times[len(times) // 2]

    # Pipelined device step: params/inputs device-resident, N async
    # dispatches, one fetch. Subtract one fetch round trip (measured as the
    # n=1 time) so the fixed transport latency isn't amortized into the
    # step; best of two passes (shared dev chip).
    import jax

    apply_j = model.raw_apply()
    staged = {k: jax.device_put(v) for k, v in inputs.items()}
    np.asarray(apply_j(staged)["logits"])  # warm
    step = None
    # Best of three passes: the dev chip is shared, and one pass can land
    # inside someone else's burst.
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(apply_j(staged)["logits"])
        t_one = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = None
        for _ in range(pipeline_n):
            r = apply_j(staged)
        np.asarray(r["logits"])
        t_total = time.perf_counter() - t0
        cand = max(t_total - t_one, 1e-9) / max(pipeline_n - 1, 1)
        step = cand if step is None else min(step, cand)

    # Dependent-feedback scan (the trusted device step — see docstring).
    # Smoke/CI runs skip it: the scan compile is the dominant cost on CPU
    # and the smoke config can never enter a baseline pool anyway.
    feedback_step = None
    if not os.environ.get("BENCH_SMOKE"):
        feed, scan_len = cached.get("feed") or (None, 0)
        if feed is None:
            feed, scan_len = make_bert_feedback_scan(
                apply_j, staged["attention_mask"])
            feed(staged["input_ids"]).block_until_ready()  # compile
            cached["feed"] = (feed, scan_len)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            feed(staged["input_ids"]).block_until_ready()
            t = (time.perf_counter() - t0) / scan_len
            best = t if best is None else min(best, t)
        feedback_step = best

    if trace_dir:
        # Same staged workload, one profiled pipelined pass: the trace
        # artifact names the top device ops behind the measured step.
        with jax.profiler.trace(trace_dir):
            r = None
            for _ in range(min(pipeline_n, 30)):
                r = apply_j(staged)
            np.asarray(r["logits"])
        log(f"bert: profiler trace written to {trace_dir}")

    flops = bert_flops_per_example() * batch
    mfu_step = feedback_step if feedback_step is not None else step
    achieved = flops / mfu_step
    peak = peak_flops()
    mfu = achieved / peak if peak else None
    method = "feedback-scan" if feedback_step is not None else "dispatch-loop"
    log(f"bert: device step {mfu_step * 1e3:.2f}ms [{method}] "
        f"({achieved / 1e12:.2f} TFLOP/s), dispatch step "
        f"{step * 1e3:.2f}ms, e2e step {e2e_step * 1e3:.2f}ms"
        + (f", MFU {mfu * 100:.1f}% of {peak / 1e12:.0f} TFLOP/s peak"
           if peak else " (no peak known for platform; MFU omitted)"))
    return {"ips": batch / e2e_step, "mfu": mfu, "step_s": mfu_step,
            "e2e_s": e2e_step, "dispatch_step_s": step,
            "step_method": method}


def main():
    sys.exit(_run_with_watchdog(_main))


def _run_with_watchdog(target, metric: str = "inproc_simple_ips",
                       unit: str = "infer/sec"):
    """Run ``target`` under the run-level watchdog; returns its exit code
    (None = 0).

    A device call can hang indefinitely (observed round 4: jax.devices()
    blocked for >30 min).  Device waits release the GIL, so a timer thread
    can still emit the sections that completed — the driver then records a
    partial (but honest) BENCH json instead of a timeout with no output —
    and exit NONZERO: a partial is not a green run.  Every bench entry
    point (the driver run AND --mfu-study) runs under this."""
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", "1500"))
    finished = threading.Event()

    def emit_partial(status: str, reason: str | None = None):
        # ONE constructor for every failure-path emit (watchdog partial and
        # crash) so the two schemas cannot diverge by hand-synchronization.
        # Self-describing (VERDICT r4 #7): consumers must never have to
        # infer "0.0 means outage" — completed sections are already in
        # _RESULT (each probe merges in as it finishes and has persisted to
        # BENCH_HISTORY independently), the filter tag says why a short run
        # is short, and `status` names the failure mode.
        partial = dict(_RESULT)
        partial.setdefault("metric", metric)
        partial.setdefault("unit", unit)
        # A failure before the first section completes leaves _RESULT
        # empty; the driver schema still needs a numeric value field.
        partial.setdefault("value", 0.0)
        partial["partial"] = True
        partial["status"] = status
        if reason is not None:
            partial["reason"] = reason
        try:
            sections_env = _sections_tag()
        except BaseException:  # noqa: BLE001 — when the crash being
            # reported IS the filter validation error, re-validating here
            # would re-raise it and kill the emit; fall back to the raw env
            sections_env = os.environ.get("BENCH_SECTIONS", "").strip()
        if sections_env:
            partial["sections"] = sections_env
        if _FAILED:
            partial["sections_failed"] = sorted(set(_FAILED))
        partial["sections_completed"] = sorted(
            k for k in partial
            if k not in ("metric", "unit", "value", "partial", "status",
                         "reason", "sections", "sections_completed",
                         "sections_failed", "sections_skipped",
                         "sections_not_run", "section_s", "platform",
                         "device_kind", "device_count"))
        _append_history({"probe": "run-status", "status": status,
                         **({"reason": reason} if reason else {}),
                         **({"sections": sections_env} if sections_env
                            else {}),
                         **({"sections_failed": partial["sections_failed"]}
                            if _FAILED else {}),
                         "sections_completed":
                             partial["sections_completed"]})
        _emit(partial)

    def watchdog():
        if finished.wait(deadline_s):
            return
        log(f"WATCHDOG: bench exceeded {deadline_s:.0f}s (device hang?); "
            "emitting partial results")
        emit_partial("partial-outage")
        os._exit(1)

    threading.Thread(target=watchdog, daemon=True).start()
    try:
        return target()
    except BaseException as exc:  # noqa: BLE001 — emit before propagating
        # A crash (not a hang) still owes the driver its one JSON line:
        # completed sections plus the crash reason instead of leaving
        # stdout empty with a nonzero rc.
        emit_partial("error", reason=repr(exc)[:300])
        raise
    finally:
        finished.set()


# The ONE result dict: _main fills it section by section; the final emit
# and the watchdog's partial emit both print THIS dict, so the schema
# cannot diverge between the two paths.
_RESULT: dict = {}
_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _emit(d: dict) -> None:
    """Print the single stdout JSON line exactly once — the watchdog firing
    while _main is mid-final-print must not produce two lines."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        print(json.dumps(d), flush=True)


def _main():
    _sections_filter()  # validate BENCH_SECTIONS before spending backend init
    devices = preflight()
    platform = devices[0].platform
    config = f"mb{BENCH_MAX_BATCH}-c{BENCH_CONCURRENCY}-i{BENCH_INSTANCES}"
    # Every emit — final, partial or crash — names the device the numbers
    # came from, as JAX reports it.
    _RESULT.update({"platform": platform,
                    "device_kind": devices[0].device_kind,
                    "device_count": len(devices)})
    # Every per-probe history record carries these tags so vs_baseline
    # filtering works on probe records as well as run aggregates.
    _HIST_CTX.update({"platform": platform,
                      "device_kind": devices[0].device_kind,
                      "config": config})

    def _rec_simple(s):
        _RESULT.update({"metric": "inproc_simple_ips",
                        "value": round(s["ips"], 2), "unit": "infer/sec",
                        "p99_us": round(s["p99_us"], 1),
                        "stable": s["stable"],
                        "windows": s["windows"]})
        extra = {}
        for k in ("hist_p50_us", "hist_p99_us", "fill_ratio", "duty_cycle",
                  "xla_compiles", "pad_waste_device_s",
                  "timeseries_samples", "census_attr_fraction"):
            if k in s:
                _RESULT[k] = s[k]
                extra[k] = s[k]
        # Same rounding as _RESULT: a run's history record and its final
        # JSON must agree exactly — vs_baseline and the watchdog tests
        # compare the two.
        _append_history({"probe": "simple", "metric": "inproc_simple_ips",
                         "value": round(s["ips"], 2),
                         "p99_us": round(s["p99_us"], 1),
                         "stable": s["stable"], "windows": s["windows"],
                         **extra})

    def _rec_bert(b):
        _RESULT["bert_b8_ips"] = round(b["ips"], 2)
        _RESULT["bert_b8_step_ms"] = round(b["step_s"] * 1e3, 3)
        _RESULT["bert_b8_step_method"] = b["step_method"]
        _RESULT["bert_b8_dispatch_step_ms"] = round(
            b["dispatch_step_s"] * 1e3, 3)
        _RESULT["bert_b8_e2e_ms"] = round(b["e2e_s"] * 1e3, 3)
        if b["mfu"] is not None:
            _RESULT["bert_b8_mfu"] = round(b["mfu"], 4)
        _append_history({"probe": "bert", "bert_ips": b["ips"],
                         "mfu": b["mfu"], "step_ms": b["step_s"] * 1e3,
                         "step_method": b["step_method"],
                         "dispatch_step_ms": b["dispatch_step_s"] * 1e3,
                         "e2e_ms": b["e2e_s"] * 1e3})

    def _rec_shm_ab(shm_ab):
        _RESULT["shm_ab"] = shm_ab
        tpushm_ips = (shm_ab.get("tpu") or {}).get("ips")
        if tpushm_ips is not None:
            _RESULT["tpushm_ips"] = round(tpushm_ips, 2)
        _append_history({"probe": "shm_ab", "shm_ab": shm_ab})

    def _rec_shm_ab_large(r):
        _RESULT["shm_ab_large"] = r
        _append_history({"probe": "shm_ab_large", "shm_ab_large": r})

    def _rec_shm_ring(r):
        _RESULT["shm_ring"] = r
        # Top-level p99 = the ring path's tail so bench_summary --check
        # gates the new data plane like every other probe.
        _append_history({"probe": "shm_ring",
                         "p99_us": (r.get("ring") or {}).get("p99_us"),
                         "fill_ratio": r.get("fill_ratio"),
                         "duty_cycle": r.get("duty_cycle"),
                         "shm_ring": r})

    def _rec_shm_fanin(r):
        _RESULT["shm_fanin"] = r
        # Top-level p99 = the LIVE plane's tail while shadow replay runs —
        # what bench_summary --check gates: shadow traffic regressing the
        # live p99 is exactly the failure this probe exists to catch.
        _append_history({"probe": "shm_fanin",
                         "p99_us": (r.get("live_shadow") or {}).get("p99_us"),
                         "fanin_vs_single_ips": r.get("fanin_vs_single_ips"),
                         "shadow_p99_ratio": r.get("shadow_p99_ratio"),
                         "shm_fanin": r})

    def _rec_gauntlet(r):
        _RESULT["gauntlet"] = r
        # Top-level p99 = the interactive tenant's tail THROUGH the
        # flash crowd — the number the QoS system exists to defend;
        # the evidence fields are what bench_summary --check verifies
        # (SLO held, governor fired AND cleared).
        _append_history({"probe": "gauntlet",
                         "p99_us": (r.get("flash") or {}).get("p99_us"),
                         "slo_pass": r.get("slo_pass"),
                         "throttle_fired": (r.get("flash") or {}).get(
                             "throttle_fired"),
                         "throttle_cleared": (r.get("flash") or {}).get(
                             "throttle_cleared"),
                         "gauntlet": r})

    def _rec_selfdriving(r):
        _RESULT["selfdriving"] = r
        # Top-level p99 = the routed baseline before any chaos — the
        # plain-serving tail this fleet config yields; the evidence
        # fields are what bench_summary --check verifies (every loop
        # fired AND cleared, fill recovered, actuation bounded).
        _append_history({"probe": "selfdriving",
                         "p99_us": (r.get("baseline") or {}).get("p99_us"),
                         "loops_closed": r.get("loops_closed"),
                         "fill_recovered": r.get("fill_recovered"),
                         "bounded": r.get("bounded"),
                         "blackbox_bundles": r.get("blackbox_bundles"),
                         "blackbox_capture_ms":
                             r.get("blackbox_capture_ms"),
                         "selfdriving": r})

    def _rec_seq(s):
        _RESULT["seq_oldest_steps_s"] = round(s["steps_s"], 1)
        _RESULT["seq_oldest"] = s
        _append_history({"probe": "seq_oldest",
                         "seq_oldest_steps_s": s["steps_s"],
                         "stable": s["stable"], "avg_wave": s["avg_wave"],
                         "windows": s["windows"]})

    def _rec_gen(g):
        _RESULT["gen"] = g
        _RESULT["gen_tok_s"] = g["tok_s"]
        # Top-level p99 (inter-token latency, us) so bench_summary --check
        # gates the generative path's tail like every other probe.
        itl_p99 = g.get("itl_ms_p99")
        _append_history({"probe": "gen", "gen": g,
                         "p99_us": (round(itl_p99 * 1000, 1)
                                    if itl_p99 else None),
                         # hoisted so the summary's efficiency line (and
                         # eye-balling the raw JSON) sees them per run
                         **{k: g[k] for k in ("duty_cycle",
                                              "wave_step_ms_p50")
                            if k in g}})

    def _rec_device_steady(r):
        _RESULT["device_steady"] = r
        _append_history({"probe": "device_steady", "device_steady": r})

    def _rec_gen_net(r):
        _RESULT["gen_net"] = r
        _append_history({"probe": "gen_net", "gen_net": r})

    def _rec_seq_streaming(r):
        _RESULT["seq_streaming"] = r
        _append_history({"probe": "seq_streaming", "seq_streaming": r})

    def _rec_ssd_net(r):
        _RESULT["ssd_net"] = r
        _append_history({"probe": "ssd_net", "ssd_net": r})

    def _rec_autotune(r):
        _RESULT["autotune"] = r
        _append_history({"probe": "autotune", **r})

    def _rec_dlrm(r):
        _RESULT["dlrm"] = r
        _RESULT["dlrm_ips"] = r["ips"]
        if r.get("cache_hit_rate") is not None:
            # hoisted so the summary's efficiency line sees it per run
            _RESULT["cache_hit_rate"] = r["cache_hit_rate"]
        _append_history({"probe": "dlrm", "dlrm_ips": r["ips"],
                         "p99_us": r["p99_us"],
                         "fill_ratio": r.get("fill_ratio"),
                         "cache_hit_rate": r.get("cache_hit_rate"),
                         "sharded_parity": r.get("sharded_parity"),
                         "dlrm": r})

    def _rec_router(r):
        _RESULT["router"] = r
        # Top-level p99 of the 2-replica point so bench_summary --check
        # gates the router path like every other probe.
        _append_history({"probe": "router",
                         "p99_us": (r.get("x2") or {}).get("p99_us"),
                         **r})

    # Section order = re-capture priority (VERDICT r4 #1c): after the
    # headline, the rows whose evidence is least established run first, so
    # a mid-run outage (or the time-budget skip) costs the least.  As of
    # round 5 the in-process sections have committed driver artifacts
    # (artifacts/r05) while the networked sections do not — so the
    # networked ones run right after the headline.  _run_section handles
    # filter / budget / deadline / failure bookkeeping uniformly; record
    # closures run outside the armed window.
    simple = _run_section("simple", bench_inproc_simple, _rec_simple)
    ips = simple["ips"] if simple else None
    p99_us = simple["p99_us"] if simple else None
    _run_section("gen_net", bench_gen_net, _rec_gen_net)
    _run_section("seq_streaming", bench_seq_streaming, _rec_seq_streaming)
    _run_section("ssd_net", bench_ssd_net, _rec_ssd_net)
    _run_section("router", bench_router, _rec_router)
    _run_section("autotune", bench_autotune, _rec_autotune)
    _run_section("dlrm", bench_dlrm, _rec_dlrm)
    bres = _run_section("bert", bench_bert_mfu, _rec_bert)
    bert_ips = bres["ips"] if bres else None
    mfu = bres["mfu"] if bres else None
    _run_section("shm_ab", bench_shm_ab, _rec_shm_ab)
    _run_section("shm_ab_large", bench_shm_ab_large, _rec_shm_ab_large)
    _run_section("shm_ring", bench_shm_ring, _rec_shm_ring)
    _run_section("shm_fanin", bench_shm_fanin, _rec_shm_fanin)
    _run_section("gauntlet", bench_gauntlet, _rec_gauntlet)
    _run_section("selfdriving", bench_selfdriving, _rec_selfdriving)
    seq_res = _run_section("seq", bench_sequence_oldest, _rec_seq)
    seq_steps_s = seq_res["steps_s"] if seq_res else None
    gen = _run_section("gen", bench_generative, _rec_gen)
    _run_section("device_steady", bench_device_steady, _rec_device_steady)

    # vs_baseline compares only same-platform runs — a CPU dev-box number is
    # not a baseline for the TPU chip or vice versa. Entries without a
    # platform tag (or malformed ones) are excluded rather than grandfathered.
    # Same-config comparisons only: entries tagged with a different (or
    # absent) bench config measured a different thing — a concurrency or
    # batch-ceiling change must not masquerade as a perf delta.  Probe
    # records (probe == "simple") and legacy run aggregates both carry the
    # metric/value keys, so both populate the baseline.  Records from THIS
    # run are excluded by run_ts: a run must not baseline itself.
    if _FAILED:
        _RESULT["sections_failed"] = sorted(set(_FAILED))
    if simple is None:
        # No headline probe — either filtered out (BENCH_SECTIONS without
        # "simple") or the probe itself failed.  Emit an explicitly-labeled
        # partial rather than a fake headline, with the status naming which
        # of the two happened.
        _RESULT.setdefault("metric", "inproc_simple_ips")
        # 0.0 (not null): the driver schema wants a numeric value; the
        # distinct status is what says "no headline was measured".
        _RESULT.setdefault("value", 0.0)
        _RESULT.setdefault("unit", "infer/sec")
        status = ("sections-filtered" if not _want("simple")
                  else "headline-failed")
        _RESULT["status"] = status
        if _sections_filter() is not None:
            _RESULT["sections"] = _sections_tag()
        _append_history({"probe": "run-status", "status": status,
                         **({"sections": _RESULT["sections"]}
                            if "sections" in _RESULT else {}),
                         **({"sections_failed": _RESULT["sections_failed"]}
                            if _FAILED else {})})
        _emit(_RESULT)
        # Deliberately filtered is a clean run; a failed headline (or any
        # failed section) is not.
        return 1 if _FAILED or status == "headline-failed" else 0
    hist_path = _hist_path()
    try:
        with open(hist_path) as f:
            hist = json.load(f)
        if not isinstance(hist, list):
            hist = []
    except Exception:  # noqa: BLE001 — first run
        hist = []
    best = max((h["value"] for h in hist
                if isinstance(h, dict)
                and h.get("metric") == "inproc_simple_ips"
                and isinstance(h.get("value"), (int, float))
                and h.get("platform") == platform
                and h.get("config") == config
                # Outage placeholders carry value 0.0 with
                # status=unavailable; they are not baselines (and must
                # not be, should the placeholder value ever change).
                and h.get("status") != "unavailable"
                and h.get("run_ts") != _RUN_TS),
               default=None)
    vs = ips / best if best else 1.0
    _RESULT["vs_baseline"] = round(vs, 4)
    # A filtered run that did include the headline still must not pass for a
    # complete capture: carry the filter on both the emit and the record.
    filtered = _sections_filter() is not None
    status = "ok-sections-filtered" if filtered else "ok"
    _RESULT["status"] = status
    if filtered:
        _RESULT["sections"] = _sections_tag()
    _append_history({"probe": "run-status", "status": status,
                     "metric": "inproc_simple_ips", "value": ips,
                     "p99_us": p99_us, "stable": simple["stable"],
                     "bert_ips": bert_ips, "mfu": mfu,
                     "seq_oldest_steps_s": seq_steps_s,
                     "gen_tok_s": gen["tok_s"] if gen else None,
                     "gen_chunk": gen.get("chunk") if gen else None,
                     "vs_baseline": round(vs, 4),
                     **({"sections": _sections_tag()}
                        if filtered else {}),
                     **({"sections_failed": _RESULT["sections_failed"]}
                        if _FAILED else {}),
                     **({"sections_skipped": _RESULT["sections_skipped"]}
                        if "sections_skipped" in _RESULT else {})})

    _emit(_RESULT)
    return 1 if _FAILED else 0


def mfu_study(n_runs: int = 5, trace_dir: str | None = None):
    """Flagship MFU variance study (VERDICT r4 #4): N repeated BERT-base
    b8 probes on identical code, reported as a distribution — separating
    shared-chip contention from code drift — plus one jax.profiler trace
    naming the top ops, saved as an artifact.

    Run: ``python bench.py --mfu-study [n_runs]``.  Appends each probe to
    BENCH_HISTORY (probe="mfu_study") and prints a summary JSON line.
    """
    devices = preflight()
    _HIST_CTX.update({"platform": devices[0].platform,
                      "config": "bert-b8-mfu-study"})
    steps_ms: list[float] = []
    mfus: list[float] = []
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    n_runs = max(1, n_runs)
    for i in range(n_runs):
        # The last run also captures the profiler trace (same staged
        # workload, no extra compile).
        td = trace_dir if i == n_runs - 1 else None
        kw = {"iters": 3, "pipeline_n": 5} if smoke else {}
        bres = bench_bert_mfu(trace_dir=td, **kw)
        mfu, step_s = bres["mfu"], bres["step_s"]
        steps_ms.append(round(step_s * 1e3, 3))
        if mfu is not None:
            mfus.append(round(mfu, 4))
        _append_history({"probe": "mfu_study", "run": i,
                         "step_ms": step_s * 1e3, "mfu": mfu,
                         "step_method": bres["step_method"],
                         "dispatch_step_ms": bres["dispatch_step_s"] * 1e3,
                         "e2e_ms": bres["e2e_s"] * 1e3})
        log(f"mfu-study run {i + 1}/{n_runs}: step {step_s * 1e3:.2f}ms"
            + (f", MFU {mfu * 100:.1f}%" if mfu is not None else ""))
    trace_note = trace_dir
    summary = {
        "metric": "bert_b8_mfu_study", "n_runs": n_runs,
        "step_method": bres["step_method"],
        "step_ms": steps_ms,
        "step_ms_min": min(steps_ms), "step_ms_max": max(steps_ms),
        "mfu": mfus,
        "mfu_min": min(mfus) if mfus else None,
        "mfu_max": max(mfus) if mfus else None,
        "trace": trace_note,
    }
    _append_history({"probe": "mfu_study_summary", **summary})
    print(json.dumps(summary), flush=True)


def sweep_concurrency(concs):
    """Reproduce the headline's saturation-knee sweep in one command:
    ``python bench.py --sweep-concurrency 256,512,768,1024``.  The round-4
    sweep that picked c768 (BENCH_CONCURRENCY's comment block) was run by
    hand; this makes the knee re-derivable and appends every point to
    BENCH_HISTORY as it completes (a killed run keeps its points).  Same stable-window
    probe as the headline — only the concurrency varies; per-point fault
    isolation so one collapsed point (c1024 is expected to) does not cost
    the sweep."""
    devices = preflight()
    _HIST_CTX.update({
        "platform": devices[0].platform,
        "config": f"mb{BENCH_MAX_BATCH}-sweep-i{BENCH_INSTANCES}"})
    out = {}
    for c in concs:
        try:
            res = bench_inproc_simple(concurrency=c)
            row = {k: res[k] for k in ("ips", "p99_us", "stable")}
        except Exception as exc:  # noqa: BLE001 — per-point isolation
            row = {"error": repr(exc)[:200]}
        out[f"c{c}"] = row
        _append_history({"probe": "simple_sweep", "concurrency": c, **row})
        log(f"sweep c{c}: {json.dumps(row)}")
    print(json.dumps({"metric": "simple_concurrency_sweep", **out}),
          flush=True)


if __name__ == "__main__":
    if "--mfu-study" in sys.argv:
        idx = sys.argv.index("--mfu-study")
        n = (int(sys.argv[idx + 1])
             if len(sys.argv) > idx + 1 and sys.argv[idx + 1].isdigit()
             else 5)
        trace = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "artifacts", "mfu_trace")
        _run_with_watchdog(lambda: mfu_study(n, trace_dir=trace),
                           metric="bert_b8_mfu_study", unit="ms")
    elif "--sweep-concurrency" in sys.argv:
        idx = sys.argv.index("--sweep-concurrency")
        arg = (sys.argv[idx + 1] if len(sys.argv) > idx + 1
               else "256,384,512,768,1024")
        concs = [int(x) for x in arg.split(",") if x.strip()]
        _run_with_watchdog(lambda: sweep_concurrency(concs),
                           metric="simple_concurrency_sweep",
                           unit="infer/sec")
    else:
        main()
