"""Operational control plane: event journal, SLO burn rates, exemplars.

Unit coverage for the journal ring/filters/sinks, the SLO config and
multi-window burn math (fake clock), OpenMetrics rendering with
exemplars, the extended promlint checks, the model_instruments
registration race, and the bench_summary regression gate — plus the
chaos end-to-end acceptance scenarios: breaker/shed/drain transitions
land in ``/v2/events`` with trace ids resolvable in
``/v2/trace/requests``, sustained injected 5xx flips
``/v2/health/ready`` to DEGRADED via the SLO tracker, and the
OpenMetrics ``/metrics`` scrape lints clean with at least one exemplar.
"""

import importlib.util
import io
import json
import logging
import os
import threading
from urllib.request import Request, urlopen

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu import faults
from client_tpu.admission import AdmissionConfig, AdmissionController
from client_tpu.admission.drain import drain
from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.models import build_repository
from client_tpu.observability import scrape
from client_tpu.observability.events import (
    EventJournal,
    configure_logging,
    journal,
)
from client_tpu.observability.metrics import EngineMetrics, MetricRegistry
from client_tpu.observability.slo import SloConfig, SloTracker
from client_tpu.resilience import CircuitBreaker
from client_tpu.server import GrpcInferenceServer, HttpInferenceServer
from client_tpu.utils import InferenceServerException


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..",
                           "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


promlint = _load_tool("promlint")
bench_summary = _load_tool("bench_summary")


# -- event journal units ------------------------------------------------------


class TestEventJournal:
    def _journal(self, capacity=8):
        clock = [1000.0]
        mono = [0]

        def tick():
            clock[0] += 1.0
            return clock[0]

        def tick_ns():
            mono[0] += 1
            return mono[0]

        return EventJournal(capacity=capacity, clock=tick, mono_ns=tick_ns)

    def test_emit_snapshot_roundtrip(self):
        j = self._journal()
        e = j.emit("breaker", "open", severity="ERROR", model="m",
                   version=1, trace_id="t" * 32, host="h", failures=3)
        assert e.seq == 1
        (got,) = j.snapshot()
        assert got.category == "breaker" and got.name == "open"
        d = got.to_dict()
        assert d["detail"] == {"host": "h", "failures": 3}
        assert d["version"] == "1" and d["trace_id"] == "t" * 32

    def test_ring_drops_oldest_and_counts(self):
        j = self._journal(capacity=4)
        for i in range(7):
            j.emit("c", f"e{i}")
        events = j.snapshot()
        assert [e.name for e in events] == ["e3", "e4", "e5", "e6"]
        assert j.dropped() == 3
        out = j.export()
        assert out["dropped"] == 3 and out["next_seq"] == 7
        assert out["capacity"] == 4

    def test_severity_is_a_minimum_filter(self):
        j = self._journal()
        j.emit("c", "a", severity="DEBUG")
        j.emit("c", "b", severity="INFO")
        j.emit("c", "c", severity="WARNING")
        j.emit("c", "d", severity="ERROR")
        names = [e.name for e in j.snapshot(severity="warning")]
        assert names == ["c", "d"]
        with pytest.raises(ValueError):
            j.snapshot(severity="LOUD")
        with pytest.raises(ValueError):
            j.emit("c", "x", severity="LOUD")

    def test_model_category_since_and_limit_filters(self):
        j = self._journal(capacity=32)
        j.emit("admission", "shed", model="a")
        j.emit("admission", "shed", model="b")
        j.emit("breaker", "open", model="a")
        assert [e.model for e in j.snapshot(model="a")] == ["a", "a"]
        assert [e.name for e in j.snapshot(category="breaker")] == ["open"]
        # exclusive cursor: seq 1 already seen
        assert [e.seq for e in j.snapshot(since_seq=1)] == [2, 3]
        # limit keeps the newest
        assert [e.seq for e in j.snapshot(limit=1)] == [3]

    def test_sinks_receive_events_and_broken_sink_is_ignored(self):
        j = self._journal()
        seen = []

        def bad(_evt):
            raise RuntimeError("boom")

        j.add_sink(bad)
        j.add_sink(seen.append)
        j.emit("c", "x")
        assert len(seen) == 1 and seen[0].name == "x"
        j.remove_sink(seen.append)
        j.emit("c", "y")
        assert len(seen) == 1

    def test_clear_keeps_seq_cursor(self):
        j = self._journal()
        j.emit("c", "a")
        j.clear()
        e = j.emit("c", "b")
        assert e.seq == 2 and len(j) == 1

    def test_json_log_sink_mirrors_events(self):
        j = self._journal()
        out = io.StringIO()
        installed = configure_logging(environ={"CLIENT_TPU_LOG": "json"},
                                      stream=out, jour=j)
        assert installed
        try:
            j.emit("drain", "begin", deadline_s=5)
            line = out.getvalue().strip().splitlines()[-1]
            d = json.loads(line)
            assert d["kind"] == "event" and d["name"] == "begin"
            assert d["detail"] == {"deadline_s": 5}
        finally:
            logger = logging.getLogger("client_tpu")
            for h in list(logger.handlers):
                if getattr(h, "_client_tpu_json", False):
                    logger.removeHandler(h)
            logger.propagate = True

    def test_configure_logging_off_by_default(self):
        assert configure_logging(environ={}) is False


# -- SLO units ----------------------------------------------------------------


class _FakeClock:
    def __init__(self, t=10_000.0):
        self.t = t

    def __call__(self):
        return self.t


class TestSloConfig:
    def test_from_env_unset_is_disabled(self):
        cfg = SloConfig.from_env(environ={})
        assert cfg.enabled is False
        tracker = SloTracker(cfg)
        tracker.record("m", success=False)  # no-op
        assert tracker.fast_burn() == []
        assert tracker.snapshot()["models"] == {}

    def test_inline_json_and_model_override(self):
        cfg = SloConfig.from_env(environ={
            "CLIENT_TPU_SLO": json.dumps({
                "availability": 0.99, "latency_threshold_us": 50000,
                "models": {"bert": {"availability": 0.9}}})})
        assert cfg.enabled and cfg.availability == 0.99
        assert cfg.for_model("bert").availability == 0.9
        # overrides inherit unset fields from the base
        assert cfg.for_model("bert").latency_threshold_us == 50000
        assert cfg.for_model("other").availability == 0.99

    def test_file_reference(self, tmp_path):
        p = tmp_path / "slo.json"
        p.write_text(json.dumps({"availability": 0.95}))
        cfg = SloConfig.from_env(environ={"CLIENT_TPU_SLO": f"@{p}"})
        assert cfg.availability == 0.95

    def test_unknown_keys_and_bad_values_rejected(self):
        with pytest.raises(ValueError):
            SloConfig.from_dict({"availabilty": 0.9})  # typo
        with pytest.raises(ValueError):
            SloConfig.from_dict({"models": {"m": {"nope": 1}}})
        with pytest.raises(ValueError):
            SloConfig(availability=1.5)
        with pytest.raises(ValueError):
            SloConfig(latency_threshold_us=-1)


class TestSloBurnRates:
    def test_burn_rate_math(self):
        clock = _FakeClock()
        t = SloTracker(SloConfig(availability=0.99), clock=clock)
        for i in range(100):
            t.record("m", success=(i % 10 != 0))  # 10% errors
        snap = t.snapshot()
        w = snap["models"]["m"]["windows"]["5m"]
        assert w["requests"] == 100 and w["errors"] == 10
        # 10% bad over a 1% budget = burn rate 10
        assert w["availability_burn_rate"] == pytest.approx(10.0)

    def test_fast_burn_requires_both_windows(self):
        clock = _FakeClock(t=10_000.0)
        t = SloTracker(SloConfig(availability=0.999,
                                 fast_burn_threshold=14.4), clock=clock)
        for _ in range(20):
            t.record("m", success=False)
        # Recent errors appear in BOTH windows -> fast burn.
        assert t.fast_burn() == ["m"]
        # 10 minutes later the 5m window is clean; the same errors still
        # burn the 1h window, but one window alone must not flip health.
        clock.t += 600
        assert t.fast_burn() == []
        snap = t.snapshot()
        assert snap["models"]["m"]["windows"]["5m"]["requests"] == 0
        assert snap["models"]["m"]["windows"]["1h"]["errors"] == 20

    def test_latency_objective_counts_slow_successes(self):
        clock = _FakeClock()
        t = SloTracker(SloConfig(availability=0.999,
                                 latency_threshold_us=1000.0,
                                 latency_target=0.9), clock=clock)
        for i in range(10):
            t.record("m", success=True,
                     duration_us=5000.0 if i < 5 else 10.0)
        w = t.snapshot()["models"]["m"]["windows"]["5m"]
        assert w["slow"] == 5
        # 50% slow over a 10% budget = burn 5
        assert w["latency_burn_rate"] == pytest.approx(5.0)
        # failures don't feed the latency objective
        t.record("m", success=False, duration_us=99999.0)
        w = t.snapshot()["models"]["m"]["windows"]["5m"]
        assert w["slow"] == 5

    def test_gauges_exported(self):
        reg = MetricRegistry()
        clock = _FakeClock()
        t = SloTracker(SloConfig(availability=0.99), registry=reg,
                       clock=clock)
        t.record("m", success=False)
        t.snapshot()
        text = reg.render()
        assert ('tpu_slo_burn_rate{model="m",objective="availability",'
                'window="5m"}') in text
        assert 'tpu_slo_fast_burn{model="m"} 1' in text
        assert ('tpu_slo_objective_target{model="m",'
                'objective="availability"} 0.99') in text

    def test_ring_slots_reset_when_stale(self):
        clock = _FakeClock(t=100.0)
        t = SloTracker(SloConfig(availability=0.99), clock=clock)
        t.record("m", success=False)
        clock.t += 3601  # same slot index one hour later must not leak
        t.record("m", success=True)
        w = t.snapshot()["models"]["m"]["windows"]["1h"]
        assert w["requests"] == 1 and w["errors"] == 0


# -- exemplars + OpenMetrics rendering ----------------------------------------


class _Times:
    queue_ns = 10_000
    compute_input_ns = 5_000
    compute_infer_ns = 50_000
    compute_output_ns = 2_000


class TestOpenMetricsRender:
    def _metrics(self):
        em = EngineMetrics()
        inst = em.model_instruments("m", "1")
        inst.observe_request(5_000_000, _Times(), trace_id="a" * 32)
        return em

    def test_om_render_has_eof_exemplar_and_total_suffix(self):
        text = self._metrics().render(openmetrics=True)
        assert text.rstrip().splitlines()[-1] == "# EOF"
        ex_lines = [ln for ln in text.splitlines()
                    if "tpu_request_duration" in ln and " # {" in ln]
        assert ex_lines, "duration histogram lost its exemplar"
        assert f'trace_id="{"a" * 32}"' in ex_lines[0]
        # counters rename their samples to _total in OM mode only
        assert promlint.lint(text, openmetrics=True) == []

    def test_classic_render_is_unchanged(self):
        text = self._metrics().render()
        assert "# EOF" not in text and " # {" not in text
        assert promlint.lint(text) == []

    def test_exemplar_tracks_latest_observation_per_bucket(self):
        em = EngineMetrics()
        inst = em.model_instruments("m", "1")
        inst.observe_request(5_000_000, _Times(), trace_id="a" * 32)
        inst.observe_request(5_000_000, _Times(), trace_id="b" * 32)
        text = em.render(openmetrics=True)
        joined = "\n".join(ln for ln in text.splitlines() if " # {" in ln)
        assert "b" * 32 in joined and "a" * 32 not in joined

    def test_untraced_observations_render_without_exemplar(self):
        em = EngineMetrics()
        inst = em.model_instruments("m", "1")
        inst.observe_request(5_000_000, _Times())
        text = em.render(openmetrics=True)
        dur = [ln for ln in text.splitlines()
               if ln.startswith("tpu_request_duration_us_bucket")]
        assert dur and all(" # {" not in ln for ln in dur)
        assert promlint.lint(text, openmetrics=True) == []

    def test_scrape_parses_om_and_classic_identically(self):
        em = self._metrics()
        om = {(n, tuple(sorted(ls.items())), v) for n, ls, v in
              scrape.parse_samples(em.render(openmetrics=True))}
        cl = {(n, tuple(sorted(ls.items())), v) for n, ls, v in
              scrape.parse_samples(em.render())}

        def norm(s):
            return {(n[:-6] if n.endswith("_total") else n, ls, v)
                    for n, ls, v in s}

        assert norm(om) == norm(cl)

    def test_hbm_gauges_present_and_zero_on_cpu(self):
        em = EngineMetrics()
        em.update_device_gauges()
        samples = dict()
        for n, ls, v in scrape.parse_samples(em.render()):
            samples.setdefault(n, v)
        assert samples.get("tpu_hbm_limit_bytes") == 0
        assert samples.get("tpu_hbm_peak_bytes") == 0


class TestPromlintOpenMetrics:
    GOOD = (
        "# HELP c Total.\n# TYPE c counter\nc_total 5\n"
        "# HELP h H.\n# TYPE h histogram\n"
        'h_bucket{le="+Inf"} 1 # {trace_id="abc"} 2.0\n'
        "h_sum 2.0\nh_count 1\n# EOF\n")

    def test_good_exposition_is_clean(self):
        assert promlint.lint(self.GOOD) == []

    def test_auto_detects_openmetrics_from_eof(self):
        bare_counter = self.GOOD.replace("c_total 5", "c 5")
        errs = promlint.lint(bare_counter)  # no explicit mode
        assert any("_total" in e for e in errs)

    def test_missing_eof_flagged_in_om_mode(self):
        errs = promlint.lint(self.GOOD.replace("# EOF\n", ""),
                             openmetrics=True)
        assert any("missing the '# EOF'" in e for e in errs)

    def test_content_after_eof_flagged(self):
        errs = promlint.lint(self.GOOD + "stray 1\n")
        assert any("content after" in e for e in errs)

    def test_malformed_exemplar_and_bad_placement(self):
        text = (
            "# HELP h H.\n# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 1 # {trace_id=oops} 1.0\n'
            "h_sum 1.0\nh_count 1 # {trace_id=\"x\"} 1.0\n# EOF\n")
        errs = promlint.lint(text)
        assert any("malformed label pair" in e for e in errs)
        assert any("only _bucket and" in e for e in errs)

    def test_exemplar_rune_budget(self):
        big = "x" * 150
        text = (
            "# HELP h H.\n# TYPE h histogram\n"
            f'h_bucket{{le="+Inf"}} 1 # {{trace_id="{big}"}} 1.0\n'
            "h_sum 1.0\nh_count 1\n# EOF\n")
        errs = promlint.lint(text)
        assert any("128" in e for e in errs)

    def test_classic_mode_unaffected_by_om_rules(self):
        # A classic counter family carries _total on the family name
        # itself — the exact shape the OM dialect forbids (families there
        # advertise the base name). Clean here proves OM-only rules
        # (family naming, EOF, exemplar placement) don't leak.
        classic = "# HELP c_total Total.\n# TYPE c_total counter\nc_total 5\n"
        assert promlint.lint(classic) == []


class TestModelInstrumentsRace:
    def test_concurrent_registration_yields_one_instance(self):
        em = EngineMetrics()
        start = threading.Barrier(8)
        got = []

        def grab():
            start.wait()
            for _ in range(50):
                got.append(em.model_instruments("m", "1"))

        threads = [threading.Thread(target=grab) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len({id(x) for x in got}) == 1
        # distinct keys stay distinct
        assert em.model_instruments("m", "2") is not got[0]


class TestBenchCheck:
    def _hist(self, *p99s):
        return [{"probe": "simple", "p99_us": v, "run_ts": 1000.0 + i,
                 "ts": 1000.0 + i, "platform": "cpu"}
                for i, v in enumerate(p99s)]

    def test_single_run_passes(self):
        assert bench_summary.check(self._hist(100.0)) == 0

    def test_within_threshold_passes(self):
        assert bench_summary.check(self._hist(100.0, 102.0, 120.0)) == 0

    def test_regression_fails(self):
        assert bench_summary.check(self._hist(100.0, 102.0, 140.0)) == 1

    def test_run_status_records_ignored(self):
        hist = self._hist(100.0, 140.0)
        hist.insert(0, {"probe": "run-status", "status": "ok",
                        "run_ts": 999.0, "p99_us": 1.0})
        assert bench_summary.check(hist, threshold=0.5) == 0


# -- chaos end-to-end ---------------------------------------------------------


def _inputs(mod):
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    i0 = mod.InferInput("INPUT0", a.shape, "INT32")
    i0.set_data_from_numpy(a)
    i1 = mod.InferInput("INPUT1", b.shape, "INT32")
    i1.set_data_from_numpy(b)
    return a, b, [i0, i1]


@pytest.fixture(scope="module")
def stack():
    eng = TpuEngine(build_repository(["simple"]))
    http_srv = HttpInferenceServer(eng, port=0).start()
    grpc_srv = GrpcInferenceServer(eng, port=0).start()
    yield {"engine": eng, "http": http_srv,
           "grpc_url": f"127.0.0.1:{grpc_srv.port}"}
    faults.reset()
    http_srv.stop()
    grpc_srv.stop()
    eng.shutdown()


@pytest.fixture(autouse=True)
def clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.mark.chaos
class TestEventsEndpointE2e:
    def test_server_start_and_model_load_in_journal(self, stack):
        out = json.load(urlopen(
            f"http://{stack['http'].url}/v2/events?category=lifecycle",
            timeout=10))
        names = [e["name"] for e in out["events"]]
        assert "server_start" in names
        out = json.load(urlopen(
            f"http://{stack['http'].url}/v2/events?category=model",
            timeout=10))
        assert any(e["name"] == "load" and e["model"] == "simple"
                   for e in out["events"])

    def test_filters_and_bad_params(self, stack):
        base = f"http://{stack['http'].url}/v2/events"
        out = json.load(urlopen(f"{base}?severity=ERROR&limit=5", timeout=10))
        assert all(e["severity"] == "ERROR" for e in out["events"])
        assert len(out["events"]) <= 5
        from urllib.error import HTTPError

        with pytest.raises(HTTPError) as ei:
            urlopen(f"{base}?severity=LOUD", timeout=10)
        assert ei.value.code == 400
        with pytest.raises(HTTPError) as ei:
            urlopen(f"{base}?limit=nope", timeout=10)
        assert ei.value.code == 400

    def test_breaker_open_event_carries_request_trace_id(self, stack):
        """Two injected 5xx trip the client breaker; the breaker.open
        event lands in the shared journal with the failing request's
        trace id, and that id resolves in /v2/trace/requests."""
        faults.configure({"model.execute": {
            "probability": 1.0, "seed": 3, "error_status": 503}})
        cursor = journal().export()["next_seq"]
        c = httpclient.InferenceServerClient(
            stack["http"].url,
            circuit_breaker=CircuitBreaker(failure_threshold=2,
                                           cooldown_s=30.0))
        try:
            _, _, inputs = _inputs(httpclient)
            for _ in range(2):
                with pytest.raises(InferenceServerException):
                    c.infer("simple", inputs)
        finally:
            c.close()
        opens = journal().snapshot(category="breaker", since_seq=cursor)
        opens = [e for e in opens if e.name == "open"]
        assert opens, "breaker never opened"
        evt = opens[-1]
        assert evt.severity == "ERROR"
        assert evt.trace_id and len(evt.trace_id) == 32
        # the same transition is visible over HTTP
        out = json.load(urlopen(
            f"http://{stack['http'].url}/v2/events?category=breaker"
            f"&since={cursor}", timeout=10))
        assert any(e["name"] == "open" and e.get("trace_id") == evt.trace_id
                   for e in out["events"])
        # ... and the trace id resolves to a recorded request timeline
        trace = json.load(urlopen(
            f"http://{stack['http'].url}/v2/trace/requests"
            f"?trace_id={evt.trace_id}", timeout=10))
        assert any(ev.get("args", {}).get("trace_id") == evt.trace_id
                   for ev in trace["traceEvents"])

    def test_admission_shed_event_with_trace_id(self, stack):
        eng = stack["engine"]
        orig = eng.admission
        eng.admission = AdmissionController(
            # 0.01/s cannot refill the one-token burst inside the test: at
            # 5/s a second request more than 200 ms after the first (six
            # xdist workers) was admitted and no 429 came.
            AdmissionConfig.from_dict({"models": {"simple": {
                "tokens_per_s": 0.01, "burst": 1.0}}}), metrics=eng.metrics)
        cursor = journal().export()["next_seq"]
        c = httpclient.InferenceServerClient(stack["http"].url)
        try:
            _, _, inputs = _inputs(httpclient)
            c.infer("simple", inputs)  # drains the burst
            with pytest.raises(InferenceServerException) as ei:
                c.infer("simple", inputs)
            assert ei.value.status() == 429
        finally:
            c.close()
            eng.admission = orig
        sheds = [e for e in journal().snapshot(category="admission",
                                               since_seq=cursor)
                 if e.name == "shed"]
        assert sheds and sheds[-1].model == "simple"
        assert sheds[-1].trace_id and len(sheds[-1].trace_id) == 32
        assert sheds[-1].detail["reason"] == "throttled"
        assert any(e.name == "degraded_enter" for e in
                   journal().snapshot(category="admission",
                                      since_seq=cursor))

    def test_drain_events_bracket_the_sequence(self):
        eng = TpuEngine(build_repository(["simple"]))
        cursor = journal().export()["next_seq"]
        report = drain(eng, deadline_s=10.0)
        assert report["clean"]
        evts = journal().snapshot(category="drain", since_seq=cursor)
        names = [e.name for e in evts]
        assert names == ["begin", "end"]
        assert evts[1].detail["clean"] is True
        assert evts[1].detail["drain_s"] >= 0

    def test_grpc_events_and_slo_accessors(self, stack):
        c = grpcclient.InferenceServerClient(stack["grpc_url"])
        try:
            out = c.get_events(category="lifecycle")
            assert any(e["name"] == "server_start" for e in out["events"])
            assert out["next_seq"] > 0
            # detail JSON round-trips through the proto
            loads = c.get_events(category="model")
            assert any("detail" not in e or isinstance(e["detail"], dict)
                       for e in loads["events"])
            slo = c.get_slo_status()
            assert slo["enabled"] is False and "windows" in slo
            with pytest.raises(InferenceServerException):
                c.get_events(severity="LOUD")
        finally:
            c.close()


@pytest.mark.chaos
class TestSloHealthE2e:
    def test_sustained_5xx_flips_ready_to_degraded(self, monkeypatch):
        """With CLIENT_TPU_SLO set, a run of injected execution failures
        burns both windows past threshold and /v2/health/ready reports
        DEGRADED; once tracking sees only successes in a fresh tracker,
        health returns to READY."""
        monkeypatch.setenv("CLIENT_TPU_SLO", json.dumps(
            {"availability": 0.999, "fast_burn_threshold": 14.4}))
        eng = TpuEngine(build_repository(["simple"]))
        http_srv = HttpInferenceServer(eng, port=0).start()
        c = httpclient.InferenceServerClient(http_srv.url)
        try:
            assert eng.slo.enabled
            _, _, inputs = _inputs(httpclient)
            faults.configure({"model.execute": {
                "probability": 1.0, "seed": 5, "error_status": 503}})
            for _ in range(10):
                with pytest.raises(InferenceServerException) as ei:
                    c.infer("simple", inputs)
                assert ei.value.status() == 503
            resp = urlopen(f"http://{http_srv.url}/v2/health/ready",
                           timeout=10)
            assert resp.headers["X-Health-State"] == "DEGRADED"
            slo = json.load(urlopen(f"http://{http_srv.url}/v2/slo",
                                    timeout=10))
            assert slo["enabled"] is True
            m = slo["models"]["simple"]
            assert m["fast_burn"] is True
            assert m["windows"]["5m"]["errors"] >= 10
            assert m["windows"]["5m"]["availability_burn_rate"] > 14.4
            # the degradation is also on the journal timeline
            health = [e for e in journal().snapshot(category="lifecycle")
                      if e.name == "health"]
            assert health and health[-1].detail["state"] == "DEGRADED"
            assert health[-1].detail["slo_fast_burn"] == ["simple"]
            # burn gauges render on /metrics
            text = eng.prometheus_metrics()
            assert 'tpu_slo_fast_burn{model="simple"} 1' in text
        finally:
            faults.reset()
            c.close()
            http_srv.stop()
            eng.shutdown()

    def test_slo_disabled_never_degrades_health(self, stack):
        """The shared stack has no CLIENT_TPU_SLO: even after the breaker
        test's injected failures, health stays un-degraded by SLO."""
        eng = stack["engine"]
        assert not eng.slo.enabled
        assert eng.slo.fast_burn() == []


@pytest.mark.chaos
class TestOpenMetricsScrapeE2e:
    def test_om_scrape_lints_clean_with_exemplar(self, stack):
        c = httpclient.InferenceServerClient(stack["http"].url)
        try:
            _, _, inputs = _inputs(httpclient)
            c.infer("simple", inputs)
            stat = c.get_infer_stat()
        finally:
            c.close()
        # the client's stats surface the trace id for the jump
        assert stat["last_trace_id"] and len(stat["last_trace_id"]) == 32
        base = f"http://{stack['http'].url}/metrics"
        om = urlopen(Request(base, headers={
            "Accept": "application/openmetrics-text"}),
            timeout=10).read().decode()
        assert promlint.lint(om, openmetrics=True) == []
        ex = [ln for ln in om.splitlines()
              if "tpu_request_duration" in ln and " # {" in ln]
        assert ex, "no exemplar on tpu_request_duration"
        classic = urlopen(base, timeout=10).read().decode()
        assert promlint.lint(classic) == []
        assert "# EOF" not in classic
