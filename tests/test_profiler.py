"""Efficiency profiler (PR-5): fill-ratio cost attribution, compile
telemetry, duty cycle, /v2/profile + Profile RPC, and the TraceManager
stop/start race fixes that ride along.

Unit sections drive an :class:`EfficiencyProfiler` with a fake clock —
no engine, no jax. The e2e section boots the real stack once and checks
the one-compilation-per-bucket invariant plus both transports.
"""

import importlib.util
import json
import os
import threading
from urllib.request import urlopen

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.engine.trace import TraceManager
from client_tpu.engine.types import EngineError
from client_tpu.models import build_repository
from client_tpu.observability import events
from client_tpu.observability.metrics import MetricRegistry
from client_tpu.observability.profiler import (
    EfficiencyProfiler,
    _suggest_bucket_tweak,
    profiler,
    reset_profiler,
)
from client_tpu.server import GrpcInferenceServer, HttpInferenceServer


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..",
                           "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


promlint = _load_tool("promlint")
profile_report = _load_tool("profile_report")


class FakeClock:
    """monotonic_ns stand-in: starts at 1s, advanced manually."""

    def __init__(self, t_ns=1_000_000_000):
        self.t = t_ns

    def __call__(self):
        return self.t

    def advance_s(self, s):
        self.t += int(s * 1e9)


def _prof(window_s=60.0):
    clk = FakeClock()
    return EfficiencyProfiler(window_s=window_s, now=clk), clk


# -- cost attribution units ---------------------------------------------------


class TestCostAttribution:
    def test_fill_ratio_and_padding_math(self):
        p, _ = _prof()
        # 3 real rows padded to bucket 8 → 5 padded rows, fill 3/8
        p.record_execution("m", 1, 8, rows=3, device_ns=8_000_000)
        snap = p.snapshot()
        b = snap["models"]["m:1"]["buckets"][0]
        assert b["bucket"] == 8
        assert b["rows"] == 3 and b["padded_rows"] == 5
        assert b["fill_ratio"] == pytest.approx(3 / 8)
        # waste = device_s * padded/(real+padded) = 8ms * 5/8
        assert b["padding_waste_device_s"] == pytest.approx(0.005)

    def test_unbatched_bucket_zero_never_pads(self):
        p, _ = _prof()
        p.record_execution("m", 1, None, rows=1, device_ns=1_000_000)
        b = p.snapshot()["models"]["m:1"]["buckets"][0]
        assert b["bucket"] == 0
        assert b["padded_rows"] == 0
        assert b["fill_ratio"] == 1.0
        assert b["padding_waste_device_s"] == 0.0

    def test_cold_execution_counts_rows_but_not_device_time(self):
        p, _ = _prof()
        p.record_execution("m", 1, 8, rows=2, device_ns=30_000_000_000,
                           cold=True)
        b = p.snapshot()["models"]["m:1"]["buckets"][0]
        assert b["executions"] == 1 and b["cold_executions"] == 1
        assert b["rows"] == 2 and b["padded_rows"] == 6
        # the 30s trace interval is compile, not load
        assert b["device_s"] == 0.0
        assert b["device_s_per_call_ewma"] == 0.0
        assert p.duty_cycle() == 0.0

    def test_ewma_tracks_per_call_device_time(self):
        p, _ = _prof()
        p.record_execution("m", 1, 4, rows=4, device_ns=10_000_000)
        p.record_execution("m", 1, 4, rows=4, device_ns=20_000_000)
        b = p.snapshot()["models"]["m:1"]["buckets"][0]
        # alpha=0.2: 0.2*20ms + 0.8*10ms = 12ms
        assert b["device_s_per_call_ewma"] == pytest.approx(0.012)
        assert b["device_s"] == pytest.approx(0.030)

    def test_snapshot_model_filter_and_rollup(self):
        p, _ = _prof()
        p.record_execution("a", 1, 4, rows=2, device_ns=4_000_000)
        p.record_execution("a", 1, 8, rows=8, device_ns=8_000_000)
        p.record_execution("b", 1, 4, rows=4, device_ns=1_000_000)
        snap = p.snapshot(model="a")
        assert set(snap["models"]) == {"a:1"}
        m = snap["models"]["a:1"]
        assert len(m["buckets"]) == 2
        assert m["device_s"] == pytest.approx(0.012)
        assert m["padding_waste_device_s"] == pytest.approx(0.002)

    def test_reset_drops_costs(self):
        p, _ = _prof()
        p.record_execution("m", 1, 4, rows=1, device_ns=1_000_000)
        p.reset()
        assert p.snapshot()["models"] == {}


# -- compile telemetry --------------------------------------------------------


class TestCompileTelemetry:
    def test_compile_counted_and_journaled(self):
        events.reset_journal()
        p, _ = _prof()
        p.record_compile("m", 1, 8, compile_ns=2_500_000_000,
                         trace_id="0" * 31 + "1")
        m = p.snapshot()["models"]["m:1"]
        assert m["compilations"] == 1
        assert m["compile_s"] == pytest.approx(2.5)
        evts = events.journal().snapshot(category="compile")
        assert len(evts) == 1
        e = evts[0]
        assert e.name == "finished" and e.model == "m"
        assert e.detail["bucket"] == 8
        assert e.detail["compile_s"] == pytest.approx(2.5)
        events.reset_journal()

    def test_compile_metrics_on_bound_registry(self):
        p, _ = _prof()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.record_compile("m", 1, 8, compile_ns=1_000_000_000)
        p.record_execution("m", 1, 8, rows=3, device_ns=5_000_000)
        text = reg.render()
        assert 'tpu_xla_compilations_total{bucket="8",model="m",' in text \
            or "tpu_xla_compilations_total" in text
        assert "tpu_xla_compile_seconds" in text
        assert "tpu_padded_rows_total" in text
        assert "tpu_batch_fill_ratio" in text

    def test_binding_is_per_registry_and_pruned_when_dead(self):
        p, _ = _prof()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.bind_metrics(reg)  # idempotent
        assert len(p._bindings()) == 1
        del reg
        assert p._bindings() == []


# -- duty cycle ---------------------------------------------------------------


class TestDutyCycle:
    def test_busy_fraction_over_window(self):
        p, clk = _prof(window_s=10.0)
        clk.advance_s(20.0)  # process older than the window
        p.record_execution("m", 1, 4, rows=4, device_ns=2_000_000_000)
        # 2s busy over a 10s window
        assert p.duty_cycle() == pytest.approx(0.2, abs=1e-6)

    def test_old_intervals_age_out(self):
        p, clk = _prof(window_s=10.0)
        clk.advance_s(20.0)
        p.record_execution("m", 1, 4, rows=4, device_ns=2_000_000_000)
        clk.advance_s(15.0)  # interval now fully outside the window
        assert p.duty_cycle() == 0.0

    def test_young_process_uses_age_not_window(self):
        p, clk = _prof(window_s=60.0)
        clk.advance_s(2.0)  # only 2s old
        p.record_execution("m", 1, 4, rows=4, device_ns=1_000_000_000)
        assert p.duty_cycle() == pytest.approx(0.5, abs=1e-6)

    def test_gauge_updated_on_bound_registries(self):
        p, clk = _prof(window_s=10.0)
        reg = MetricRegistry()
        p.bind_metrics(reg)
        clk.advance_s(20.0)
        p.record_execution("m", 1, 4, rows=4, device_ns=5_000_000_000)
        p.update_gauges()
        assert "tpu_device_duty_cycle 0.5" in reg.render()


# -- bucket-ladder suggestion -------------------------------------------------


def _bucket(bucket=8, executions=10, fill=0.5, max_rows=4,
            waste=1.0, device_s=2.0):
    return {"bucket": bucket, "executions": executions,
            "fill_ratio": fill, "max_rows": max_rows,
            "padding_waste_device_s": waste, "device_s": device_s}


class TestSuggestion:
    def test_fires_on_underfilled_bucket(self):
        s = _suggest_bucket_tweak([_bucket()])
        assert s is not None and s["action"] == "add_bucket"
        assert s["bucket"] == 4 and s["below"] == 8
        assert s["est_saving_device_s"] == pytest.approx(1.0)

    def test_requires_enough_calls(self):
        assert _suggest_bucket_tweak([_bucket(executions=7)]) is None

    def test_well_filled_ladder_is_left_alone(self):
        assert _suggest_bucket_tweak([_bucket(fill=0.9)]) is None

    def test_no_headroom_no_suggestion(self):
        # max observed rows == bucket: a smaller bucket can't absorb them
        assert _suggest_bucket_tweak([_bucket(max_rows=8)]) is None

    def test_bucket_one_and_unbatched_ignored(self):
        assert _suggest_bucket_tweak(
            [_bucket(bucket=1, max_rows=1), _bucket(bucket=0)]) is None

    def test_picks_worst_waste(self):
        s = _suggest_bucket_tweak(
            [_bucket(bucket=8, waste=0.5),
             _bucket(bucket=16, max_rows=5, waste=3.0)])
        assert s["below"] == 16 and s["bucket"] == 5


# -- global singleton ---------------------------------------------------------


class TestGlobalProfiler:
    def test_concurrent_access_yields_one_instance(self):
        reset_profiler()
        got = []
        barrier = threading.Barrier(8)

        def grab():
            barrier.wait()
            got.append(profiler())

        ts = [threading.Thread(target=grab) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert len({id(p) for p in got}) == 1
        reset_profiler()


# -- TraceManager races (satellite) ------------------------------------------


class _FakeJaxProfiler:
    def __init__(self, fail_start=False, fail_stop=False):
        self.fail_start = fail_start
        self.fail_stop = fail_stop
        self.starts = 0
        self.stops = 0

    def start_trace(self, log_dir, profiler_options=None):
        self.starts += 1
        self.options = profiler_options
        if self.fail_start:
            raise RuntimeError("profiler already running")

    def stop_trace(self):
        self.stops += 1
        if self.fail_stop:
            raise RuntimeError("no profiler running")


@pytest.fixture()
def fake_jax(monkeypatch):
    import jax

    fake = _FakeJaxProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


class TestTraceManagerRaces:
    def test_stop_when_never_started_is_noop(self, fake_jax):
        tm = TraceManager()
        out = tm.update({"trace_level": ["OFF"]})
        assert out["trace_level"] == ["OFF"]
        assert fake_jax.stops == 0

    def test_stop_error_does_not_wedge_active(self, fake_jax, tmp_path):
        tm = TraceManager()
        tm.update({"trace_level": ["TIMESTAMPS"], "log_dir": str(tmp_path)})
        fake_jax.fail_stop = True
        # something else already stopped the process-wide profiler: the
        # manager must still deactivate instead of raising
        out = tm.update({"trace_level": ["OFF"]})
        assert out["trace_level"] == ["OFF"]
        # and a fresh start works afterwards
        fake_jax.fail_stop = False
        out = tm.update({"trace_level": ["TIMESTAMPS"]})
        assert out["trace_level"] == ["TIMESTAMPS"]
        tm.shutdown()

    def test_failed_start_raises_500_and_stays_inactive(self, fake_jax,
                                                        tmp_path):
        tm = TraceManager()
        fake_jax.fail_start = True
        with pytest.raises(EngineError) as ei:
            tm.update({"trace_level": ["TIMESTAMPS"],
                       "log_dir": str(tmp_path)})
        assert ei.value.status == 500
        assert tm.setting()["trace_level"] == ["OFF"]
        # best-effort cleanup stop was attempted
        assert fake_jax.stops == 1
        # a later OFF is a no-op, not a stop on a never-started profiler
        fake_jax.stops = 0
        tm.update({"trace_level": ["OFF"]})
        assert fake_jax.stops == 0


# -- promlint unit-suffix rule (satellite) ------------------------------------


class TestPromlintUnitSuffix:
    def _classic(self, kind, name):
        return (f"# HELP {name} t\n# TYPE {name} {kind}\n{name} 1\n")

    def test_counter_without_total_flagged(self):
        errs = promlint.lint(self._classic("counter", "x_seconds"))
        assert any("bare unit suffix" in e for e in errs)
        errs = promlint.lint(self._classic("counter", "z"))
        assert any("should end in '_total'" in e for e in errs)

    def test_gauge_with_total_flagged(self):
        errs = promlint.lint(self._classic("gauge", "y_total"))
        assert any("reserved for counters" in e for e in errs)

    def test_conforming_names_clean(self):
        hist = ("# HELP c_seconds t\n# TYPE c_seconds histogram\n"
                'c_seconds_bucket{le="1"} 1\nc_seconds_bucket{le="+Inf"} 1\n'
                "c_seconds_sum 0.5\nc_seconds_count 1\n")
        text = (self._classic("counter", "a_seconds_total")
                + self._classic("gauge", "b_ratio") + hist)
        assert promlint.lint(text) == []

    def test_allowlisted_legacy_names_exempt(self):
        errs = promlint.lint(self._classic("counter",
                                           "tpu_inference_request_success"))
        assert errs == []

    def test_om_counter_family_advertised_without_total(self):
        text = ("# HELP w t\n# TYPE w counter\nw_total 1\n# EOF\n")
        assert promlint.lint(text, openmetrics=True) == []
        bad = ("# HELP w_total t\n# TYPE w_total counter\n"
               "w_total_total 1\n# EOF\n")
        errs = promlint.lint(bad, openmetrics=True)
        assert any("without the '_total' suffix" in e for e in errs)


# -- InferStat cold-start fields (satellite) ----------------------------------


class TestInferStatColdStart:
    def test_compile_entry_counted(self):
        from client_tpu.observability.client_stats import InferStat

        s = InferStat()
        s.record(1000.0, server_timing={"queue": 5.0, "compile": 2_000_000.0})
        s.record(800.0, server_timing={"queue": 5.0})
        out = s.get()
        assert out["cold_start_count"] == 1
        assert out["last_compile_s"] == pytest.approx(2.0)


# -- e2e: one compilation per bucket, /v2/profile, both transports ------------


@pytest.fixture(scope="class")
def stack():
    reset_profiler()
    events.reset_journal()
    eng = TpuEngine(build_repository(["simple"]), warmup=False)
    http_srv = HttpInferenceServer(eng, port=0).start()
    grpc_srv = GrpcInferenceServer(eng, port=0).start()
    yield {"engine": eng, "http": http_srv,
           "grpc_url": f"127.0.0.1:{grpc_srv.port}"}
    http_srv.stop()
    grpc_srv.stop()
    eng.shutdown()
    reset_profiler()
    events.reset_journal()


def _http_infer(client, batch):
    a = np.arange(16 * batch, dtype=np.int32).reshape(batch, 16)
    b = np.ones((batch, 16), dtype=np.int32)
    i0 = httpclient.InferInput("INPUT0", a.shape, "INT32")
    i0.set_data_from_numpy(a)
    i1 = httpclient.InferInput("INPUT1", b.shape, "INT32")
    i1.set_data_from_numpy(b)
    return client.infer("simple", [i0, i1])


class TestProfileE2e:
    def test_one_compilation_per_bucket_then_zero(self, stack):
        c = httpclient.InferenceServerClient(stack["http"].url)
        try:
            # batches 1 and 3 → buckets 1 and 8 (mixed fill); 10 calls
            # on bucket 8 so the ladder suggestion has enough evidence
            _http_infer(c, 1)
            for _ in range(10):
                _http_infer(c, 3)
            snap = stack["engine"].profile_snapshot(model="simple")
            m = next(iter(snap["models"].values()))
            by_bucket = {b["bucket"]: b for b in m["buckets"]}
            assert set(by_bucket) >= {1, 8}
            # exactly one compile per touched bucket, on the cold call
            assert by_bucket[1]["compilations"] == 1
            assert by_bucket[8]["compilations"] == 1
            assert by_bucket[1]["cold_executions"] == 1
            assert by_bucket[8]["cold_executions"] == 1
            # re-running a warm shape compiles nothing new
            _http_infer(c, 3)
            snap = stack["engine"].profile_snapshot(model="simple")
            m = next(iter(snap["models"].values()))
            assert m["compilations"] == 2
            # journal saw both compile.finished events
            evts = events.journal().snapshot(category="compile")
            assert len(evts) == 2
        finally:
            c.close()

    def test_http_profile_endpoint_shows_waste(self, stack):
        out = json.load(urlopen(
            f"http://{stack['http'].url}/v2/profile?model=simple",
            timeout=10))
        assert "duty_cycle" in out and "window_s" in out
        m = next(iter(out["models"].values()))
        by_bucket = {b["bucket"]: b for b in m["buckets"]}
        # batch-3 rows padded to 8 → fill < 1 and nonzero waste
        assert by_bucket[8]["fill_ratio"] < 1.0
        assert by_bucket[8]["padded_rows"] > 0
        assert m["padding_waste_device_s"] > 0.0
        # 11 warm+cold executions at 3/8 fill with headroom → suggestion
        sug = m["suggestion"]
        assert sug is not None and sug["action"] == "add_bucket"
        assert sug["bucket"] == 3 and sug["below"] == 8

    def test_http_client_accessor_and_cold_start_stat(self, stack):
        c = httpclient.InferenceServerClient(stack["http"].url)
        try:
            out = c.get_profile(model_name="simple")
            assert "models" in out and out["models"]
            # batch 5 → bucket 8 is warm already; no compile entry
            _http_infer(c, 3)
            stat = c.get_infer_stat()
            assert stat["cold_start_count"] == 0
            # batch 16 → new bucket → cold start visible client-side
            _http_infer(c, 16)
            stat = c.get_infer_stat()
            assert stat["cold_start_count"] == 1
            assert stat["last_compile_s"] > 0.0
        finally:
            c.close()

    def test_grpc_profile_roundtrip(self, stack):
        c = grpcclient.InferenceServerClient(stack["grpc_url"])
        try:
            out = c.get_profile(model_name="simple")
            assert "models" in out and "duty_cycle" in out
            m = next(iter(out["models"].values()))
            assert any(b["fill_ratio"] < 1.0 for b in m["buckets"])
        finally:
            c.close()

    def test_metrics_expose_profiler_families(self, stack):
        text = stack["engine"].prometheus_metrics()
        for family in ("tpu_batch_fill_ratio", "tpu_padded_rows_total",
                       "tpu_xla_compilations_total",
                       "tpu_xla_compile_seconds",
                       "tpu_device_seconds_total", "tpu_device_duty_cycle"):
            assert family in text, family
        assert promlint.lint(text) == []
        om = stack["engine"].prometheus_metrics(openmetrics=True)
        assert promlint.lint(om, openmetrics=True) == []

    def test_profile_report_renders_live_and_saved(self, stack, tmp_path,
                                                   capsys):
        base = f"http://{stack['http'].url}"
        snap = profile_report.load_snapshot(base, model="simple")
        assert set(snap["models"]) == {"simple:1"}
        profile_report.render(snap)
        out = capsys.readouterr().out
        assert "model simple" in out and "fill" in out
        assert "suggestion: add bucket" in out
        # saved-snapshot path with model filter
        path = tmp_path / "prof.json"
        path.write_text(json.dumps(profile_report.load_snapshot(base)))
        assert profile_report.main([str(path), "--model", "simple"]) == 0
        out = capsys.readouterr().out
        assert "duty_cycle" in out


# -- decode wave stats (generative fused path) --------------------------------


class TestDecodeWaves:
    def test_record_wave_snapshot_and_duty(self):
        p, clk = _prof(window_s=10.0)
        clk.advance_s(20.0)
        p.record_wave("m", 1, bucket=8, chunk=4,
                      duration_ns=2_000_000_000, waves=4)
        snap = p.snapshot()
        m = snap["models"]["m:1"]
        waves = m["decode_waves"]
        assert len(waves) == 1
        w = waves[0]
        assert w["bucket"] == 8 and w["chunk"] == 4 and w["waves"] == 4
        assert w["device_s"] == pytest.approx(2.0)
        # 2s chunk of 4 waves -> 500ms per wave
        assert w["wave_ms_p50"] == pytest.approx(500.0)
        # wave time rolls into the model's device time and the duty cycle
        assert m["device_s"] == pytest.approx(2.0)
        assert p.duty_cycle() == pytest.approx(0.2, abs=1e-6)

    def test_wave_histogram_on_bound_registry(self):
        p, _ = _prof()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.record_wave("m", 1, bucket=8, chunk=1, duration_ns=3_000_000)
        text = reg.render()
        assert "tpu_decode_wave_seconds" in text
        assert 'bucket="8"' in text and 'chunk="1"' in text
        assert promlint.lint(text) == []

    def test_percentiles_over_many_waves(self):
        p, _ = _prof()
        for i in range(100):
            p.record_wave("m", 1, bucket=4, chunk=1,
                          duration_ns=(i + 1) * 1_000_000)
        w = p.snapshot()["models"]["m:1"]["decode_waves"][0]
        assert w["waves"] == 100
        assert 45 <= w["wave_ms_p50"] <= 55
        assert w["wave_ms_p99"] >= 95

    def test_reset_drops_waves(self):
        p, _ = _prof()
        p.record_wave("m", 1, bucket=4, chunk=1, duration_ns=1_000_000)
        p.reset()
        assert p.snapshot()["models"] == {}
