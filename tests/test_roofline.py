"""Roofline attribution plane (PR-19): XLA static cost capture, the
peak-spec registry + ``CLIENT_TPU_ROOFLINE`` grammar, the join math
(MFU/MBU/AI/bound), and the surfaces that carry it — profiler snapshot,
``tpu_mfu``/``tpu_mbu``/``tpu_model_flops_total`` metrics, fleet drift
signals, ``tools/profile_report.py --roofline``, and both transports
end to end.

Unit sections drive the pure functions and a fake-clock profiler with
hand-built cost dicts — no engine required. Capture tests exercise a
real ``jax.jit`` lowering on CPU (cost_analysis works there) plus fake
objects for every degradation path: the contract is *annotated absence,
never a raise*. The e2e section boots the real stack once with an env
peaks override (the CPU escape hatch) so MFU is computable off-TPU.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.models import build_repository
from client_tpu.observability import events
from client_tpu.observability import fleet as fleet_obs
from client_tpu.observability import roofline
from client_tpu.observability.metrics import MetricRegistry
from client_tpu.observability.profiler import (
    EfficiencyProfiler,
    profiler,
    reset_profiler,
)
from client_tpu.observability.roofline import (
    ENV_VAR,
    PEAK_SPECS,
    PeakSpec,
    RooflineConfig,
    bert_flops_per_example,
    bucket_roofline,
    capture_cost_model,
    capture_memory_analysis,
    classify_bound,
    require_device_peaks,
)
from client_tpu.observability.timeseries import MODEL_SIGNALS
from client_tpu.server import GrpcInferenceServer, HttpInferenceServer


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..",
                           "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


promlint = _load_tool("promlint")


@pytest.fixture(autouse=True)
def _clean_roofline(monkeypatch):
    """Every test starts with no env override and a fresh device-kind
    detection cache (the cache is process-global by design)."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    roofline.reset_roofline()
    yield
    roofline.reset_roofline()


class FakeClock:
    def __init__(self, t_ns=1_000_000_000):
        self.t = t_ns

    def __call__(self):
        return self.t

    def advance_s(self, s):
        self.t += int(s * 1e9)


PEAKS = PeakSpec(1000.0, 100.0, source="env")  # ridge = 10 flops/byte


def _cost(flops=100.0, byts=50.0):
    return {"available": True, "flops": flops, "bytes_accessed": byts,
            "transcendentals": 0.0}


# -- the join: bucket_roofline ------------------------------------------------


class TestJoinMath:
    def test_rates_intensity_and_utilization(self):
        # 4 warm calls x (100 flops, 50 B) over 2 s against (1000, 100)
        rl = bucket_roofline(_cost(), calls=4, device_s=2.0,
                             padded_fraction=0.25, peaks=PEAKS)
        assert rl["cost_model"] == "xla"
        assert rl["flops_per_call"] == 100.0
        assert rl["bytes_per_call"] == 50.0
        assert rl["total_flops"] == 400.0
        assert rl["total_bytes"] == 200.0
        assert rl["arithmetic_intensity"] == pytest.approx(2.0)
        assert rl["achieved_flops_per_s"] == pytest.approx(200.0)
        assert rl["achieved_bytes_per_s"] == pytest.approx(100.0)
        assert rl["mfu"] == pytest.approx(0.2)
        assert rl["mbu"] == pytest.approx(1.0)
        # padded fraction of the static FLOPs multiplied zeros
        assert rl["padding_wasted_flops"] == pytest.approx(100.0)
        # AI 2 < ridge 10 -> bandwidth-bound
        assert rl["bound"] == "bandwidth"

    def test_compute_bound_above_ridge(self):
        peaks = PeakSpec(100.0, 1000.0)  # ridge = 0.1
        rl = bucket_roofline(_cost(), calls=1, device_s=1.0, peaks=peaks)
        assert rl["bound"] == "compute"

    def test_no_peaks_degrades_to_measured_only(self):
        rl = bucket_roofline(_cost(), calls=2, device_s=1.0, peaks=None)
        assert rl["achieved_flops_per_s"] == pytest.approx(200.0)
        assert rl["mfu"] is None and rl["mbu"] is None
        assert rl["bound"] == "unknown"

    def test_partial_peaks_computes_what_it_can(self):
        rl = bucket_roofline(_cost(), calls=1, device_s=1.0,
                             peaks=PeakSpec(1000.0, None))
        assert rl["mfu"] == pytest.approx(0.1)
        assert rl["mbu"] is None
        assert rl["bound"] == "unknown"  # no ridge without bandwidth

    def test_zero_bytes_means_no_intensity(self):
        # gather-only executables (embedding bag) report ~0 flops too
        rl = bucket_roofline(_cost(flops=0.0, byts=0.0), calls=3,
                             device_s=1.0, peaks=PEAKS)
        assert rl["arithmetic_intensity"] is None
        assert rl["bound"] == "unknown"
        assert rl["mfu"] == 0.0

    def test_no_device_time_keeps_totals_but_no_rates(self):
        rl = bucket_roofline(_cost(), calls=0, device_s=0.0, peaks=PEAKS)
        assert rl["total_flops"] == 0.0
        assert rl["achieved_flops_per_s"] is None
        assert rl["mfu"] is None

    def test_unavailable_cost_is_annotated_absence(self):
        rl = bucket_roofline({"available": False, "reason": "no backend"},
                             calls=5, device_s=1.0, peaks=PEAKS)
        assert rl == {"cost_model": "unavailable", "reason": "no backend",
                      "bound": "unknown"}
        rl = bucket_roofline(None, calls=5, device_s=1.0, peaks=PEAKS)
        assert rl["cost_model"] == "unavailable"
        assert rl["reason"] == "not captured"

    def test_padded_fraction_clamped(self):
        rl = bucket_roofline(_cost(), calls=1, device_s=1.0,
                             padded_fraction=1.5, peaks=PEAKS)
        assert rl["padding_wasted_flops"] == pytest.approx(100.0)


class TestClassifyBound:
    def test_thresholds(self):
        assert classify_bound(9.99, PEAKS) == "bandwidth"
        assert classify_bound(10.0, PEAKS) == "compute"  # at the ridge
        assert classify_bound(None, PEAKS) == "unknown"
        assert classify_bound(2.0, None) == "unknown"
        assert classify_bound(2.0, PeakSpec(None, 100.0)) == "unknown"


# -- peak registry + env grammar ---------------------------------------------


class TestPeakRegistry:
    def test_registry_resolution_case_insensitive(self):
        spec = RooflineConfig().resolve_peaks("TPU v5e")
        assert spec.flops_per_s == PEAK_SPECS["tpu v5e"].flops_per_s
        assert spec.source == "registry"

    def test_substring_match_for_kind_variants(self):
        # libtpu has reported "TPU v5 lite" and longer strings
        spec = RooflineConfig().resolve_peaks("TPU v5 lite (something)")
        assert spec.flops_per_s == PEAK_SPECS["tpu v5 lite"].flops_per_s

    def test_cpu_and_unknown_kinds_resolve_to_none(self):
        assert RooflineConfig().resolve_peaks("cpu") is None
        assert RooflineConfig().resolve_peaks("unknown") is None

    def test_explicit_pair_beats_everything(self):
        cfg = RooflineConfig(peak_flops=1e12, peak_bytes_per_s=1e11,
                             device_kinds={"tpu v5e": PeakSpec(1.0, 1.0)})
        spec = cfg.resolve_peaks("TPU v5e")
        assert spec.flops_per_s == 1e12 and spec.source == "env"

    def test_env_device_kinds_beat_registry(self):
        cfg = RooflineConfig(
            device_kinds={"tpu v5e": PeakSpec(7.0, 8.0, source="env")})
        spec = cfg.resolve_peaks("TPU v5e")
        assert spec.flops_per_s == 7.0 and spec.source == "env"

    def test_unlisted_tpu_kind_is_a_startup_error(self):
        from types import SimpleNamespace as Dev

        require_device_peaks([Dev(platform="tpu", device_kind="TPU v5 lite")])
        require_device_peaks([Dev(platform="cpu", device_kind="cpu")])
        with pytest.raises(RuntimeError, match="TPU v99"):
            require_device_peaks([Dev(platform="tpu", device_kind="TPU v99")])
        # An env row is a row: the operator named the chip.
        require_device_peaks(
            [Dev(platform="tpu", device_kind="TPU v99")],
            environ={"CLIENT_TPU_ROOFLINE": json.dumps({"device_kinds": {
                "tpu v99": {"peak_flops": 1e12,
                            "peak_bytes_per_s": 1e11}}})})

    def test_ridge(self):
        assert PEAKS.ridge() == pytest.approx(10.0)
        assert PeakSpec(None, 100.0).ridge() is None
        assert PeakSpec(100.0, None).ridge() is None


class TestEnvGrammar:
    def test_unset_defaults_on(self):
        cfg = roofline.roofline_config({})
        assert cfg.capture is True and cfg.peak_flops is None

    @pytest.mark.parametrize("raw", ["1", "on", "true", "TRUE"])
    def test_enable_aliases(self, raw):
        assert roofline.roofline_config({ENV_VAR: raw}).capture is True

    @pytest.mark.parametrize("raw", ["0", "off", "false"])
    def test_disable_aliases(self, raw):
        assert roofline.roofline_config({ENV_VAR: raw}).capture is False

    def test_inline_json_peaks(self):
        cfg = roofline.roofline_config(
            {ENV_VAR: '{"peak_flops": 1e12, "peak_bytes_per_s": 1e11}'})
        spec = cfg.resolve_peaks("cpu")
        assert spec.flops_per_s == 1e12 and spec.bytes_per_s == 1e11

    def test_at_file(self, tmp_path):
        p = tmp_path / "roofline.json"
        p.write_text('{"peak_flops": 5e12}')
        cfg = roofline.roofline_config({ENV_VAR: f"@{p}"})
        assert cfg.peak_flops == 5e12

    @pytest.mark.parametrize("raw,needle", [
        ("@/nonexistent/roofline.json", "cannot read"),
        ("{not json", "invalid JSON"),
        ("[1, 2]", "expected a JSON object"),
        ('{"peak_flopz": 1}', "unknown key"),
        ('{"peak_flops": "fast"}', "expects a number"),
        ('{"peak_flops": true}', "expects a number"),
        ('{"peak_flops": -1}', "must be > 0"),
        ('{"peak_flops": 0}', "must be > 0"),
        ('{"capture": "yes"}', "expects a boolean"),
        ('{"device_kinds": [1]}', "expects an object"),
        ('{"device_kinds": {"x": 3}}', "expects an"),
        ('{"device_kinds": {"x": {"peak_watts": 1}}}', "unknown"),
        ('{"device_kinds": {"x": {"peak_flops": -2}}}', "must be > 0"),
    ])
    def test_malformed_values_fail_fast(self, raw, needle):
        with pytest.raises(ValueError, match="CLIENT_TPU_ROOFLINE"):
            try:
                roofline.roofline_config({ENV_VAR: raw})
            except ValueError as exc:
                assert needle in str(exc)
                raise

    def test_context_annotates_instead_of_raising(self):
        ctx = roofline.roofline_context({ENV_VAR: "{bad"})
        assert ctx["peaks"] == "unknown"
        assert "invalid JSON" in ctx["config_error"]

    def test_resolve_peaks_swallows_malformed_env(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "{bad")
        assert roofline.resolve_peaks() is None

    def test_engine_fails_fast_at_startup(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, '{"peak_flops": -1}')
        reset_profiler()
        with pytest.raises(ValueError, match="CLIENT_TPU_ROOFLINE"):
            TpuEngine(build_repository(["simple"]), warmup=False)
        reset_profiler()


# -- static cost capture: degrade, never raise --------------------------------


class _FakeLowered:
    def __init__(self, analysis):
        self._analysis = analysis

    def cost_analysis(self):
        if isinstance(self._analysis, Exception):
            raise self._analysis
        return self._analysis


class _FakeJitted:
    def __init__(self, analysis):
        self._analysis = analysis

    def lower(self, *args, **kwargs):
        if isinstance(self._analysis, Exception) \
                and str(self._analysis) == "lower boom":
            raise self._analysis
        return _FakeLowered(self._analysis)


class TestCaptureCostModel:
    def test_real_jit_on_cpu(self):
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x: jnp.dot(x, x) + 1.0)
        x = np.ones((8, 8), np.float32)
        fn(x)  # trace-cache the lowering like the serve path does
        cost = capture_cost_model(fn, (x,))
        assert cost["available"] is True
        assert cost["flops"] > 0
        assert cost["bytes_accessed"] > 0

    def test_not_jitted(self):
        cost = capture_cost_model(lambda x: x, (1,))
        assert cost["available"] is False
        assert "no .lower" in cost["reason"]

    def test_lower_raises(self):
        cost = capture_cost_model(_FakeJitted(RuntimeError("lower boom")))
        assert cost["available"] is False
        assert "RuntimeError" in cost["reason"]

    def test_cost_analysis_raises(self):
        cost = capture_cost_model(
            _FakeJitted(NotImplementedError("no cost model")))
        assert cost["available"] is False
        assert "NotImplementedError" in cost["reason"]

    def test_cost_analysis_returns_none(self):
        cost = capture_cost_model(_FakeJitted(None))
        assert cost["available"] is False
        assert "NoneType" in cost["reason"]

    def test_missing_both_keys(self):
        cost = capture_cost_model(_FakeJitted({"utilization": 1.0}))
        assert cost["available"] is False
        assert "neither" in cost["reason"]

    def test_legacy_list_of_dicts_form(self):
        cost = capture_cost_model(
            _FakeJitted([{"flops": 12.0, "bytes accessed": 34.0}]))
        assert cost["available"] is True
        assert cost["flops"] == 12.0 and cost["bytes_accessed"] == 34.0

    def test_empty_list(self):
        cost = capture_cost_model(_FakeJitted([]))
        assert cost["available"] is False

    def test_negative_sentinels_clamped(self):
        cost = capture_cost_model(
            _FakeJitted({"flops": -1.0, "bytes accessed": 64.0,
                         "transcendentals": -1.0}))
        assert cost["flops"] == 0.0
        assert cost["bytes_accessed"] == 64.0
        assert cost["transcendentals"] == 0.0

    def test_partial_keys_default_zero(self):
        cost = capture_cost_model(_FakeJitted({"flops": 8.0}))
        assert cost["available"] is True
        assert cost["bytes_accessed"] == 0.0

    def test_capture_disabled_by_env(self):
        cfg = RooflineConfig(capture=False)
        cost = capture_cost_model(_FakeJitted({"flops": 1.0}), config=cfg)
        assert cost["available"] is False
        assert ENV_VAR in cost["reason"]

    def test_malformed_env_falls_back_to_defaults(self, monkeypatch):
        # late env mutation must not break the serve path
        monkeypatch.setenv(ENV_VAR, "{bad")
        cost = capture_cost_model(_FakeJitted({"flops": 2.0}))
        assert cost["available"] is True


class TestCaptureMemoryAnalysis:
    def test_attrs_extracted(self):
        class Mem:
            argument_size_in_bytes = 128
            output_size_in_bytes = 64
            temp_size_in_bytes = 0

        class Compiled:
            def memory_analysis(self):
                return Mem()

        out = capture_memory_analysis(Compiled())
        assert out["available"] is True
        assert out["argument_size_in_bytes"] == 128
        assert "generated_code_size_in_bytes" not in out

    def test_none_and_raise_degrade(self):
        class NoneCompiled:
            def memory_analysis(self):
                return None

        class BadCompiled:
            def memory_analysis(self):
                raise RuntimeError("unimplemented")

        assert capture_memory_analysis(NoneCompiled())["available"] is False
        assert capture_memory_analysis(BadCompiled())["available"] is False
        assert capture_memory_analysis(object())["available"] is False


# -- profiler join: snapshot + gauges -----------------------------------------


def _prof():
    clk = FakeClock()
    return EfficiencyProfiler(window_s=60.0, now=clk), clk


PEAKS_ENV = '{"peak_flops": 1e3, "peak_bytes_per_s": 1e2}'


class TestProfilerJoin:
    def test_bucket_roofline_joins_warm_calls_only(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, _cost())
        # cold call: counted, but excluded from the rate denominator
        p.record_execution("m", 1, 8, rows=8, device_ns=5_000_000_000,
                           cold=True)
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        snap = p.snapshot()
        assert snap["roofline"]["peaks"]["flops_per_s"] == 1e3
        b = snap["models"]["m:1"]["buckets"][0]
        rl = b["roofline"]
        assert rl["cost_model"] == "xla"
        assert rl["total_flops"] == 200.0      # 2 warm x 100
        assert rl["achieved_flops_per_s"] == pytest.approx(100.0)
        assert rl["mfu"] == pytest.approx(0.1)
        assert rl["mbu"] == pytest.approx(0.5)
        assert rl["bound"] == "bandwidth"      # AI 2 < ridge 10
        # model rollup covers this bucket's device time fully
        mrl = snap["models"]["m:1"]["roofline"]
        assert mrl["mfu"] == pytest.approx(0.1)
        assert mrl["cost_model_coverage"] == pytest.approx(1.0)

    def test_padding_wasted_flops_from_fill(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, _cost())
        # 2 real rows padded to 8 -> 6/8 of the static FLOPs are zeros
        p.record_execution("m", 1, 8, rows=2, device_ns=1_000_000_000)
        rl = p.snapshot()["models"]["m:1"]["buckets"][0]["roofline"]
        assert rl["padding_wasted_flops"] == pytest.approx(100.0 * 6 / 8)

    def test_uncaptured_bucket_annotated(self):
        p, _ = _prof()
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        rl = p.snapshot()["models"]["m:1"]["buckets"][0]["roofline"]
        assert rl["cost_model"] == "unavailable"
        assert rl["reason"] == "not captured"
        assert rl["bound"] == "unknown"

    def test_unavailable_capture_recorded_with_reason(self):
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, {"available": False,
                                        "reason": "interpret mode"})
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        rl = p.snapshot()["models"]["m:1"]["buckets"][0]["roofline"]
        assert rl["cost_model"] == "unavailable"
        assert rl["reason"] == "interpret mode"

    def test_available_capture_wins_over_unavailable(self):
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, {"available": False, "reason": "x"})
        p.record_cost_model("m", 1, 8, _cost())
        p.record_cost_model("m", 1, 8, {"available": False, "reason": "y"})
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        rl = p.snapshot()["models"]["m:1"]["buckets"][0]["roofline"]
        assert rl["cost_model"] == "xla"      # the unavailable re-capture
        assert rl["flops_per_call"] == 100.0  # did not clobber the good one

    def test_no_peaks_on_cpu_is_measured_only(self):
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, _cost())
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        snap = p.snapshot()
        assert snap["roofline"]["peaks"] == "unknown"
        rl = snap["models"]["m:1"]["buckets"][0]["roofline"]
        assert rl["achieved_flops_per_s"] == pytest.approx(100.0)
        assert rl["mfu"] is None
        assert rl["bound"] == "unknown"

    def test_wave_roofline_uses_dispatches(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        p.record_wave_cost_model("g", 1, 8, 4, _cost(flops=40.0, byts=4.0))
        # one dispatch covering 4 logical waves, then another
        p.record_wave("g", 1, 8, 4, duration_ns=500_000_000, waves=4)
        p.record_wave("g", 1, 8, 4, duration_ns=500_000_000, waves=4)
        snap = p.snapshot()
        w = snap["models"]["g:1"]["decode_waves"][0]
        assert w["dispatches"] == 2
        rl = w["roofline"]
        # cost is per *dispatch*: 2 x 40 flops over 1 s
        assert rl["total_flops"] == 80.0
        assert rl["mfu"] == pytest.approx(0.08)
        mrl = snap["models"]["g:1"]["roofline"]
        assert mrl["total_flops"] == 80.0

    def test_model_rollup_mixes_buckets_and_waves(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, _cost())
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        p.record_wave_cost_model("m", 1, 8, 1, _cost(flops=50.0, byts=10.0))
        p.record_wave("m", 1, 8, 1, duration_ns=1_000_000_000)
        mrl = p.snapshot()["models"]["m:1"]["roofline"]
        assert mrl["total_flops"] == 150.0
        assert mrl["total_bytes"] == 60.0
        assert mrl["cost_model_coverage"] == pytest.approx(1.0)
        assert mrl["achieved_flops_per_s"] == pytest.approx(75.0)

    def test_coverage_honest_when_one_bucket_lacks_cost(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        p.record_cost_model("m", 1, 8, _cost())
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        p.record_execution("m", 1, 16, rows=16, device_ns=3_000_000_000)
        mrl = p.snapshot()["models"]["m:1"]["roofline"]
        assert mrl["cost_model_coverage"] == pytest.approx(0.25)

    def test_snapshot_never_raises_on_malformed_env(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "{bad")
        p, _ = _prof()
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        snap = p.snapshot()
        assert snap["roofline"]["peaks"] == "unknown"
        assert "config_error" in snap["roofline"]


class TestRooflineMetrics:
    def test_gauges_and_flops_counter(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.record_cost_model("m", 1, 8, _cost())
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000,
                           cold=True)
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        p.update_gauges()
        text = reg.render()
        # counter ticks per *warm* call (cold calls excluded)
        assert 'tpu_model_flops_total{model="m",version="1",bucket="8"} '\
            '200' in text
        assert 'tpu_mfu{model="m",version="1",bucket="8"} 0.1' in text
        assert 'tpu_mbu{model="m",version="1",bucket="8"} 0.5' in text
        assert promlint.lint(text) == []
        om = reg.render(openmetrics=True)
        assert "tpu_mfu" in om
        assert promlint.lint(om, openmetrics=True) == []

    def test_wave_dispatches_tick_flops_counter(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, PEAKS_ENV)
        p, _ = _prof()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.record_wave_cost_model("g", 1, 8, 2, _cost(flops=30.0))
        p.record_wave("g", 1, 8, 2, duration_ns=1_000_000, waves=2)
        text = reg.render()
        assert 'tpu_model_flops_total{model="g",version="1",bucket="8"} '\
            '30' in text

    def test_no_peaks_means_no_samples_but_clean_exposition(self):
        p, _ = _prof()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.record_cost_model("m", 1, 8, _cost())
        p.record_execution("m", 1, 8, rows=8, device_ns=1_000_000_000)
        p.update_gauges()
        text = reg.render()
        # family declared, no rows: absent-but-lintable beats lying zeros
        assert "# TYPE tpu_mfu gauge" in text
        assert 'tpu_mfu{' not in text
        assert promlint.lint(text) == []
        assert promlint.lint(reg.render(openmetrics=True),
                             openmetrics=True) == []


# -- fleet: drift signals + federation ----------------------------------------


def _snap_with_mfu(mfu, device_s=10.0):
    return {
        "window_s": 600.0, "duty_cycle": 0.5,
        "roofline": {"device_kind": "tpu v5e",
                     "peaks": PeakSpec(1e12, 1e11).as_dict()},
        "models": {"m:1": {
            "model": "m", "version": "1", "device_s": device_s,
            "buckets": [], "roofline": {"mfu": mfu, "mbu": 0.5,
                                        "bound": "compute"},
        }},
    }


class TestFleetRoofline:
    def test_profile_signal_device_time_weighted(self):
        snap = _snap_with_mfu(0.4)
        snap["models"]["n:1"] = {
            "model": "n", "version": "1", "device_s": 30.0,
            "buckets": [], "roofline": {"mfu": 0.2},
        }
        sig = fleet_obs.profile_signals(snap)
        # (0.4*10 + 0.2*30) / 40
        assert sig["mfu"] == pytest.approx(0.25)

    def test_signal_omitted_without_evidence(self):
        snap = _snap_with_mfu(None)
        assert "mfu" not in fleet_obs.profile_signals(snap)

    def test_merge_profiles_scores_mfu_drift(self):
        merged = fleet_obs.merge_profiles({
            "r0": _snap_with_mfu(0.40),
            "r1": _snap_with_mfu(0.41),
            "r2": _snap_with_mfu(0.10),  # the sick replica
        })
        fleet = merged["fleet"]
        assert fleet["signals"]["r2"]["mfu"] == pytest.approx(0.10)
        assert fleet["medians"]["mfu"] == pytest.approx(0.40)
        scores = fleet["drift_scores"]
        assert scores["r2"]["mfu"] > scores["r1"]["mfu"]
        # per-replica roofline passes through untouched for --fleet
        assert merged["replicas"]["r0"]["models"]["m:1"]["roofline"][
            "mfu"] == 0.40

    def test_timeseries_signals_median_mfu(self):
        export = {"samples": [
            {"ts_wall": 100.0 + i,
             "signals": {"mfu": {"m": 0.3 + 0.1 * (i % 2)}}}
            for i in range(10)
        ]}
        sig = fleet_obs.timeseries_signals(export, window_s=60.0, now=110.0)
        assert sig["mfu"] == pytest.approx(0.35)

    def test_mfu_registered_as_model_signal(self):
        assert "mfu" in MODEL_SIGNALS
        assert "mfu" in fleet_obs.SIGNAL_FLOORS


# -- tools/profile_report.py --roofline ---------------------------------------


class TestProfileReportRoofline:
    def _render(self, snap, capsys, tmp_path):
        profile_report = _load_tool("profile_report")
        path = tmp_path / "snap.json"
        path.write_text(json.dumps(snap))
        profile_report.main([str(path), "--roofline"])
        return capsys.readouterr().out

    def test_renders_buckets_waves_and_header(self, capsys, tmp_path):
        snap = _snap_with_mfu(0.4)
        snap["models"]["m:1"]["buckets"] = [{
            "bucket": 8, "axis": "rows", "executions": 4,
            "cold_executions": 1, "rows": 24, "padded_rows": 8,
            "device_s": 2.0, "fill_ratio": 0.75,
            "roofline": bucket_roofline(_cost(), 3, 2.0, 0.25, PEAKS),
        }]
        snap["models"]["m:1"]["decode_waves"] = [{
            "bucket": 8, "chunk": 4, "waves": 8, "dispatches": 2,
            "device_s": 1.0, "wave_ms_p50": 5.0,
            "roofline": bucket_roofline(_cost(), 2, 1.0, 0.0, PEAKS),
        }]
        out = self._render(snap, capsys, tmp_path)
        assert "tpu v5e" in out
        assert "bandwidth" in out
        assert "wave*4" in out

    def test_renders_peaks_unknown_and_unavailable(self, capsys, tmp_path):
        snap = _snap_with_mfu(None)
        snap["roofline"] = {"device_kind": "cpu", "peaks": "unknown"}
        snap["models"]["m:1"]["buckets"] = [{
            "bucket": 8, "axis": "rows", "executions": 1,
            "cold_executions": 1, "rows": 8, "padded_rows": 0,
            "device_s": 0.0, "fill_ratio": 1.0,
            "roofline": {"cost_model": "unavailable",
                         "reason": "interpret mode", "bound": "unknown"},
        }]
        out = self._render(snap, capsys, tmp_path)
        assert "peaks unknown" in out
        assert "unavailable: interpret mode" in out

    def test_renders_config_error(self, capsys, tmp_path):
        snap = _snap_with_mfu(None)
        snap["roofline"] = {"device_kind": "cpu", "peaks": "unknown",
                            "config_error": "CLIENT_TPU_ROOFLINE: bad"}
        out = self._render(snap, capsys, tmp_path)
        assert "CONFIG ERROR" in out


# -- e2e: the real stack on CPU with the env escape hatch ---------------------


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


@pytest.fixture()
def peaks_env(monkeypatch):
    """The CPU escape hatch: capture happens at first call regardless;
    peaks are resolved at snapshot/scrape time, so a per-test env
    override is enough to make MFU computable off-TPU."""
    monkeypatch.setenv(
        ENV_VAR, '{"peak_flops": 1e12, "peak_bytes_per_s": 1e11}')


def _http_infer(client, batch):
    a = np.arange(16 * batch, dtype=np.int32).reshape(batch, 16)
    b = np.ones((batch, 16), dtype=np.int32)
    i0 = httpclient.InferInput("INPUT0", a.shape, "INT32")
    i0.set_data_from_numpy(a)
    i1 = httpclient.InferInput("INPUT1", b.shape, "INT32")
    i1.set_data_from_numpy(b)
    return client.infer("simple", [i0, i1])


class TestRooflineE2e:
    def test_http_profile_carries_roofline(self, stack, peaks_env):
        c = httpclient.InferenceServerClient(stack["http"].url)
        try:
            for _ in range(3):
                _http_infer(c, 3)
        finally:
            c.close()
        snap = stack["engine"].profile_snapshot(model="simple")
        assert snap["roofline"]["peaks"]["flops_per_s"] == 1e12
        m = next(iter(snap["models"].values()))
        assert m["roofline"]["mfu"] is not None
        assert m["roofline"]["bound"] in ("compute", "bandwidth")
        b = next(b for b in m["buckets"] if b["bucket"] == 8)
        rl = b["roofline"]
        assert rl["cost_model"] == "xla"
        assert rl["flops_per_call"] > 0
        # warm-only join: 3 calls, 1 cold
        assert rl["total_flops"] == pytest.approx(2 * rl["flops_per_call"])

    def test_grpc_profile_carries_roofline(self, stack, peaks_env):
        with grpcclient.InferenceServerClient(stack["grpc_url"]) as c:
            out = c.get_profile(model_name="simple")
        assert out["roofline"]["peaks"]["flops_per_s"] == 1e12
        m = next(iter(out["models"].values()))
        assert m["roofline"]["mfu"] is not None

    def test_metrics_expose_mfu_both_dialects(self, stack, peaks_env):
        text = stack["engine"].prometheus_metrics()
        assert 'tpu_mfu{model="simple"' in text
        assert 'tpu_mbu{model="simple"' in text
        assert 'tpu_model_flops_total{model="simple"' in text
        # the registry block (which carries the new families) lints clean
        assert promlint.lint(stack["engine"].metrics.render()) == []
        om = stack["engine"].prometheus_metrics(openmetrics=True)
        assert "tpu_mfu" in om
        assert promlint.lint(om, openmetrics=True) == []

    def test_timeseries_sample_carries_mfu(self, stack, monkeypatch):
        # A peak that the model's few hundred operations a call stay in
        # sight of: against ``peaks_env``'s 1e12 a warm call of a
        # millisecond, which a loaded host takes, rounds to an MFU of 0.0
        # in the sample's six decimals (peaks are resolved at sample time).
        monkeypatch.setenv(
            ENV_VAR, '{"peak_flops": 1e6, "peak_bytes_per_s": 1e5}')
        sample = stack["engine"].timeseries_sample()
        assert sample["mfu"]["simple"] > 0


class TestRooflineE2eNoPeaks:
    def test_cpu_host_degrades_gracefully(self):
        reset_profiler()
        events.reset_journal()
        eng = TpuEngine(build_repository(["simple"]), warmup=False)
        try:
            a = np.zeros((2, 16), np.int32)
            eng.infer(InferRequest(model_name="simple",
                                   inputs={"INPUT0": a, "INPUT1": a}))
            snap = eng.profile_snapshot(model="simple")
            assert snap["roofline"]["peaks"] == "unknown"
            m = next(iter(snap["models"].values()))
            # static cost captured; ratios degrade, nothing errors
            rl = m["buckets"][0]["roofline"]
            assert rl["cost_model"] == "xla"
            assert rl["mfu"] is None
            assert rl["bound"] == "unknown"
            assert m["roofline"]["mfu"] is None
            # scrape stays promlint-clean with empty mfu families
            om = eng.prometheus_metrics(openmetrics=True)
            assert promlint.lint(om, openmetrics=True) == []
        finally:
            eng.shutdown()
            reset_profiler()
            events.reset_journal()


class TestSharedDenominator:
    def test_bert_flops_formula(self):
        s, h, f = 128, 768, 3072
        per_layer = 8 * s * h * h + 4 * s * s * h + 4 * s * h * f
        assert bert_flops_per_example() == 12 * per_layer
        assert bert_flops_per_example(seq_len=1) < bert_flops_per_example()

    def test_bench_reexports_it(self):
        import bench

        assert bench.bert_flops_per_example is bert_flops_per_example
