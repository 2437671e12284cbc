"""The program's set-up timeline (PR 57): the launcher's phases and the three
phases of every compilation as spans of one list on ``time.monotonic_ns()``,
fed by the process's one ``jax.monitoring`` listener."""

import importlib
import time

import jax
import jax.numpy as jnp
import pytest

from client_tpu.observability import spans
from client_tpu.observability.profiler import (
    _STARTUP_SPANS_MAX,
    EfficiencyProfiler,
    clear_compile_scope,
    install_compile_listener,
    process_start_ns,
    profiler,
    reset_profiler,
    set_compile_scope,
)

# (The package exports the function ``profiler`` under the module's name.)
mod = importlib.import_module("client_tpu.observability.profiler")
COMPILE = (spans.COMPILE_TRACE, spans.COMPILE_LOWER, spans.COMPILE_BACKEND)


@pytest.fixture
def fresh():
    """A fresh global profiler behind the installed listener."""
    install_compile_listener()
    reset_profiler()
    yield profiler()
    clear_compile_scope()
    reset_profiler()


def absolute(snap):
    """The snapshot's spans with their bounds in monotonic ns."""
    entry = snap["startup_clock"]["entry_monotonic_s"]
    return [dict(s, a=(entry + s["start_s"]) * 1e9,
                 b=(entry + s["end_s"]) * 1e9) for s in snap["startup"]]


def compiled_under_scope(scope, salt):
    """Compile a program nobody compiled before, under ``scope``; the
    monotonic readings around the call."""
    def step(x):
        return jnp.tanh(x * salt) + jnp.where(x > salt, x, -x)

    step.__name__ = step.__qualname__ = f"step{salt}"
    fn = jax.jit(step)
    x = jnp.arange(7, dtype=jnp.float32)
    set_compile_scope(*scope)
    t0 = time.monotonic_ns()
    fn(x).block_until_ready()
    t1 = time.monotonic_ns()
    clear_compile_scope()
    return fn, x, t0, t1


class TestCompileSpans:
    def test_one_span_a_phase_with_scope_and_program_on_one_clock(
            self, fresh):
        fn, x, t0, t1 = compiled_under_scope(("m", 1, "decode", 4), 31)
        mine = [s for s in absolute(fresh.snapshot())
                if s.get("scope") == "m:1:decode:4"]
        assert [s["name"] for s in mine] == list(COMPILE)
        trace, lower, backend = mine
        assert trace["fun_name"] == "step31"
        assert "step31" in lower["fun_name"] and \
            "step31" in backend["fun_name"]
        assert backend["cache"] == "miss"  # the suite runs without a cache
        assert "cache" not in trace and "cache" not in lower
        assert all(s["cause"] is None for s in mine)
        # In order, each inside the readings taken around the call (a
        # microsecond of slack: a float of seconds since the epoch has a
        # quarter of one).
        slack = 2_000
        assert t0 - slack <= trace["a"] <= trace["b"] <= lower["a"] + slack
        assert lower["b"] <= backend["a"] + slack
        assert backend["a"] <= backend["b"] <= t1 + slack
        # A second call of the same signature compiles nothing.
        before = fresh.snapshot()["startup"]
        set_compile_scope("m", 1, "decode", 4)
        fn(x).block_until_ready()
        clear_compile_scope()
        assert fresh.snapshot()["startup"] == before

    def test_a_function_traced_inside_another_leaves_one_trace_span(
            self, fresh):
        inner = jax.jit(lambda x: x * 2 + 1)

        def outer(x):
            return inner(x) + inner(x + 1)

        x = jnp.ones(3)  # made outside the scope: it compiles programs too
        set_compile_scope("m", 1, "prefill", 8)
        jax.jit(outer)(x).block_until_ready()
        clear_compile_scope()
        mine = [s for s in fresh.snapshot()["startup"]
                if s.get("scope") == "m:1:prefill:8"]
        assert [s["name"] for s in mine] == list(COMPILE)
        assert mine[0]["fun_name"] == "outer"

    def test_a_trace_inside_a_lowering_is_the_lowerings_time(self, fresh):
        """A Pallas kernel's body traces a jitted jnp function an operator
        while its program is being lowered: JAX reports each as a trace
        span, and the timeline keeps the lowering alone."""
        now = time.time()
        set_compile_scope("m", 1, "prefill", 512)
        mod._on_compile_start(mod.LOWER_EVENT, now)
        for k in range(3):
            mod._on_compile_start(mod.TRACE_EVENT, now + k)
            mod._on_compile_span(mod.TRACE_EVENT, now + k, now + k + 0.5,
                                 fun_name="less")
        mod._on_compile_span(mod.LOWER_EVENT, now, now + 4,
                             fun_name="jit(prefill)")
        mod._on_compile_start(mod.TRACE_EVENT, now + 5)
        mod._on_compile_span(mod.TRACE_EVENT, now + 5, now + 6,
                             fun_name="decode")
        clear_compile_scope()
        snap = fresh.snapshot()
        assert [(s["name"], s["fun_name"]) for s in snap["startup"]] == [
            ("compile.lower", "jit(prefill)"), ("compile.trace", "decode")]
        assert snap["compiles"]["trace_seconds"] == pytest.approx(1.0,
                                                                  abs=1e-5)
        assert snap["compiles"]["lower_seconds"] == pytest.approx(4.0,
                                                                  abs=1e-5)

    def test_the_sums_equal_the_sums_over_scopes(self, fresh):
        compiled_under_scope(("m", 1, "decode", 1), 41)
        compiled_under_scope(("m", 1, "decode", 2), 42)
        jax.jit(lambda x: x - 43)(jnp.ones(2)).block_until_ready()
        c = fresh.snapshot()["compiles"]
        rows = c["by_scope"].values()
        assert c["count"] == sum(r["count"] for r in rows) >= 3
        assert c["seconds"] == pytest.approx(sum(r["seconds"] for r in rows))
        assert c["trace_seconds"] == pytest.approx(
            sum(r["trace_s"] for r in rows))
        assert c["lower_seconds"] == pytest.approx(
            sum(r["lower_s"] for r in rows))
        assert c["cache_hits"] == sum(r["hits"] for r in rows) == 0
        one = c["by_scope"]["m:1:decode:1"]
        assert one["count"] == 1 and one["trace_s"] > 0 and one["lower_s"] > 0
        # The spans hold the same seconds as the sums.
        by = {name: 0.0 for name in COMPILE}
        for s in fresh.snapshot()["startup"]:
            if s["name"] in by:
                by[s["name"]] += s["end_s"] - s["start_s"]
        assert by[spans.COMPILE_TRACE] == pytest.approx(
            c["trace_seconds"], abs=1e-6)
        assert by[spans.COMPILE_LOWER] == pytest.approx(
            c["lower_seconds"], abs=1e-6)
        assert by[spans.COMPILE_BACKEND] == pytest.approx(
            c["seconds"], abs=1e-6)

    def test_reset_profiler_needs_no_re_registration(self, fresh):
        compiled_under_scope(("m", 1, "apply", 1), 51)
        assert fresh.snapshot()["compiles"]["count"] >= 1
        reset_profiler()
        assert profiler().snapshot()["compiles"]["count"] == 0
        compiled_under_scope(("m", 1, "apply", 2), 52)
        snap = profiler().snapshot()
        assert snap["compiles"]["by_scope"]["m:1:apply:2"]["count"] == 1
        assert [s["name"] for s in snap["startup"]
                if s.get("scope") == "m:1:apply:2"] == list(COMPILE)

    def test_one_listener_of_each_kind(self):
        from jax._src import monitoring

        install_compile_listener()
        install_compile_listener()
        assert monitoring.get_event_time_span_listeners().count(
            mod._on_compile_span) == 1
        assert monitoring.get_event_duration_listeners().count(
            mod._on_compile_duration) == 1
        assert monitoring.get_event_listeners().count(
            mod._on_compile_event) == 1
        assert monitoring.get_scalar_listeners().count(
            mod._on_compile_start) == 1

    def test_a_cache_hit_marks_the_backend_span_that_follows_it(self, fresh):
        set_compile_scope("m", 1, "decode", 16)
        now = time.time()
        mod._on_compile_event(mod.CACHE_HIT_EVENT)
        mod._on_compile_duration(mod.CACHE_RETRIEVAL_EVENT, 0.125)
        mod._on_compile_span(mod.BACKEND_COMPILE_EVENT, now - 0.5, now,
                             fun_name="jit_decode")
        mod._on_compile_span(mod.BACKEND_COMPILE_EVENT, now, now + 0.25,
                             fun_name="jit_decode")
        clear_compile_scope()
        snap = fresh.snapshot()
        hit, miss = [s for s in snap["startup"]
                     if s.get("scope") == "m:1:decode:16"]
        assert hit["cache"] == "hit" and hit["retrieval_s"] == 0.125
        assert miss["cache"] == "miss" and "retrieval_s" not in miss
        row = snap["compiles"]["by_scope"]["m:1:decode:16"]
        assert row["count"] == 2 and row["hits"] == 1
        assert row["seconds"] == pytest.approx(0.75, abs=1e-5)
        assert snap["compiles"]["cache_hits"] == 1


class TestPhases:
    def test_children_of_a_warm_up_partition_it(self):
        p = EfficiencyProfiler()
        ms = 1_000_000
        p.record_compile_span(spans.COMPILE_TRACE, 90 * ms, 95 * ms,
                              fun_name="before")  # the model load's
        p.record_startup(spans.STARTUP_MODEL_LOAD + "m", 50 * ms, 100 * ms)
        for k, (a, b) in enumerate([(110, 130), (130, 150), (150.5, 250)]):
            p.record_compile_span(COMPILE[k], int(a * ms), int(b * ms),
                                  ("m", 1, "decode", 8), "decode")
        p.record_compile_span(spans.COMPILE_BACKEND, 400 * ms, 500 * ms,
                              ("m", 1, "prefill", 8), "prefill", hit=True)
        p.record_startup(spans.STARTUP_WARMUP + "m", 100 * ms, 600 * ms,
                         rest=spans.STARTUP_FIRST_RUN + "m")
        p.record_compile_span(spans.COMPILE_TRACE, 700 * ms, 710 * ms,
                              fun_name="under_traffic")
        by = {}
        for s in p.snapshot()["startup"]:
            by.setdefault(s["name"], []).append(s)
        warm = by["startup.warmup:m"][0]
        runs = by["startup.first_run:m"]
        # The half millisecond between two phases of one compilation is no
        # program's first run.
        assert [(round(s["start_s"], 4), round(s["end_s"], 4))
                for s in runs] == [(0.05, 0.06), (0.2, 0.35), (0.45, 0.55)]
        children = runs + [s for s in by["compile.trace"]
                           + by["compile.lower"] + by["compile.backend"]
                           if s["cause"] == warm["name"]]
        assert len(children) == 7
        total = sum(s["end_s"] - s["start_s"] for s in children)
        assert total <= warm["end_s"] - warm["start_s"]
        assert total == pytest.approx(0.4995)
        causes = [s["cause"] for s in by["compile.trace"]]
        assert causes == ["startup.model_load:m", "startup.warmup:m", None]

    def test_the_bound_counts_what_it_drops(self):
        p = EfficiencyProfiler()
        for k in range(_STARTUP_SPANS_MAX + 5):
            p.record_compile_span(spans.COMPILE_LOWER, k * 10, k * 10 + 5)
        p.record_startup(spans.STARTUP_FRONTENDS, 0, 1)
        snap = p.snapshot()
        assert len(snap["startup"]) == _STARTUP_SPANS_MAX
        assert snap["startup_clock"]["dropped"] == 6
        # The sums are not the list's: they lose nothing.
        assert snap["compiles"]["lower_seconds"] == pytest.approx(
            (_STARTUP_SPANS_MAX + 5) * 5e-9)

    def test_the_snapshot_carries_the_absolute_entry(self):
        clock = [5_000_000_000]
        p = EfficiencyProfiler(now=lambda: clock[0])
        p.record_startup(spans.STARTUP_BACKEND_INIT, 4_000_000_000,
                         4_500_000_000)
        p.startup_entry()
        snap = p.snapshot()
        assert snap["startup_clock"] == {"entry_monotonic_s": 5.0,
                                         "dropped": 0}
        assert snap["startup"] == [{"name": "startup.backend_init",
                                    "start_s": -1.0, "end_s": -0.5}]
        # With no launcher the earliest start stands in for the entry.
        q = EfficiencyProfiler()
        q.record_startup(spans.STARTUP_MODEL_LOAD + "m", 7_000_000_000,
                         8_000_000_000)
        assert q.snapshot()["startup_clock"]["entry_monotonic_s"] == 7.0

    def test_the_process_span_runs_from_the_os_start_to_the_first_phase(
            self):
        born = process_start_ns()
        assert born is not None and born < time.monotonic_ns()
        # The interpreter started this module's imports after the OS
        # started the process, and no more than the suite's hours before.
        assert time.monotonic_ns() - born < 6 * 3600 * 1e9
        p = EfficiencyProfiler()
        now = time.monotonic_ns()
        p.record_process_start(now)
        p.record_startup(spans.STARTUP_BACKEND_INIT, now, now + 1000)
        p.startup_entry()  # a phase is there already: no second span
        p.record_process_start(now + 5)
        snap = p.snapshot()
        assert [s["name"] for s in snap["startup"]] == [
            "startup.process", "startup.backend_init"]
        first = snap["startup"][0]
        entry = snap["startup_clock"]["entry_monotonic_s"]
        assert (entry + first["start_s"]) * 1e9 == pytest.approx(
            born, abs=20e6)  # two readings of a clock of 10 ms ticks
        assert (entry + first["end_s"]) * 1e9 == pytest.approx(now, abs=1e3)

    def test_imports_run_from_the_last_phase_to_now(self):
        clock = [3_000]
        p = EfficiencyProfiler(now=lambda: clock[0])
        p.record_startup_since_last(spans.STARTUP_IMPORTS)  # nothing before
        assert p.snapshot()["startup"] == []
        p.record_startup(spans.STARTUP_BACKEND_INIT, 1_000, 2_000)
        p.record_compile_span(spans.COMPILE_TRACE, 2_100, 2_900)
        p.record_startup_since_last(spans.STARTUP_IMPORTS)
        last = p.snapshot()["startup"][-1]
        assert last["name"] == "startup.imports"
        assert (last["start_s"], last["end_s"]) == (
            pytest.approx(1e-6), pytest.approx(2e-6))
        # The compile span inside it is its child.
        assert p.snapshot()["startup"][1]["cause"] == "startup.imports"


class TestACompileAfterTheWindowsStart:
    def test_it_is_on_the_timeline_and_in_no_setup_sum(self, fresh):
        import os
        import sys

        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark")
        sys.path.insert(0, bench)
        import setupspans

        compiled_under_scope(("m", 1, "decode", 1), 61)
        fresh.record_startup(spans.STARTUP_FRONTENDS, time.monotonic_ns(),
                             time.monotonic_ns())
        t0 = time.monotonic()
        compiled_under_scope(("m", 1, "decode", 2), 62)
        ctx = {"snap_before": {"profile": fresh.snapshot()}, "t0": t0,
               "setup_s": 100.0, "traffic": {"preroll_s": 0}}
        late = [s for s in ctx["snap_before"]["profile"]["startup"]
                if s.get("scope") == "m:1:decode:2"]
        assert [s["name"] for s in late] == list(COMPILE)
        assert ctx["snap_before"]["profile"]["compiles"]["count"] >= 2
        kept = setupspans.spans(ctx)
        assert "m:1:decode:1" in {s.get("scope") for s in kept}
        assert "m:1:decode:2" not in {s.get("scope") for s in kept}
        early = fresh.snapshot()["compiles"]["by_scope"]["m:1:decode:1"]
        assert setupspans.summed(ctx, setupspans.TRACE) == pytest.approx(
            sum(s["b"] - s["a"] for s in kept
                if s["name"] == "compile.trace"))
        assert setupspans.summed(
            ctx, setupspans.BACKEND,
            where=lambda s: s.get("scope") == "m:1:decode:1") == \
            pytest.approx(early["seconds"], abs=1e-6)
