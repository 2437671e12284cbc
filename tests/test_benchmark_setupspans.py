"""The ``.setup`` readers of PR 57 (``benchmark/setupspans.py`` and one file a
metric under ``benchmark/metrics/``) on a hand-made launch: a harness clock, a
``/v2/profile`` snapshot at the window's start, a traffic file's pre-roll."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import setupspans  # noqa: E402

NEW = ["startup_process_s.setup", "startup_trace_s.setup",
       "startup_lower_s.setup", "startup_cache_miss_s.setup",
       "startup_first_run_s.setup", "setup_warm_traffic_s.setup",
       "setup_unspanned_s.setup"]
WARM_UP_CELLS = ["kimi_linear.longgen", "smallthinker_21b.mixed",
                 "nemotron3_nano_30b.assistant", "ouro_2b6.fewshot",
                 "command_a_plus.rag", "granite4_h_micro.helpdesk"]


def reader(name):
    path = os.path.join(BENCH, "metrics", name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name[:-6], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, a, b, **attrs):
    return {"name": name, "start_s": a, "end_s": b, **attrs}


def compile_spans(at, scope, trace, lower, backend, cache, cause=None):
    """One compilation's three spans, back to back from ``at``."""
    common = {"cause": cause, "scope": scope, "fun_name": "jit_" + scope}
    return [span("compile.trace", at, at + trace, **common),
            span("compile.lower", at + trace, at + trace + lower, **common),
            span("compile.backend", at + trace + lower,
                 at + trace + lower + backend, cache=cache, **common)]


# The harness launched at 1000.0 on the machine's monotonic clock; the
# program's entry was at 1004.0 and everything below is relative to it.  The
# window opened at 1100.0: setup_s 100, a pre-roll of 36 + 0.25 s.
ENTRY, T0, SETUP_S, PREROLL_S = 1004.0, 1100.0, 100.0, 36.0
STARTUP = [
    span("startup.process", -3.9, -1.0),            # OS start at 1000.1
    span("startup.backend_init", -1.0, 0.5),
    *compile_spans(1.0, "", 0.25, 0.25, 0.5, "hit",
                   cause="startup.imports"),         # a module-level jit
    span("startup.imports", 0.5, 3.0),
    *compile_spans(4.0, "", 0.0, 0.0, 2.0, "hit",
                   cause="startup.model_load:m"),    # the weights' programs
    span("startup.model_load:m", 3.5, 10.0),
    *compile_spans(10.0, "m:1:prefill:512", 3.0, 2.0, 5.0, "miss",
                   cause="startup.warmup:m"),
    span("startup.first_run:m", 20.0, 24.0),
    *compile_spans(24.0, "m:1:decode:8", 1.0, 1.0, 2.0, "hit",
                   cause="startup.warmup:m"),
    span("startup.first_run:m", 28.0, 30.0),
    span("startup.warmup:m", 10.0, 30.0),
    span("startup.frontends", 30.0, 30.5),
    # A compile inside the warm traffic (a bucket the launcher did not warm),
    # and one on another thread that overlaps it.
    *compile_spans(40.0, "m:1:decode:4", 0.5, 0.5, 3.0, "hit"),
    span("compile.backend", 42.0, 45.0, cause=None, scope="", fun_name="x",
         cache="miss"),
    # After the window's start: in the snapshot, in no sum.
    *compile_spans(96.0, "m:1:decode:2", 1.0, 1.0, 1.0, "miss"),
]


def ctx(startup=STARTUP, clock=True, preroll_s=PREROLL_S):
    profile = {"startup": startup,
               "compiles": {"count": 7, "seconds": 16.5, "by_scope": {}}}
    if clock:
        profile["startup_clock"] = {"entry_monotonic_s": ENTRY, "dropped": 0}
    return {"snap_before": {"t": T0, "stats": {}, "profile": profile},
            "t0": T0, "setup_s": SETUP_S,
            "traffic": {"preroll_s": preroll_s}, "phases": {}}


class TestReaders:
    def test_each_reads_its_spans(self):
        got = {name: reader(name)(ctx()) for name in NEW}
        assert got["startup_process_s.setup"] == pytest.approx(2.9 + 2.5)
        assert got["startup_trace_s.setup"] == pytest.approx(
            0.25 + 0.0 + 3.0 + 1.0 + 0.5)
        assert got["startup_lower_s.setup"] == pytest.approx(
            0.25 + 0.0 + 2.0 + 1.0 + 0.5)
        # The prefill program's compile and the overlapping one's.
        assert got["startup_cache_miss_s.setup"] == pytest.approx(5.0 + 3.0)
        assert got["startup_first_run_s.setup"] == pytest.approx(6.0)
        # Serving at 1034.5, released at 1100 - 36.25 = 1063.75; the compile
        # spans inside cover [40, 45] of the program's clock once.
        assert got["setup_warm_traffic_s.setup"] == pytest.approx(
            (1063.75 - 1034.5) - 5.0)
        # Under no span: launch to the OS's start (0.1), imports' end to the
        # model load (0.5); the pre-roll and the warm traffic are named.
        assert got["setup_unspanned_s.setup"] == pytest.approx(0.6)

    def test_a_compile_after_the_windows_start_counts_nowhere(self):
        late = [s for s in STARTUP if s.get("scope") == "m:1:decode:2"]
        assert len(late) == 3
        without = [s for s in STARTUP if s not in late]
        for name in NEW:
            assert reader(name)(ctx()) == pytest.approx(
                reader(name)(ctx(without)))

    def test_none_on_a_parents_snapshot(self):
        old = [span(s["name"], s["start_s"], s["end_s"]) for s in STARTUP
               if s["name"].startswith(("startup.backend_init",
                                        "startup.model_load",
                                        "startup.warmup",
                                        "startup.frontends"))]
        for name in NEW:
            assert reader(name)(ctx(old, clock=False)) is None
            assert reader(name)({"snap_before": None, "t0": T0,
                                 "setup_s": SETUP_S, "traffic": {}}) is None
        assert setupspans.partition(ctx(old, clock=False)) is None

    def test_zero_and_not_none_where_a_launch_has_no_such_span(self):
        bare = [span("startup.process", -3.9, 0.0),
                span("startup.frontends", 30.0, 30.5)]
        got = {name: reader(name)(ctx(bare)) for name in NEW}
        assert got["startup_cache_miss_s.setup"] == 0.0
        assert got["startup_trace_s.setup"] == 0.0
        assert got["startup_lower_s.setup"] == 0.0
        assert got["startup_first_run_s.setup"] == 0.0
        assert got["startup_process_s.setup"] == pytest.approx(3.9)
        assert all(isinstance(v, float) for v in got.values())

    def test_the_accepted_readers_read_what_they_read(self):
        for name, want in [("startup_backend_init_s.setup", 1.5),
                           ("startup_model_load_s.setup", 6.5),
                           ("startup_compile_s.setup", 16.5)]:
            assert reader(name)(ctx()) == pytest.approx(want)
            # ... and on a snapshot without the clock, as on the parent.
            assert reader(name)(ctx(clock=False)) == pytest.approx(want)


class TestPartition:
    def test_the_parts_sum_to_setup_s(self):
        p = setupspans.partition(ctx())
        parts = ["startup_process_s", "startup_backend_init_s",
                 "startup_model_load_s", "startup_trace_s", "startup_lower_s",
                 "startup_compile_s", "startup_first_run_s", "frontends_s",
                 "setup_warm_traffic_s", "preroll_s"]
        assert p["preroll_s"] == pytest.approx(36.25)
        assert p["startup_compile_s"] == pytest.approx(0.5 + 2 + 5 + 2 + 3 + 3)
        # Counted twice: the compile inside the model load (2.0; the one
        # inside the imports is counted under the imports and as a compile
        # too: 1.0) and where two threads compiled at once ([42, 44]).
        assert p["overlap_s"] == pytest.approx(2.0 + 1.0 + 2.0)
        assert sum(p[k] for k in parts) - p["overlap_s"] \
            + p["setup_unspanned_s"] == pytest.approx(SETUP_S)
        assert p["setup_s"] == SETUP_S

    def test_spans_that_overlap_are_covered_once(self):
        assert setupspans.covered(
            [(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)], -1, 10) == 5.0
        assert setupspans.covered([(0, 2), (1, 3), (5, 7)], 1, 6) == 3.0
        assert setupspans.covered([], 0, 10) == 0.0

    def test_no_pre_roll_and_no_frontends(self):
        c = ctx(preroll_s=0)
        assert setupspans.release(c) == T0 - 0.25
        assert reader("setup_warm_traffic_s.setup")(c) == pytest.approx(
            (T0 - 0.25 - 1034.5) - 5.0)
        headless = [s for s in STARTUP if s["name"] != "startup.frontends"]
        assert reader("setup_warm_traffic_s.setup")(ctx(headless)) is None
        # Nothing names the harness's seconds then: they are unspanned.
        assert reader("setup_unspanned_s.setup")(ctx(headless)) > 36.0


class TestManifest:
    def test_the_seven_are_listed_last_with_their_cells(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        cells = [w["name"] for w in manifest["workloads"]]
        # (Last until PR 59 put its one behind them.)
        last = manifest["per_layer"][-8:-1]
        assert [m["name"] for m in last] == NEW
        for m in last:
            assert (m["unit"], m["better"], m["moves"], m["source"]) == (
                "s", "lower", "setup_s", "program_span")
            want = WARM_UP_CELLS if m["name"].startswith("startup_first_run") \
                else cells
            assert m["workloads"] == want
            assert os.path.exists(os.path.join(
                BENCH, "metrics", m["name"].rsplit(".", 1)[0] + ".py"))

    def test_first_run_is_listed_where_the_launcher_warms_up(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        for w in manifest["workloads"]:
            with open(os.path.join(BENCH, "traffic",
                                   w["traffic"] + ".json")) as f:
                warms = "--warmup" in json.load(f).get("server_args", [])
            assert warms == (w["name"] in WARM_UP_CELLS)


class TestRecordedLaunches:
    def test_the_paper_check_passes(self, capsys):
        """``benchmark/testdata/check_setup_spans.py``: the ten readers on
        two launches recorded on the chip (one cell that warms up in the
        launcher, one that does not), ``setup_unspanned_s.setup`` under a
        tenth of ``setup_s``."""
        sys.path.insert(0, os.path.join(BENCH, "testdata"))
        import check_setup_spans

        assert check_setup_spans.main() == 0
        said = capsys.readouterr().out
        assert "FAIL" not in said and said.count("\nok ") + 1 >= 16
