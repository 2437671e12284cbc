"""The benchmark's readers of the program's own clock
(``benchmark/progspans.py`` and one reader per metric under
``benchmark/metrics/``) on hand-made ``/v2/profile`` snapshots, and
``benchmark/hostgaps.py`` on a hand-made trace and on the piece of a v5e trace
recorded by PR 24's traced run."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import hostgaps  # noqa: E402
import progspans  # noqa: E402

NEW_METRICS = [
    "sched_host_ms_per_wave.itl", "sched_device_wait_share.itl",
    "sched_dispatch_ms_mean.itl", "drain_multi_wave_share.obs",
    "wave_live_lanes_mean.itl", "wave_padded_lane_share.itl",
    "arena_live_share.itl", "first_token_wait_ms_mean.obs",
    "xla_compiles_in_window.itl", "startup_backend_init_s.setup",
    "startup_model_load_s.setup", "startup_compile_s.setup",
]


def reader(name):
    """The way ``run.py`` finds a reader: the file named by the metric,
    or by the metric less its suffix."""
    stem = name if os.path.exists(
        os.path.join(BENCH, "metrics", name + ".py")) \
        else name.rsplit(".", 1)[0]
    path = os.path.join(BENCH, "metrics", stem + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def snap(spans_ms, counters, compiles=(0, 0.0), startup=()):
    """A harness snapshot whose one generative model has these span totals
    (name -> (count, ms)) and counters."""
    return {"t": 0.0, "stats": {}, "profile": {
        "compiles": {"count": compiles[0], "seconds": compiles[1],
                     "cache_misses": 0, "by_scope": {}},
        "startup": [{"name": n, "start_s": a, "end_s": b}
                    for n, a, b in startup],
        "models": {"gpt:1": {"model": "gpt", "version": "1", "generative": {
            "spans": {k: {"count": n, "total_ns": int(ms * 1e6),
                          "max_ns": int(ms * 1e6)}
                      for k, (n, ms) in spans_ms.items()},
            "counters": dict(counters)}}}}}


BEFORE = snap(
    {"gen.loop": (100, 50_000), "gen.idle": (1, 1000), "gen.admit": (5, 10),
     "gen.prefill_dispatch": (5, 20), "gen.sweep": (100, 5),
     "gen.wave_stage": (100, 30), "gen.wave_dispatch": (100, 100),
     "gen.fetch_wait": (105, 48_000), "gen.emit": (105, 200)},
    {"dispatches": 100, "fetched_waves": 70, "fetched_lanes_live": 3000,
     "fetched_lanes_padded": 1000, "fetched_positions_valid": 2_000_000,
     "drains": 90,
     "drains_multi": 10, "first_tokens": 30, "first_token_wait_ns": 3e11},
    compiles=(14, 31.5),
    startup=[("startup.backend_init", -9.0, -0.5),
             ("startup.model_load:gpt", 0.25, 4.25),
             ("startup.model_load:other", 4.25, 5.25),
             ("startup.warmup:gpt", 5.25, 40.0),
             ("startup.frontends", 40.0, 40.5)])
AFTER = snap(
    {"gen.loop": (122, 70_000), "gen.idle": (1, 1000), "gen.admit": (8, 16),
     "gen.prefill_dispatch": (8, 36), "gen.sweep": (122, 6),
     "gen.wave_stage": (122, 41), "gen.wave_dispatch": (122, 1100),
     "gen.fetch_wait": (130, 66_500), "gen.emit": (130, 260)},
    {"dispatches": 122, "fetched_waves": 92, "fetched_lanes_live": 3858,
     "fetched_lanes_padded": 1150, "fetched_positions_valid": 2_700_000,
     "drains": 110,
     "drains_multi": 12, "first_tokens": 34, "first_token_wait_ns": 3.6e11},
    compiles=(14, 31.5))
CTX = {"snap_before": BEFORE, "snap_after": AFTER,
       "cfg": {"serve": {"kwargs": {"max_streams": 48}}},
       "traffic": {"max_model_len": 1024}}

# Worked out by hand from BEFORE/AFTER above.
EXPECTED = {
    # (20000 - 18500 - 0 - 1000 - 16) ms over 22 dispatches
    "sched_host_ms_per_wave.itl": 484 / 22,
    # fetch 18500 + dispatch 1000 + prefill dispatch 16 of 20000 ms
    "sched_device_wait_share.itl": 100 * 19_516 / 20_000,
    "sched_dispatch_ms_mean.itl": 1000 / 22,
    "drain_multi_wave_share.obs": 100 * 2 / 20,
    "wave_live_lanes_mean.itl": 858 / 22,
    "wave_padded_lane_share.itl": 100 * 150 / (858 + 150),
    "arena_live_share.itl": 100 * 700_000 / (22 * 48 * 1024),
    "first_token_wait_ms_mean.obs": 0.6e11 / 4 / 1e6,
    "xla_compiles_in_window.itl": 0.0,
    "startup_backend_init_s.setup": 8.5,
    "startup_model_load_s.setup": 5.0,
    "startup_compile_s.setup": 31.5,
}


class TestReaders:
    @pytest.mark.parametrize("name", NEW_METRICS)
    def test_value_on_a_hand_made_window(self, name):
        assert reader(name)(CTX) == pytest.approx(EXPECTED[name])

    @pytest.mark.parametrize("name", NEW_METRICS)
    def test_nothing_to_read_on_the_parent(self, name):
        """A program without the spans (the parent commit) serves a
        profile with none of the three objects: the reader returns nothing
        and does not raise."""
        old = {"t": 0.0, "stats": {}, "profile": {"models": {"gpt:1": {
            "decode_waves": []}}}}
        ctx = dict(CTX, snap_before=old, snap_after=old)
        assert reader(name)(ctx) is None
        assert reader(name)(dict(ctx, snap_before=None,
                                 snap_after=None)) is None

    def test_every_new_metric_is_in_the_manifest_with_a_reader(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        by = {m["name"]: m for m in manifest["per_layer"]}
        for name in NEW_METRICS:
            m = by[name]
            assert "gpt2_small.chat" in m["workloads"]
            assert m["moves"] == ("setup_s" if name.endswith(".setup")
                                  else "itl_mean_ms")
            assert callable(reader(name))
        # together and in the order they were added (later PRs append
        # after them: the manifest only grows at its end)
        names = [m["name"] for m in manifest["per_layer"]]
        at = names.index(NEW_METRICS[0])
        assert names[at:at + len(NEW_METRICS)] == NEW_METRICS

    def test_every_counter_a_reader_asks_for_is_in_the_vocabulary(self):
        """The readers ask the snapshot for counters by name: each name is
        one the program serves (a misspelt one would read as 0)."""
        import re

        from client_tpu.observability import spans

        asked = set()
        for name in NEW_METRICS:
            stem = name.rsplit(".", 1)[0]
            with open(os.path.join(BENCH, "metrics", stem + ".py")) as f:
                src = f.read()
            asked |= set(re.findall(r'"((?:fetched_|first_|drains|dispatches)'
                                    r'[a-z_]*)"', src))
            for span in re.findall(r'"(gen\.[a-z_]+)"', src):
                assert span in spans.GEN_SPANS, (name, span)
        assert asked and asked <= set(spans.GEN_COUNTERS), asked

    def test_window_sums_models_and_keeps_the_run_max(self):
        two = json.loads(json.dumps(AFTER))
        two["profile"]["models"]["b:1"] = json.loads(json.dumps(
            AFTER["profile"]["models"]["gpt:1"]))
        g = progspans.generative(two)
        assert g["counters"]["dispatches"] == 244
        assert g["spans"]["gen.loop"]["count"] == 244
        w = progspans.window({"snap_before": BEFORE, "snap_after": AFTER})
        assert w["counters"]["dispatches"] == 22
        assert w["spans"]["gen.fetch_wait"]["total_ns"] == 18_500_000_000
        assert w["spans"]["gen.fetch_wait"]["max_ns"] == 66_500_000_000

    def test_zero_denominators_read_as_nothing(self):
        ctx = dict(CTX, snap_after=BEFORE)  # an empty window
        for name in NEW_METRICS[:8]:
            assert reader(name)(ctx) is None


# Device: two programs with a gap of 3 us between them, then a gap of 2 us
# to a third.  Host thread: gen.loop over everything; inside it a
# gen.fetch_wait covering the first gap's first 2 us and a gen.wave_stage
# with a gen.wave_dispatch nested in it over part of the second gap; an
# exec.run on another thread that no gap touches.
HAND_MADE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_decode(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_prefill(77)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "worker" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 9200000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 9500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 200000 } }
  lines { id: 2 name: "batcher" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 100000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "gen.loop" } }
  event_metadata { key: 2 value { id: 2 name: "gen.fetch_wait" } }
  event_metadata { key: 3 value { id: 3 name: "gen.wave_stage" } }
  event_metadata { key: 4 value { id: 4 name: "gen.wave_dispatch" } }
  event_metadata { key: 5 value { id: 5 name: "exec.run" } }
  event_metadata { key: 6 value { id: 6 name: "$python call" } } }
"""


def _load_text(text):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


class TestHostGaps:
    def test_hand_made_trace(self):
        out = hostgaps.reduce_gaps(_load_text(HAND_MADE))
        assert out["gaps"] == 2
        assert out["idle_s"] == pytest.approx(5e-6)
        by = {name: (seconds, n) for name, seconds, n in out["by_span"]}
        # gap 1 = [5000, 8000) ns: fetch_wait covers to 7000, then the loop
        # gap 2 = [10000, 12000): loop to 10200, stage to 10500, dispatch
        # to 11500, stage again to 11700, loop to 12000
        assert by["gen.fetch_wait"] == (pytest.approx(2.0e-6), 1)
        assert by["gen.wave_dispatch"] == (pytest.approx(1.0e-6), 1)
        assert by["gen.wave_stage"] == (pytest.approx(0.5e-6), 0)
        assert by["gen.loop"] == (pytest.approx(1.5e-6), 0)
        assert "exec.run" not in by and hostgaps.NO_SPAN not in by
        assert sum(s for s, _ in by.values()) == pytest.approx(5e-6)
        assert out["host_spans"] == {
            "gen.loop": 1, "gen.fetch_wait": 1, "gen.wave_stage": 1,
            "gen.wave_dispatch": 2, "exec.run": 1}

    def test_trace_without_annotations_is_all_no_span(self):
        device_only = HAND_MADE[:HAND_MADE.index('planes { id: 2')]
        out = hostgaps.reduce_gaps(_load_text(device_only))
        assert out["by_span"] == [[hostgaps.NO_SPAN,
                                   pytest.approx(5e-6), 2]]

    def test_trace_without_a_device_plane_has_no_gaps(self):
        host_only = HAND_MADE[HAND_MADE.index('planes { id: 2'):]
        out = hostgaps.reduce_gaps(_load_text(host_only))
        assert out["gaps"] == 0 and out["by_span"] == []

    def test_recorded_v5e_trace(self):
        """The piece of PR 24's traced chip run kept under
        ``benchmark/testdata/``: figures recomputed by brute force."""
        sys.path.insert(0, os.path.join(BENCH, "testdata"))
        import check_hostgaps

        assert check_hostgaps.main() == 0

    def test_idle_gaps_ignore_overlapping_programs(self):
        mods = [("a", 0, 10), ("b", 5, 8), ("c", 12, 20), ("d", 20, 25)]
        assert hostgaps.idle_gaps(mods) == [(10, 12)]


# -- PR 27's checks as tier-1 tests, and PR 28's readers ----------------------

@pytest.mark.parametrize("script", ["check_readers", "check_spread",
                                    "check_kimi_linear"])
def test_the_testdata_checks_pass(script):
    """``benchmark/testdata/check_readers.py`` (PR 27's four readers on a
    hand-made context) and ``check_spread.py`` (the manifest's window and
    bound against the rule on the recorded sets): ``main()`` returns 0."""
    sys.path.insert(0, os.path.join(BENCH, "testdata"))
    import importlib

    assert importlib.import_module(script).main() == 0


CACHE_METRICS = [
    "cache_rows_live_share.itl", "cache_summary_row_share.itl",
    "cache_context_per_row.obs", "window_dumps_per_s.obs",
    "window_dump_device_share.itl", "decode_attn_roofline.itl",
]
CELL = "evabyte_6b5.longdoc"


def cache_ctx():
    """A window of 1000 waves of 10 live lanes, each lane reading 1000
    summaries and 1000 exact rows at a context of 17000 positions; 20 dumps
    in 50 s; a trace of 4 s holding 300 whole waves and 20 pieces."""
    import numpy as np

    with open(os.path.join(BENCH, "configs", "evabyte_6b5.json")) as f:
        cfg = json.load(f)
    zero = dict.fromkeys(
        ("fetched_waves", "fetched_lanes_live", "fetched_positions_valid",
         "fetched_rows_exact", "fetched_rows_summary", "transitions"), 0)
    after = dict(fetched_waves=1000, fetched_lanes_live=10_000,
                 fetched_positions_valid=170_000_000,
                 fetched_rows_exact=10_000_000,
                 fetched_rows_summary=10_000_000, transitions=20)
    layers = cfg["num_hidden_layers"]
    # the decode kernel at its roofline: 10 lanes x 2001 rows x 4096 x 2 B x
    # K,V = 327.8 MB a layer at 819 GB/s, 300 waves x 8 layers; traced at
    # twice that.
    least = 2 * 10 * 2001 * 4096 * 2 / 819e9
    trace = {"window_s": 4.0, "modules": {
        "jit_decode": {"count": 300, "total_s": 3.0, "mean_ms": 10.0},
        "jit_prefill": {"count": 20, "total_s": 0.9, "mean_ms": 45.0},
        "jit_transition": {"count": 2, "total_s": 0.002, "mean_ms": 1.0}},
        "device_ops": [
            ["decode_wave_attention.3_bf16_8_17_4096_4096_",
             2 * 300 * layers * least],
            ["fusion.7_bf16_2048_11008_", 0.5],
            ["flash_attention.5_bf16_1_32_2048_128_", 20 * layers * 1e-3]]}
    req = {"prompt_len": np.asarray([2048.0 * 3]),
           "in_window": np.asarray([True]), "ok": np.asarray([True])}
    return {"cfg": cfg, "seconds": 50.0, "trace": trace, "req": req,
            "device": {"kind": "TPU v5e"},
            "snap_before": snap({"gen.transition_dispatch": (0, 0)}, zero),
            "snap_after": snap({"gen.transition_dispatch": (20, 8)}, after)}


class TestCacheReaders:
    def test_values_on_a_hand_made_window(self):
        ctx = cache_ctx()
        got = {name: reader(name)(ctx) for name in CACHE_METRICS}
        assert got["cache_rows_live_share.itl"] == pytest.approx(
            100 * 20_000 / (16 * 4096))
        assert got["cache_summary_row_share.itl"] == pytest.approx(50.0)
        assert got["cache_context_per_row.obs"] == pytest.approx(8.5)
        assert got["window_dumps_per_s.obs"] == pytest.approx(0.4)
        assert got["window_dump_device_share.itl"] == pytest.approx(0.05)
        assert got["decode_attn_roofline.itl"] == pytest.approx(
            50.0 * 2000 / 2001 * (2001 / 2000), rel=1e-3)
        # a trace that happens to hold no dump reads 0, not nothing
        del ctx["trace"]["modules"]["jit_transition"]
        assert reader("window_dump_device_share.itl")(ctx) == 0.0
        assert all(v is not None and v <= 100 for v in got.values())

    @pytest.mark.parametrize("name", CACHE_METRICS)
    def test_nothing_to_read_on_the_parent(self, name):
        """The parent serves none of the counters, the span or the
        programs, and its configuration has no ``cache_slot_rows``: each
        reader returns nothing and does not raise."""
        ctx = cache_ctx()
        old = {"t": 0.0, "stats": {}, "profile": {"models": {"gpt:1": {
            "decode_waves": [], "generative": {
                "spans": {"gen.loop": {"count": 1, "total_ns": 1,
                                       "max_ns": 1}},
                "counters": {"fetched_waves": 5}}}}}}
        with open(os.path.join(BENCH, "configs", "gpt2_small.json")) as f:
            gpt = json.load(f)
        parent = dict(ctx, cfg=gpt, snap_before=old, snap_after=old,
                      trace={"window_s": 4.0, "modules": {"jit_decode": {
                          "count": 3, "total_s": 1.0, "mean_ms": 4.0}},
                          "device_ops": [["fusion.1_f32_8_", 1.0]]})
        assert reader(name)(parent) is None
        assert reader(name)(dict(parent, trace=None, snap_before=None,
                                 snap_after=None)) is None

    def test_the_manifest_lists_them_for_the_cell_only(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        by = {m["name"]: m for m in manifest["per_layer"]}
        for name in CACHE_METRICS:
            # (The decode kernel's share is read on the other cells that
            # run the kernel as well: a global layer's calls, PR 43, and an
            # attention layer's between state-space layers, PR 45, and a
            # looped stack's 48 calls a wave, PR 50, and a full layer's call
            # at a query group of 16, PR 53.)
            # (and a whole model's four calls at heads of 64 in groups of
            # four, PR 59.)
            others = (["smallthinker_21b.mixed",
                       "nemotron3_nano_30b.assistant", "ouro_2b6.fewshot",
                       "command_a_plus.rag", "granite4_h_micro.helpdesk"]
                      if name == "decode_attn_roofline.itl" else [])
            assert by[name]["workloads"] == [CELL] + others
            assert by[name]["moves"] == "itl_mean_ms"
        for name in ("arena_live_share.itl", "kv_live_share.itl"):
            assert CELL not in by[name]["workloads"]   # slots x positions
        from client_tpu.observability import spans

        for name in ("fetched_rows_exact", "fetched_rows_summary",
                     "prompts_admitted", "prefill_pieces", "transitions"):
            assert name in spans.GEN_COUNTERS
        assert "gen.transition_dispatch" in spans.GEN_SPANS


# -- PR 31's readers: how tokens leave the worker, how full a prefill is --------

HANDOFF_METRICS = {
    # name -> (cells, the share worked out by hand from the two snapshots)
    # 9000 - 1000 tokens by the wave, 1100 - 100 one response each
    "emit_wave_handoff_share.itl": (
        ["gpt2_small.chat", "evabyte_6b5.longdoc"], 100 * 8000 / 9000),
    # 700 - 100 lanes held a prompt, 250 - 50 were padding
    "prefill_live_lane_share.itl": (["gpt2_small.chat"], 100 * 600 / 800),
}
HANDOFF_BEFORE = {"emit_handoffs": 40, "emitted_tokens": 1000,
                  "emitted_tokens_callback": 100, "prefill_lanes_live": 100,
                  "prefill_lanes_padded": 50}
HANDOFF_AFTER = {"emit_handoffs": 300, "emitted_tokens": 9000,
                 "emitted_tokens_callback": 1100, "prefill_lanes_live": 700,
                 "prefill_lanes_padded": 250}


def run_reader(name):
    """By its manifest name, the way the harness loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from run import load_reader

    return load_reader(name)


class TestHandoffReaders:
    @pytest.mark.parametrize("name", HANDOFF_METRICS)
    def test_share_worked_out_by_hand(self, name):
        ctx = {"snap_before": snap({}, HANDOFF_BEFORE),
               "snap_after": snap({}, HANDOFF_AFTER)}
        assert run_reader(name)(ctx) == pytest.approx(
            HANDOFF_METRICS[name][1])

    @pytest.mark.parametrize("name", HANDOFF_METRICS)
    def test_nothing_where_the_counters_are_missing(self, name):
        """The parent serves ``generative`` without the five counters, an
        older one no ``generative`` at all, and a window that saw no token
        or no prefill has nothing to divide by: None each time, never 0 and
        never a raise."""
        read = run_reader(name)
        parent = snap({}, {"fetched_waves": 5, "drains": 3})
        assert read({"snap_before": parent, "snap_after": parent}) is None
        bare = {"t": 0.0, "stats": {}, "profile": {"models": {"gpt:1": {
            "decode_waves": []}}}}
        assert read({"snap_before": bare, "snap_after": bare}) is None
        assert read({"snap_before": None, "snap_after": None}) is None
        still = snap({}, HANDOFF_AFTER)
        assert read({"snap_before": still, "snap_after": still}) is None

    def test_the_manifest_holds_them_side_by_side(self):
        """Where PR 31 appended them (later entries stand behind), each for
        the cells it named then and whatever cells joined since."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        names = [m["name"] for m in manifest["per_layer"]]
        first = names.index(next(iter(HANDOFF_METRICS)))
        tail = manifest["per_layer"][first:first + len(HANDOFF_METRICS)]
        assert [m["name"] for m in tail] == list(HANDOFF_METRICS)
        for m in tail:
            cells = HANDOFF_METRICS[m["name"]][0]
            assert m["workloads"][:len(cells)] == cells
            assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
                "generative scheduler", "itl_mean_ms", "program_counter", "%")
        from client_tpu.observability import spans

        assert set(HANDOFF_BEFORE) <= set(spans.GEN_COUNTERS)


# -- PR 41's readers: a prompt's two waits, the token gap by what stood between
# two waves, the host's work around a piece -------------------------------------

ALL_CELLS = ["gpt2_small.chat", "evabyte_6b5.longdoc",
             "pangu_ultra_moe.reasoning", "kimi_linear.longgen"]
GAP_BEFORE = {"prompts_started": 10, "admit_wait_ns": 50_000_000,
              "prefill_line_wait_ns": 4_000_000_000, "gap_lanes": 1000,
              "gap_lane_ns": 20_000_000_000, "gap_lanes_behind_prefill": 400,
              "gap_lane_behind_prefill_ns": 12_000_000_000}
GAP_AFTER = {"prompts_started": 30, "admit_wait_ns": 290_000_000,
             "prefill_line_wait_ns": 84_000_000_000, "gap_lanes": 11_000,
             "gap_lane_ns": 320_000_000_000,
             "gap_lanes_behind_prefill": 6400,
             "gap_lane_behind_prefill_ns": 252_000_000_000}
# The window, by hand: 20 prompts started, 240 ms and 80 s of waits; 10 000
# gaps summing 300 s, of which 6000 behind a prefill sum 240 s (40 ms each)
# and 4000 plain sum 60 s (15 ms each): a prefill costs a stream 25 ms, and
# 6000 x 25 ms = 150 s of the 300 are the prefills'.
GAP_METRICS = {
    # name -> (cells, source, unit, the value by hand)
    "admit_wait_ms_mean.obs": (ALL_CELLS, "program_counter", "ms", 12.0),
    "prefill_line_wait_ms_mean.obs": (ALL_CELLS, "program_counter", "ms",
                                      4000.0),
    "gaps_behind_prefill_share.obs": (ALL_CELLS, "program_counter", "%",
                                      60.0),
    "prefill_gap_cost_ms.itl": (ALL_CELLS, "program_counter", "ms", 25.0),
    "prefill_gap_share.itl": (ALL_CELLS, "program_counter", "%", 50.0),
    "prefill_stage_ms_mean.itl": (ALL_CELLS[1:], "program_span", "ms", 0.75),
}
STAGE_BEFORE = {"gen.prefill_stage": (100, 60), "gen.prefill_dispatch":
                (100, 900)}
STAGE_AFTER = {"gen.prefill_stage": (900, 660), "gen.prefill_dispatch":
               (900, 9000)}


class TestGapReaders:
    @pytest.mark.parametrize("name", GAP_METRICS)
    def test_value_worked_out_by_hand(self, name):
        ctx = {"snap_before": snap(STAGE_BEFORE, GAP_BEFORE),
               "snap_after": snap(STAGE_AFTER, GAP_AFTER)}
        assert run_reader(name)(ctx) == pytest.approx(GAP_METRICS[name][3])

    @pytest.mark.parametrize("name", GAP_METRICS)
    def test_nothing_without_the_counters_or_with_an_empty_class(self, name):
        """The parent serves ``generative`` without the seven counters and
        the span, an older one no ``generative`` at all; a window in which
        nothing started, or (for the two that subtract the classes) whose
        gaps all held a prefill or none did, has nothing to divide by:
        None each time, never 0 and never a raise."""
        read = run_reader(name)
        parent = snap({"gen.prefill_dispatch": (5, 20)},
                      {"fetched_waves": 5, "first_tokens": 3})
        assert read({"snap_before": parent, "snap_after": parent}) is None
        grown = snap({"gen.prefill_dispatch": (9, 40)},
                     {"fetched_waves": 50, "first_tokens": 7})
        assert read({"snap_before": parent, "snap_after": grown}) is None
        bare = {"t": 0.0, "stats": {}, "profile": {"models": {"gpt:1": {
            "decode_waves": []}}}}
        assert read({"snap_before": bare, "snap_after": bare}) is None
        assert read({"snap_before": None, "snap_after": None}) is None
        still = snap(STAGE_AFTER, GAP_AFTER)
        assert read({"snap_before": still, "snap_after": still}) is None
        if name in ("prefill_gap_cost_ms.itl", "prefill_gap_share.itl"):
            before = snap({}, GAP_BEFORE)
            for behind in (0, 10_000):       # no gap held a prefill; all did
                after = dict(GAP_AFTER, gap_lanes_behind_prefill=400 + behind,
                             gap_lane_behind_prefill_ns=12_000_000_000
                             + behind * 30_000_000)
                assert read({"snap_before": before,
                             "snap_after": snap({}, after)}) is None

    def test_the_manifest_holds_them_at_its_end(self):
        """Appended in the issue's order behind everything older, each for
        its cells and whatever cells join later; the scheduler's layer,
        lower is better, recorded beside ``itl_mean_ms``."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        names = [m["name"] for m in manifest["per_layer"]]
        first = names.index(next(iter(GAP_METRICS)))
        assert first > names.index("prefill_padded_position_share.itl")
        tail = manifest["per_layer"][first:first + len(GAP_METRICS)]
        assert [m["name"] for m in tail] == list(GAP_METRICS)
        for m in tail:
            cells, source, unit, _ = GAP_METRICS[m["name"]]
            assert m["workloads"][:len(cells)] == cells
            assert (m["layer"], m["moves"], m["better"], m["source"],
                    m["unit"]) == ("generative scheduler", "itl_mean_ms",
                                   "lower", source, unit)
        from client_tpu.observability import spans

        assert set(GAP_BEFORE) <= set(spans.GEN_COUNTERS)
        assert "gen.prefill_stage" in spans.GEN_SPANS


# -- PR 47's reader: the prompts a piece program held ---------------------------

LANES = "prefill_lanes_per_call.obs"
HEADS = "prefill_head_share.itl"
CARRIED = "wave_carried_share.itl"
# (pieces, programs) at the window's two ends -> the value by hand.
LANE_WINDOWS = {
    "every_piece_alone": ((40, 40), (1040, 1040), 1.0),
    "half_the_pieces_paired": ((40, 40), (1240, 840), 1.5),
    "a_one_shot_prefill_counts_no_piece": ((0, 40), (0, 1040), None),
    "no_piece_in_the_window": ((40, 40), (40, 40), None),
}


class TestLanesPerCall:
    @pytest.mark.parametrize("window", sorted(LANE_WINDOWS))
    def test_pieces_over_programs(self, window):
        """``prefill_pieces`` counts a lane's piece each, the span
        ``gen.prefill_dispatch`` a program each; nothing where no piece was
        counted (a one-shot prefill's programs are not pieces)."""
        (p0, c0), (p1, c1), want = LANE_WINDOWS[window]
        ctx = {"snap_before": snap({"gen.prefill_dispatch": (c0, 9)},
                                   {"prefill_pieces": p0}),
               "snap_after": snap({"gen.prefill_dispatch": (c1, 99)},
                                  {"prefill_pieces": p1})}
        got = run_reader(LANES)(ctx)
        assert got is None if want is None else got == pytest.approx(want)

    def test_nothing_from_a_program_without_the_profile(self):
        read = run_reader(LANES)
        assert read({"snap_before": None, "snap_after": None}) is None
        bare = {"t": 0.0, "stats": {}, "profile": {"models": {"gpt:1": {
            "decode_waves": []}}}}
        assert read({"snap_before": bare, "snap_after": bare}) is None

    def test_the_manifest_holds_it_last_for_the_cells_that_prefill_by_pieces(
            self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        # (Last of the accepted metrics until PR 50 put its two behind it,
        # PR 51 its one, PR 53 its two, PR 56 its four, PR 57 the seven of
        # the set-up timeline and PR 59 its one.)
        last = manifest["per_layer"][-18]
        stage = next(m for m in manifest["per_layer"]
                     if m["name"] == "prefill_stage_ms_mean.itl")
        assert [m["name"] for m in manifest["per_layer"][-17:-8]] == [
            "loop_dense_roofline.itl", "passes_per_wave.obs", HEADS,
            "piece_roofline.itl", "dense_branch_roofline.itl", CARRIED,
            "decode_attn_all_roofline.itl", "window_attn_all_roofline.itl",
            "piece_wave_roofline.itl"]
        assert last == {"name": LANES, "unit": "lanes", "better": "higher",
                        "source": "program_counter",
                        "layer": "generative scheduler",
                        "moves": "itl_mean_ms",
                        "workloads": stage["workloads"]}


# -- PR 51's reader: the piece programs that computed a head --------------------

# (pieces, programs, heads) at the window's two ends -> the value by hand;
# heads None: a program that has no such counter.
HEAD_WINDOWS = {
    "a_prompt_of_twelve_pieces": ((40, 40, 10), (1240, 1240, 110), 100 / 12),
    "every_piece_ends_its_prompt": ((40, 40, 40), (140, 140, 140), 100.0),
    "pairs_of_which_one_in_four_ends": ((40, 40, 10), (840, 440, 110), 25.0),
    "no_prompt_ended_in_the_window": ((40, 40, 10), (60, 60, 10), 0.0),
    "a_one_shot_prefill_counts_no_piece": ((0, 40, 0), (0, 1040, 0), None),
    "no_piece_in_the_window": ((40, 40, 10), (40, 40, 10), None),
    "the_parent_has_no_such_counter": ((40, 40, None), (1240, 1240, None),
                                       None),
}


class TestHeadShare:
    @pytest.mark.parametrize("window", sorted(HEAD_WINDOWS))
    def test_heads_over_programs(self, window):
        """``prefill_heads`` counts a piece program in which some lane ended
        its prompt, the span ``gen.prefill_dispatch`` a program each: their
        ratio in percent, 0 where the window's pieces ended no prompt, and
        nothing where no piece was dispatched or the program counts no
        heads (which is not a share of 0)."""
        def side(pieces, calls, heads):
            counters = {"prefill_pieces": pieces}
            if heads is not None:
                counters["prefill_heads"] = heads
            return snap({"gen.prefill_dispatch": (calls, 9)}, counters)

        a, b, want = HEAD_WINDOWS[window]
        got = run_reader(HEADS)({"snap_before": side(*a),
                                 "snap_after": side(*b)})
        assert got is None if want is None else got == pytest.approx(want)

    def test_nothing_from_a_program_without_the_profile(self):
        read = run_reader(HEADS)
        assert read({"snap_before": None, "snap_after": None}) is None
        bare = {"t": 0.0, "stats": {}, "profile": {"models": {"gpt:1": {
            "decode_waves": []}}}}
        assert read({"snap_before": bare, "snap_after": bare}) is None

    def test_the_manifest_holds_it_last_for_the_cells_of_the_piece_frame(
            self):
        """Appended behind every accepted metric (PR 53's two, PR 56's
        four, PR 57's seven and PR 59's one stand behind it since), for the
        cells whose
        backend runs the
        decoder's piece frame
        (``evabyte_6b5.longdoc`` prefills by pieces through a program of its
        own, which takes no ``ends``)."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        lanes = next(m for m in manifest["per_layer"] if m["name"] == LANES)
        assert manifest["per_layer"][-15] == {
            "name": HEADS, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "generative scheduler",
            "moves": "itl_mean_ms",
            "workloads": [c for c in lanes["workloads"]
                          if c != "evabyte_6b5.longdoc"]}
        from client_tpu.observability import spans

        assert spans.GEN_COUNTERS[-4:-1] == (
            "prefill_heads", "prefill_pairs_window", "prefill_pairs_global")


# -- PR 56's reader: the waves that rode in a piece's program -------------------

# (waves, carried) at the window's two ends -> the value by hand; carried
# None: a program that has no such counter.
CARRIED_WINDOWS = {
    "three_waves_in_four_rode": ((100, 60), (1100, 810), 75.0),
    "every_wave_rode": ((100, 60), (300, 260), 100.0),
    "a_backend_that_carries_and_none_rode": ((100, 60), (300, 60), 0.0),
    "no_wave_in_the_window": ((100, 60), (100, 60), None),
    "the_parent_has_no_such_counter": ((100, None), (1100, None), None),
}


class TestCarriedShare:
    @pytest.mark.parametrize("window", sorted(CARRIED_WINDOWS))
    def test_carried_over_fetched_waves(self, window):
        """``fetched_waves_carried`` counts a fetched wave whose tokens came
        out of a piece's program, ``fetched_waves`` every fetched wave: their
        ratio in percent, and nothing where no wave was fetched or the
        program has no such counter (which is not a share of 0)."""
        def side(waves, carried):
            counters = {"fetched_waves": waves}
            if carried is not None:
                counters["fetched_waves_carried"] = carried
            return snap({"gen.prefill_dispatch": (40, 9)}, counters)

        a, b, want = CARRIED_WINDOWS[window]
        got = run_reader(CARRIED)({"snap_before": side(*a),
                                   "snap_after": side(*b)})
        assert got is None if want is None else got == pytest.approx(want)

    def test_nothing_from_a_program_without_the_profile(self):
        read = run_reader(CARRIED)
        assert read({"snap_before": None, "snap_after": None}) is None

    def test_the_manifest_holds_it_last_for_the_two_cells_that_carry(self):
        """Appended behind every accepted metric (the three shares that
        read a carried wave's kernels and its program stand behind it,
        tests/test_wave_kernel_readers.py, PR 57's seven of the set-up
        timeline behind those and PR 59's one last), for the cells whose
        backend's
        piece programs carry a wave (``piece_wave``)."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        assert manifest["per_layer"][-12] == {
            "name": CARRIED, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "generative scheduler",
            "moves": "itl_mean_ms",
            "workloads": ["smallthinker_21b.mixed", "command_a_plus.rag"]}
        from client_tpu.observability import spans

        assert spans.GEN_COUNTERS[-1] == "fetched_waves_carried"
