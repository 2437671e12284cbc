"""The generative scheduler's clock inside the program: loop-phase spans and
lane counters in ``/v2/profile``, the same spans on the device trace's clock,
the compile counter that hears every jit, set-up spans, stable step names.

Everything runs the tiny generative model on the CPU; the numbers checked are
counts and identities, never times (a time comes only from a chip run)."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.trace import TraceManager
from client_tpu.models.generate import TinyGptBackend
from client_tpu.observability import spans
from client_tpu.observability.profiler import (
    BACKEND_COMPILE_EVENT,
    EfficiencyProfiler,
)

MODEL = "spans_gpt"
CHILDREN = [s for s in spans.GEN_SPANS if s != spans.GEN_LOOP]


def _engine(name=MODEL, **kw):
    kw = {"max_streams": 8, "n_layers": 2, "max_seq_len": 64, **kw}
    repo = ModelRepository()
    repo.register_backend(TinyGptBackend(name=name, **kw))
    return TpuEngine(repo)


def _stream(engine, prompt, max_tokens, model=MODEL, **params):
    """Start one stream; returns join() -> emitted tokens."""
    tokens, err, done = [], [], threading.Event()

    def cb(resp):
        if resp.error is not None:
            err.append(resp.error)
            done.set()
        elif resp.final:
            done.set()
        else:
            tokens.append(int(resp.outputs["TOKEN"][0]))

    engine.async_infer(InferRequest(
        model_name=model, inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
        parameters={"max_tokens": max_tokens, **params}), cb)

    def join():
        assert done.wait(120), "stream did not finish"
        assert not err, err
        return tokens

    return join


def _gen(engine, model=MODEL):
    """The model's committed ``generative`` object, once the worker has
    gone back to its blocking wait (every iteration committed)."""
    sched = engine._schedulers[model]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if not sched._streams and not sched._inflight \
                and sched._rec.open is sched._rec.span[spans.S_IDLE]:
            break
        time.sleep(0.005)
    return _gen_or_zero(engine, model)


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.shutdown()


PIECE = "spans_eva"      # prefills by one-lane pieces of 32 positions


@pytest.fixture(scope="module")
def piece_engine():
    from client_tpu.models.evabyte import EvaByteBackend

    repo = ModelRepository()
    repo.register_backend(EvaByteBackend(
        name=PIECE, seed=3, max_seq_len=128, window=32, chunk=4))
    eng = TpuEngine(repo)
    eng._schedulers[PIECE].warmup()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def ran(engine):
    """Known traffic, drained: (before, after, streams) where streams is
    [(prompt length, max_tokens, tokens emitted)], all ended by budget."""
    before = _gen_or_zero(engine)
    plan = [([1, 2, 3], 6), ([4, 5, 6, 7, 8], 4), ([9], 7),
            ([3, 1, 4, 1, 5, 9, 2, 6], 5), ([2, 7], 1)]
    joins = [_stream(engine, p, n) for p, n in plan]
    out = [(len(p), n, j()) for (p, n), j in zip(plan, joins)]
    return before, _gen(engine), out


def _gen_or_zero(engine, model=MODEL):
    """All zeros for a worker that has committed no iteration yet."""
    snap = engine.profile_snapshot(model=model)
    g = snap["models"].get(f"{model}:1", {}).get("generative")
    if g is None:
        g = {"spans": {s: {"count": 0, "total_ns": 0, "max_ns": 0}
                       for s in spans.GEN_SPANS},
             "counters": dict.fromkeys(spans.GEN_COUNTERS, 0)}
    return g


def _delta(before, after):
    return {k: after["counters"][k] - before["counters"][k]
            for k in spans.GEN_COUNTERS}


class TestLoopSpans:
    def test_vocabulary_is_what_the_profile_serves(self, ran):
        _, after, _ = ran
        assert list(after["spans"]) == list(spans.GEN_SPANS)
        assert list(after["counters"]) == list(spans.GEN_COUNTERS)
        assert len(set(spans.GEN_SPANS)) == 11

    def test_children_partition_the_iteration(self, ran):
        _, after, _ = ran
        loop = after["spans"][spans.GEN_LOOP]["total_ns"]
        kids = sum(after["spans"][s]["total_ns"] for s in CHILDREN)
        assert loop > 0
        # Exclusive children never exceed the inclusive parent, and what
        # is left (the loop's own bookkeeping) is a few percent.
        assert kids <= loop
        assert (loop - kids) / loop < 0.10

    @pytest.mark.parametrize("span", [
        spans.GEN_ADMIT, spans.GEN_PREFILL_DISPATCH, spans.GEN_SWEEP,
        spans.GEN_WAVE_STAGE, spans.GEN_WAVE_DISPATCH, spans.GEN_FETCH_WAIT,
        spans.GEN_EMIT, spans.GEN_IDLE])
    def test_every_phase_was_seen(self, ran, span):
        _, after, _ = ran
        s = after["spans"][span]
        assert s["count"] > 0
        assert 0 < s["max_ns"] <= s["total_ns"]

    def test_span_counts_follow_the_counters(self, ran):
        """A span's count is the count of its call site: no counter repeats
        it (decode dispatches have one, as the metrics' denominator)."""
        before, after, streams = ran
        c, s = after["counters"], after["spans"]
        assert s[spans.GEN_WAVE_DISPATCH]["count"] == c["dispatches"]
        assert s[spans.GEN_WAVE_STAGE]["count"] == c["dispatches"]
        assert s[spans.GEN_FETCH_WAIT]["count"] == s[spans.GEN_EMIT]["count"] \
            == c["dispatches"] + s[spans.GEN_PREFILL_DISPATCH]["count"]
        assert c["drains"] <= s[spans.GEN_FETCH_WAIT]["count"]

    def test_warmup_is_not_serving_time(self):
        eng = _engine(name="spans_warm", max_streams=2, max_seq_len=16)
        try:
            t0 = time.monotonic_ns()
            eng._schedulers["spans_warm"].warmup()
            warm_ns = time.monotonic_ns() - t0
            _stream(eng, [1, 2], 2, model="spans_warm")()
            g = _gen(eng, "spans_warm")
            loop = g["spans"][spans.GEN_LOOP]["total_ns"]
            idle = g["spans"][spans.GEN_IDLE]["total_ns"]
            # The precompile ran on the worker thread inside an iteration
            # and is excluded from it.
            assert warm_ns > 50e6
            assert loop - idle < warm_ns / 2
        finally:
            eng.shutdown()


class TestLaneCounters:
    def test_every_lane_is_accounted(self, ran):
        """Budget-ended streams, drained: a client's tokens are its first
        token and one for each decode lane the device ran for it."""
        before, after, streams = ran
        d = _delta(before, after)
        assert all(len(t) == n for _, n, t in streams)
        assert d["first_tokens"] + d["fetched_lanes_live"] \
            == sum(len(t) for _, _, t in streams)

    def test_a_one_shot_prefill_counts_no_piece_and_no_head(self, ran):
        """``prefill_heads`` is the piece programs': a backend that prefills
        a prompt in one program has none."""
        before, after, streams = ran
        d = _delta(before, after)
        assert d["first_tokens"] == len(streams)
        assert d["prefill_pieces"] == 0 and d["prefill_heads"] == 0

    def test_positions_valid_from_the_known_prompts(self, ran):
        before, after, streams = ran
        d = _delta(before, after)
        # A stream of prompt p and budget n gets n-1 decode waves; wave j
        # reads p+j valid positions.
        want = sum(p + j for p, n, _ in streams for j in range(n - 1))
        assert d["fetched_positions_valid"] == want
        assert d["fetched_lanes_live"] == sum(n - 1 for _, n, _ in streams)

    def test_every_stream_has_one_first_token(self, ran):
        before, after, streams = ran
        d = _delta(before, after)
        assert d["first_tokens"] == len(streams)
        assert d["first_token_wait_ns"] > 0

    def test_lanes_match_the_decode_wave_table(self, engine, ran):
        """``fetched_lanes_live + fetched_lanes_padded`` is the sum of
        bucket x waves of the profile's waves-by-bucket table (what
        wave_host_ms_mean reads)."""
        _, after, _ = ran
        snap = engine.profile_snapshot(model=MODEL)
        table = snap["models"][f"{MODEL}:1"]["decode_waves"]
        c = after["counters"]
        assert sum(w["bucket"] * w["waves"] for w in table) \
            == c["fetched_lanes_live"] + c["fetched_lanes_padded"]
        assert sum(w["waves"] for w in table) == c["fetched_waves"]
        assert c["fetched_waves"] == c["dispatches"]  # no chunking, drained
        assert c["fetched_lanes_padded"] >= 0

    def test_counters_are_monotone(self, engine, ran):
        _, first, _ = ran
        _stream(engine, [5, 5, 5], 3)()
        second = _gen(engine)
        for k in spans.GEN_COUNTERS:
            assert second["counters"][k] >= first["counters"][k], k
        for s in spans.GEN_SPANS:
            for field in ("count", "total_ns", "max_ns"):
                assert second["spans"][s][field] >= first["spans"][s][field]
        assert second["counters"]["first_tokens"] \
            == first["counters"]["first_tokens"] + 1

    def test_pipeline_depth_at_dispatch_is_remembered(self, engine):
        """A lone stream's prefill goes into an empty pipeline and its first
        token remembers that depth; its waves queue behind each other."""
        before = _gen(engine)
        _stream(engine, [1, 2, 3], 6)()
        d = _delta(before, _gen(engine))
        assert d["first_tokens"] == 1
        assert d["first_token_inflight_waves"] == 0
        assert d["inflight_waves"] > 0
        assert d["fetches_forced"] <= d["dispatches"] + 1

    def test_stop_token_ends_a_stream_mid_pipeline(self, engine):
        """Lanes the device ran for a stream that had already stopped are
        counted (the device ran them) though no client got their tokens."""
        toks = _stream(engine, [1, 2, 3], 6)()
        stop = toks[2]
        cut = toks.index(stop)
        before = _gen(engine)
        got = _stream(engine, [1, 2, 3], 6, stop_token_ids=stop)()
        d = _delta(before, _gen(engine))
        assert got == toks[:cut]
        assert d["first_tokens"] == 1
        assert d["first_tokens"] + d["fetched_lanes_live"] > len(got)


class _Collector:
    """A stream writer as the scheduler sees one: takes whole waves."""

    def __init__(self):
        self.waves = []

    def post(self, wave):
        self.waves.append(wave)


def _sink_stream(engine, writer, prompt, max_tokens, **params):
    """Start one stream that declares a token sink; returns (sink, join)
    where join() -> the responses its callback saw."""
    from client_tpu.engine.types import TokenSink

    sink, seen, done = TokenSink(writer), [], threading.Event()

    def cb(resp):
        seen.append(resp)
        if resp.final:
            done.set()

    req = InferRequest(
        model_name=MODEL, inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
        parameters={"max_tokens": max_tokens, **params})
    req.token_sink = sink
    engine.async_infer(req, cb)

    def join():
        assert done.wait(120), "stream did not finish"
        return seen

    return sink, join


EMIT_COUNTERS = ["emit_handoffs", "emitted_tokens", "emitted_tokens_callback",
                 "prefill_lanes_live", "prefill_lanes_padded"]


class TestEmitCounters:
    @pytest.mark.parametrize("name", EMIT_COUNTERS)
    def test_served_and_monotone(self, engine, ran, name):
        before, after, _ = ran
        assert name in spans.GEN_COUNTERS
        assert after["counters"][name] >= before["counters"][name] >= 0
        assert getattr(spans, "C_" + name.upper()) \
            == spans.GEN_COUNTERS.index(name)

    def test_callback_streams_count_a_token_each(self, ran):
        """No stream of the known traffic declared a sink: every token left
        as a response of its own, none by a wave's record."""
        before, after, streams = ran
        d = _delta(before, after)
        assert d["emitted_tokens_callback"] == sum(
            len(t) for _, _, t in streams)
        assert d["emitted_tokens"] == d["emit_handoffs"] == 0

    def test_prefill_lanes_add_up_to_the_programs_dispatched(self, ran):
        """A 64-position model has one 8-lane program a prompt bucket: the
        lanes that held a prompt and the padded ones are 8 a dispatch."""
        before, after, streams = ran
        d = _delta(before, after)
        calls = after["spans"][spans.GEN_PREFILL_DISPATCH]["count"] \
            - before["spans"][spans.GEN_PREFILL_DISPATCH]["count"]
        assert d["prefill_lanes_live"] == len(streams)
        assert d["prefill_lanes_live"] + d["prefill_lanes_padded"] \
            == 8 * calls

    def test_sink_and_callback_streams_together(self, engine):
        """Streams that declare a sink leave by the wave, the others by the
        response, in the same waves: the two counters sum to the tokens the
        clients received, one hand-off a fetch and writer, and a sink
        stream's callback sees its final response only, after its tokens."""
        before = _gen(engine)
        one, two = _Collector(), _Collector()
        plain = [_stream(engine, [1, 2, 3], 6), _stream(engine, [9, 9], 4)]
        sinks = [_sink_stream(engine, one, [1, 2, 3], 6),
                 _sink_stream(engine, one, [4, 5], 5, stop_token_ids=0),
                 _sink_stream(engine, two, [1, 2, 3], 3)]
        got_plain = [j() for j in plain]
        finals = [j() for _, j in sinks]
        d = _delta(before, _gen(engine))
        by_sink = {id(s): [] for s, _ in sinks}
        for w in one.waves + two.waves:
            assert w.model_version == "1"
            assert len(w.sinks) == len(w.tokens) == len(w.indices) > 0
            for s, tok, idx in zip(w.sinks, w.tokens, w.indices):
                assert idx == len(by_sink[id(s)])      # in order, no gap
                by_sink[id(s)].append(tok)
        assert {id(s) for w in one.waves for s in w.sinks} \
            <= {id(sinks[0][0]), id(sinks[1][0])}      # a writer's own lanes
        received = [by_sink[id(s)] for s, _ in sinks]
        assert received[0] == got_plain[0]             # the same greedy tokens
        assert received[2] == got_plain[0][:3]
        assert d["emitted_tokens"] == sum(map(len, received))
        assert d["emitted_tokens_callback"] == sum(map(len, got_plain))
        assert d["emit_handoffs"] == len(one.waves) + len(two.waves)
        assert d["first_tokens"] == 5
        for seen in finals:
            assert len(seen) == 1 and seen[0].final and not seen[0].outputs
            assert seen[0].error is None

    def test_a_traced_sink_stream_keeps_its_chunk_clock(self, engine):
        """``/v2/trace/requests`` shows a chunk per streamed token; tokens
        that leave by the wave pass no callback, so the scheduler stamps
        them where the engine's recorder reads."""
        from client_tpu.engine.types import TokenSink
        from client_tpu.observability.tracing import TraceContext

        sink, done = TokenSink(_Collector()), threading.Event()
        req = InferRequest(
            model_name=MODEL, inputs={"INPUT_IDS": np.asarray([3, 4], np.int32)},
            parameters={"max_tokens": 5},
            trace=TraceContext.from_traceparent(None))
        req.token_sink = sink
        engine.async_infer(req, lambda r: r.final and done.set())
        assert done.wait(120)
        assert len(sink.chunk_ts_ns) == 5
        assert sink.chunk_ts_ns == sorted(sink.chunk_ts_ns)
        events = engine.request_trace_export(req.trace.trace_id)["traceEvents"]
        assert len([e for e in events if e["name"] == "chunk"]) == 5


class TestRecorder:
    def test_snapshot_sees_whole_iterations_only(self):
        p = EfficiencyProfiler()
        rec = spans.GenRecorder(lambda r: p.commit_generative("m", 1, r))
        rec.begin_loop()
        with rec.span[spans.S_FETCH_WAIT]:
            pass
        rec.c[spans.C_DISPATCHES] += 3
        assert "m:1" not in p.snapshot()["models"]  # nothing committed yet
        rec.end_loop()
        g = p.snapshot()["models"]["m:1"]["generative"]
        assert g["counters"]["dispatches"] == 3
        assert g["spans"][spans.GEN_LOOP]["count"] == 1
        assert g["spans"][spans.GEN_FETCH_WAIT]["count"] == 1
        assert rec.c[spans.C_DISPATCHES] == 0  # handed over

    def test_children_are_exclusive_and_the_loop_inclusive(self):
        p = EfficiencyProfiler()
        rec = spans.GenRecorder(lambda r: p.commit_generative("m", 1, r))
        rec.begin_loop()
        with rec.span[spans.S_ADMIT]:
            with rec.span[spans.S_PREFILL_DISPATCH]:
                time.sleep(0.02)
        rec.end_loop()
        s = p.snapshot()["models"]["m:1"]["generative"]["spans"]
        assert s[spans.GEN_PREFILL_DISPATCH]["total_ns"] >= 20e6
        assert s[spans.GEN_ADMIT]["total_ns"] < 10e6   # less its child
        assert s[spans.GEN_LOOP]["total_ns"] >= \
            s[spans.GEN_PREFILL_DISPATCH]["total_ns"] \
            + s[spans.GEN_ADMIT]["total_ns"]

    def test_reset_drops_generative_totals(self):
        p = EfficiencyProfiler()
        rec = spans.GenRecorder(lambda r: p.commit_generative("m", 1, r))
        rec.begin_loop()
        rec.end_loop()
        p.reset()
        assert p.snapshot()["models"] == {}


# -- PR 41: a piece's span, a prompt's two waits, the token gap by class --------

WAYS = {"one_shot": (MODEL, [1, 2, 3, 4, 5]),
        "pieces": (PIECE, list(range(1, 81)))}     # three pieces of 32


def _way(way, engine, piece_engine):
    model, prompt = WAYS[way]
    return (engine if way == "one_shot" else piece_engine), model, prompt


def _held_batch(eng, model, prompts, max_tokens):
    """Send ``prompts`` so that ONE ``_admit_batch`` takes them all: the
    worker is held inside a warm-up sentinel while they queue.  Returns the
    requests (their ``times`` are the stamps) once every stream has ended."""
    from client_tpu.engine.generative import _WarmupReq

    sched = eng._schedulers[model]
    _gen(eng, model)                      # parked in its blocking wait
    gate, real = threading.Event(), sched._precompile
    sched._precompile = lambda: gate.wait(60)
    hold = _WarmupReq()
    try:
        sched.queue.put(hold)
        deadline = time.monotonic() + 10
        while sched._rec.open is sched._rec.span[spans.S_IDLE] \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        reqs, done = [], []
        for prompt in prompts:
            ev = threading.Event()
            req = InferRequest(
                model_name=model,
                inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
                parameters={"max_tokens": max_tokens})
            eng.async_infer(req, lambda r, ev=ev: (
                r.final or r.error is not None) and ev.set())
            reqs.append(req)
            done.append(ev)
    finally:
        gate.set()
        sched._precompile = real
    assert hold.done.wait(60)
    assert all(ev.wait(120) for ev in done)
    return reqs


class TestPrefillStage:
    @pytest.mark.parametrize("way", list(WAYS))
    def test_children_never_exceed_the_loop_at_any_snapshot(
            self, engine, piece_engine, way):
        """Snapshots taken while streams run see whole iterations only:
        with ``gen.prefill_stage`` among the children their totals still
        never exceed ``gen.loop``'s.  Only a backend that prefills by pieces
        opens the span, once a piece, around the piece's jitted call."""
        eng, model, prompt = _way(way, engine, piece_engine)
        before = _gen(eng, model)["spans"]
        joins = [_stream(eng, prompt, 6, model=model) for _ in range(3)]
        snaps = []
        while len(snaps) < 200 and (
                eng._schedulers[model]._streams or not snaps):
            snaps.append(_gen_or_zero(eng, model)["spans"])
        for j in joins:
            j()
        snaps.append(_gen(eng, model)["spans"])
        for g in snaps:
            assert sum(g[s]["total_ns"] for s in CHILDREN) \
                <= g[spans.GEN_LOOP]["total_ns"]
        stage, call = (
            snaps[-1][s]["count"] - before[s]["count"]
            for s in (spans.GEN_PREFILL_STAGE, spans.GEN_PREFILL_DISPATCH))
        assert call > 0
        assert stage == (0 if way == "one_shot" else call)
        if way == "pieces":
            assert call == 9 and snaps[-1][spans.GEN_PREFILL_STAGE][
                "total_ns"] > before[spans.GEN_PREFILL_STAGE]["total_ns"]


class TestAPromptsTwoWaits:
    @pytest.mark.parametrize("way", list(WAYS))
    def test_three_waits_partition_the_first_token(self, engine,
                                                   piece_engine, way):
        """N prompts admitted in one batch: ``prompts_started`` is N once
        all have started, and the three counters are the sums of the
        requests' own stamps, so together they are exactly ``first_token -
        queue_start`` summed over the requests."""
        eng, model, prompt = _way(way, engine, piece_engine)
        before = _gen(eng, model)
        n = 3
        reqs = _held_batch(eng, model, [prompt] * n, 4)
        d = _delta(before, _gen(eng, model))
        t = [r.times for r in reqs]
        assert d["prompts_started"] == d["first_tokens"] == n
        for x in t:
            assert 0 < x.queue_start <= x.compute_start < x.prefill_start \
                < x.first_token
            assert x.first_token - x.queue_start == x.queue_ns + (
                x.prefill_start - x.compute_start) + (
                x.first_token - x.prefill_start)
        assert d["admit_wait_ns"] == sum(x.queue_ns for x in t) > 0
        assert d["prefill_line_wait_ns"] == sum(
            x.prefill_start - x.compute_start for x in t) > 0
        assert d["first_token_wait_ns"] == sum(
            x.first_token - x.prefill_start for x in t)
        assert d["admit_wait_ns"] + d["prefill_line_wait_ns"] \
            + d["first_token_wait_ns"] == sum(
                x.first_token - x.queue_start for x in t)
        # one batch: the slots were taken in the same call
        assert max(x.compute_start for x in t) \
            - min(x.compute_start for x in t) < 50e6
        started = [x.prefill_start for x in t]
        if way == "pieces":
            # one lane a piece, oldest prompt first: each prompt's three
            # pieces go before the next prompt's first
            assert d["prompts_admitted"] == n and d["prefill_pieces"] == 9
            assert started == sorted(started) and len(set(started)) == n
        else:
            # one program holds them all: they leave the line together
            assert len(set(started)) == 1

    def test_a_request_trace_shows_the_line_inside_prefill(self):
        from client_tpu.engine.types import RequestTimes
        from client_tpu.observability.tracing import (
            TraceContext, build_request_trace)

        t = RequestTimes(received=1, queue_start=1, compute_start=5,
                         compute_input_end=5, compute_infer_end=30,
                         compute_output_end=30, first_token=20,
                         prefill_start=12)
        by = {s.name: (s.start_ns, s.end_ns) for s in build_request_trace(
            TraceContext("a" * 32, "b" * 16, ""), "m", "r", t, ok=True).spans}
        assert by["prefill"] == (5, 20) and by["prefill_wait"] == (5, 12)
        assert by["queue"] == (1, 5)
        t.prefill_start = 0        # a program without the stamp: no child
        names = [s.name for s in build_request_trace(
            TraceContext("a" * 32, "b" * 16, ""), "m", "r", t, ok=True).spans]
        assert "prefill" in names and "prefill_wait" not in names


# -- PR 47: a piece program of as many lanes as prompts stand in line -----------

# Backends whose pieces are 32 positions and hold two prompts a program at
# most: one that takes its lanes as an option, and one whose piece program
# declares them (``models/kimi_linear.py``, PR 48: float32, so that a lane's
# tokens are the lone prompt's to the bit).
def _two_lane_evabyte(name):
    from client_tpu.models.evabyte import EvaByteBackend

    return EvaByteBackend(name=name, seed=3, max_seq_len=128, window=32,
                          chunk=4, prefill_lanes=2)


def _two_lane_kimi(name):
    from client_tpu.models.kimi_linear import KimiLinearBackend

    return KimiLinearBackend(name=name, seed=3, max_seq_len=128, piece=32,
                             dtype="float32")


LANES = {"spans_eva_two": _two_lane_evabyte,
         "spans_kimi_two": _two_lane_kimi}


@pytest.fixture(scope="module", params=sorted(LANES))
def lane_engine(request):
    name = request.param
    repo = ModelRepository()
    repo.register_backend(LANES[name](name))
    eng = TpuEngine(repo)
    eng._schedulers[name].warmup()
    yield eng, eng.profile_snapshot(model=name)["compiles"]["count"], name
    eng.shutdown()


# Prompts admitted in one batch (their lengths) -> the lanes of each piece
# program, in order: the two oldest prompts go paired while both have pieces
# left, a prompt with nobody beside it goes alone and at once.
LINES = {
    "one_waits": ([80], [1, 1, 1]),
    "two_wait": ([80, 80], [2, 2, 2]),
    "three_wait": ([80, 80, 80], [2, 2, 2, 1, 1, 1]),
    "a_short_one_beside_a_long_one": ([20, 80], [2, 1, 1]),
    "the_third_steps_up": ([20, 80, 30], [2, 2, 1]),
}


# The same lines (and a prompt of exactly one full piece) -> the piece
# programs in which some lane ended its prompt.
HEADS = {
    "three_pieces": ([80], 1),
    "exactly_one_full_piece": ([32], 1),
    "a_pair_that_ends_together": ([80, 80], 1),
    "a_pair_with_one_ending_lane": ([20, 80], 2),
    "three_prompts_end_in_three_programs": ([20, 80, 30], 3),
}


class TestPieceLanes:
    @pytest.mark.parametrize("line", sorted(LINES))
    def test_a_piece_program_holds_the_prompts_that_wait(self, lane_engine,
                                                         line):
        """``prefill_pieces`` counts lanes' pieces and ``gen.prefill_dispatch``
        programs: a backend of two lanes runs its one-lane program when one
        prompt stands in line and its two-lane one when two do, every prompt
        gets the tokens it gets alone, and nothing compiles after the
        warm-up, which ran both lane counts."""
        eng, compiles, model = lane_engine
        lengths, want = LINES[line]
        prompts = [list(range(1 + i, 1 + i + n))
                   for i, n in enumerate(lengths)]
        alone = [_stream(eng, p, 4, model=model)() for p in prompts]
        before = _gen(eng, model)
        _held_batch(eng, model, prompts, 4)
        after = _gen(eng, model)
        d = _delta(before, after)
        calls = after["spans"][spans.GEN_PREFILL_DISPATCH]["count"] \
            - before["spans"][spans.GEN_PREFILL_DISPATCH]["count"]
        assert d["prefill_pieces"] == sum(want)
        assert calls == len(want)
        assert d["prefill_positions_valid"] == sum(lengths)
        assert d["first_tokens"] == len(prompts)
        together = [_stream(eng, p, 4, model=model) for p in prompts]
        assert [j() for j in together] == alone
        assert eng.profile_snapshot(model=model)["compiles"]["count"] \
            == compiles

    @pytest.mark.parametrize("line", sorted(HEADS))
    def test_prefill_heads_counts_the_programs_in_which_a_lane_ends(
            self, lane_engine, line):
        """``prefill_heads`` moves once a piece program in which some lane's
        piece is its prompt's last, however many lanes end in it: what the
        worker tells a backend of the decoder's piece frame in ``ends``, and
        so the programs whose head ran (PR 51).  Counted on the host for
        every backend that prefills by pieces."""
        eng, _, model = lane_engine
        lengths, want = HEADS[line]
        prompts = [list(range(1 + i, 1 + i + n))
                   for i, n in enumerate(lengths)]
        before = _gen(eng, model)
        _held_batch(eng, model, prompts, 2)
        d = _delta(before, _gen(eng, model))
        assert d["prefill_heads"] == want
        assert d["first_tokens"] == len(prompts)

    def test_the_ladder_is_the_powers_of_two_up_to_the_backends_lanes(
            self, lane_engine, piece_engine):
        """A backend of one lane has a ladder of one: the program it ran
        before there was a ladder, and no other."""
        eng, _, model = lane_engine
        assert eng._schedulers[model]._ladders == {32: [1, 2]}
        assert piece_engine._schedulers[PIECE]._ladders == {32: [1]}


class _Ready:
    """A fetched token array as ``_drain_fetches`` takes one."""

    def __init__(self, shape):
        self.shape = shape

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        return np.zeros(self.shape, np.int32)


# Heads in fetch order: (kind, lanes, K, t_done ns, first_token of a fresh
# lane or None); "idle" is the worker's gen.idle.
SCRIPT = [("wave", 3, 1, 100, None), ("piece", 0, 1, 130, None),
          ("wave", 3, 1, 150, None), ("wave", 3, 1, 170, None),
          ("prefill", 1, 1, 200, None), ("prefill", 1, 1, 230, None),
          ("wave", 4, 1, 260, 230), "idle", ("wave", 4, 1, 1000, None),
          ("chunk", 4, 2, 1060, None)]
# By hand.  100: no decode fetch before it.  150: 50 x 3 behind the piece.
# 170: 20 x 3 plain.  260: 90 behind two prefills, counted once: the three
# waiting lanes 90 each behind, the fresh lane 260 - 230 = 30 plain.  1000:
# across the idle, in neither class.  1060: a 2-chunk, 2 gaps of 30 a lane.
BY_HAND = {"gap_lanes": 3 + 3 + 4 + 8,
           "gap_lane_ns": 150 + 60 + (270 + 30) + 240,
           "gap_lanes_behind_prefill": 3 + 3,
           "gap_lane_behind_prefill_ns": 150 + 270,
           "fetched_lanes_live": 3 + 3 + 3 + 4 + 4 + 8}


@pytest.fixture(scope="module")
def scripted():
    """The counters after ``_drain_fetches`` took SCRIPT's heads at SCRIPT's
    times, on a scheduler whose worker is parked (nothing else moves)."""
    import types

    from client_tpu.engine import generative as G

    eng = _engine(name="spans_gaps", max_streams=4, max_seq_len=16,
                  n_layers=1)
    sched = eng._schedulers["spans_gaps"]
    _stream(eng, [1, 2], 2, model="spans_gaps")()
    _gen(eng, "spans_gaps")
    clock = iter(h[3] for h in SCRIPT if h != "idle")
    real_time, real_emit = G.time, sched._emit_fetched
    G.time = types.SimpleNamespace(monotonic_ns=lambda: next(clock),
                                   monotonic=time.monotonic, sleep=time.sleep)
    sched._emit_fetched = lambda head, toks: None
    try:
        assert sched._last_decode_fetch_ns == 0     # parked in gen.idle
        for h in SCRIPT:
            if h == "idle":
                with sched._idle():
                    pass
                continue
            kind, lanes, k, _, first = h
            streams = [G._Stream(InferRequest(model_name="spans_gaps",
                                              inputs={}), i, 4, 8)
                       for i in range(lanes)]
            fresh = ()
            if first is not None:
                streams[-1].req.times.first_token = first
                fresh = streams[-1:]
            decode = kind in ("wave", "chunk")
            sched._inflight.append(G._Inflight(
                kind, streams, _Ready((k, 4) if kind == "chunk" else (4,)),
                waves=k, bucket=4 if decode else 0, fresh=fresh))
            sched._inflight_waves += k
            sched._drain_fetches()
        assert not sched._inflight
        counters = dict(zip(spans.GEN_COUNTERS, sched._rec.c))
    finally:
        G.time, sched._emit_fetched = real_time, real_emit
        eng.shutdown()
    return counters


class TestGapCounters:
    @pytest.mark.parametrize("name", list(BY_HAND))
    def test_a_scripted_order_of_heads_by_hand(self, scripted, name):
        assert scripted[name] == BY_HAND[name]
        assert scripted["gap_lanes"] <= scripted["fetched_lanes_live"]
        assert scripted["gap_lanes_behind_prefill"] <= scripted["gap_lanes"]

    @pytest.mark.parametrize("way", list(WAYS))
    def test_served_streams_close_gaps_of_both_classes(
            self, engine, piece_engine, way):
        """Real streams: every counted lane is a fetched lane, a class is
        part of the whole, and a stream that decodes while another prompt
        prefills waits behind it."""
        eng, model, prompt = _way(way, engine, piece_engine)
        before = _gen(eng, model)
        first = _stream(eng, prompt[:3], 12, model=model)
        time.sleep(0.05)                  # decoding when the others arrive
        rest = [_stream(eng, prompt, 4, model=model) for _ in range(2)]
        for j in [first] + rest:
            j()
        d = _delta(before, _gen(eng, model))
        assert 0 < d["gap_lanes"] <= d["fetched_lanes_live"]
        assert 0 <= d["gap_lanes_behind_prefill"] <= d["gap_lanes"]
        assert 0 <= d["gap_lane_behind_prefill_ns"] <= d["gap_lane_ns"]
        assert d["gap_lane_ns"] > 0
        # every decode fetch but the first after an idle closes a gap
        assert d["gap_lanes"] >= d["fetched_lanes_live"] - 3 * 3


class TestPrefillSpanOfARequest:
    def test_trace_requests_shows_queue_plus_prefill(self, engine):
        from client_tpu.server import HttpInferenceServer

        srv = HttpInferenceServer(engine, port=0).start()
        trace_id = "ab" * 16
        try:
            host, port = srv.url.split(":")
            conn = http.client.HTTPConnection(host, int(port), timeout=120)
            body = json.dumps({
                "inputs": [{"name": "INPUT_IDS", "datatype": "INT32",
                            "shape": [3], "data": [1, 2, 3]}],
                "parameters": {"max_tokens": 4}}).encode()
            conn.request("POST", f"/v2/models/{MODEL}/generate", body=body,
                         headers={"traceparent":
                                  f"00-{trace_id}-{'cd' * 8}-01"})
            resp = conn.getresponse()
            assert resp.status == 200 and len(
                json.loads(resp.read())["responses"]) == 4
            conn.request("GET", f"/v2/trace/requests?trace_id={trace_id}")
            events = json.loads(conn.getresponse().read())["traceEvents"]
            conn.close()
        finally:
            srv.stop()
        by = {e["name"]: e for e in events if e["ph"] == "X"}
        assert {"queue", "prefill", "compute_infer"} <= set(by)
        q, pre, infer = by["queue"], by["prefill"], by["compute_infer"]
        # TTFT inside the server = queue + prefill, additively: prefill
        # starts where queue ends and ends inside the stream.
        assert pre["ts"] == pytest.approx(q["ts"] + q["dur"], abs=1e-3)
        assert 0 < pre["dur"] <= infer["dur"]
        chunks = sorted(e["ts"] for e in events if e["name"] == "chunk")
        assert len(chunks) == 4
        assert pre["ts"] + pre["dur"] <= chunks[0] + 1e-3

    def test_no_prefill_span_without_a_first_token(self):
        from client_tpu.engine.types import RequestTimes
        from client_tpu.observability.tracing import (
            PHASES, TraceContext, build_request_trace)

        t = RequestTimes(received=1, queue_start=1, compute_start=5,
                         compute_input_end=6, compute_infer_end=9,
                         compute_output_end=10)
        ctx = TraceContext.new() if hasattr(TraceContext, "new") \
            else TraceContext("a" * 32, "b" * 16, "")
        names = [s.name for s in build_request_trace(
            ctx, "m", "r", t, ok=True).spans]
        assert "prefill" not in names
        assert names == ["request", *PHASES]
        t.first_token = 7
        spans_ = {s.name: s for s in build_request_trace(
            ctx, "m", "r", t, ok=True).spans}
        assert (spans_["prefill"].start_ns, spans_["prefill"].end_ns) == (5, 7)


class TestCompileCounter:
    def test_equals_an_independent_listener_then_adds_nothing(self):
        import jax.monitoring as monitoring

        heard = []

        def mine(event, duration_secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                heard.append(duration_secs)

        monitoring.register_event_duration_secs_listener(mine)
        # Shapes no other test of this process compiles.
        eng = _engine(name="spans_compile", max_streams=3, max_seq_len=24,
                      n_layers=1)
        try:
            c0 = eng.profile_snapshot()["compiles"]
            n0 = len(heard)
            _stream(eng, [1, 2, 3], 3, model="spans_compile")()
            c1 = eng.profile_snapshot()["compiles"]
            n1 = len(heard)
            assert n1 - n0 >= 2          # prefill and decode, at least
            assert c1["count"] - c0["count"] == n1 - n0
            assert c1["seconds"] - c0["seconds"] == pytest.approx(
                sum(heard[n0:n1]))
            scopes = {k for k, v in c1["by_scope"].items()
                      if k.startswith("spans_compile:1:")
                      and v["count"] > c0["by_scope"].get(
                          k, {"count": 0})["count"]}
            assert "spans_compile:1:prefill:4" in scopes
            assert any(k.startswith("spans_compile:1:decode:")
                       for k in scopes)
            # The same shapes again compile nothing.
            _stream(eng, [3, 2, 1], 3, model="spans_compile")()
            c2 = eng.profile_snapshot()["compiles"]
            assert c2["count"] == c1["count"] and len(heard) == n1
            text = eng.prometheus_metrics()
            assert 'tpu_xla_compilations_total{model="spans_compile",' \
                   'version="1",bucket="4"} 1' in text
        finally:
            eng.shutdown()
            monitoring.unregister_event_duration_listener(mine)

    def test_record_compile_no_longer_counts(self):
        from client_tpu.observability.metrics import MetricRegistry

        p = EfficiencyProfiler()
        reg = MetricRegistry()
        p.bind_metrics(reg)
        p.record_compile("m", 1, 8, compile_ns=1_000_000_000)
        assert p.snapshot()["compiles"]["count"] == 0
        assert p.snapshot()["models"]["m:1"]["compilations"] == 1
        assert 'tpu_xla_compilations_total{' not in reg.render()
        p.record_backend_compile(0.5, ("m", 1, "apply", 8))
        p.record_backend_compile(0.25, None)
        c = p.snapshot()["compiles"]
        assert c["count"] == 2 and c["seconds"] == pytest.approx(0.75)
        assert c["by_scope"] == {
            "": {"count": 1, "seconds": 0.25, "trace_s": 0.0,
                 "lower_s": 0.0, "hits": 0},
            "m:1:apply:8": {"count": 1, "seconds": 0.5, "trace_s": 0.0,
                            "lower_s": 0.0, "hits": 0}}
        text = reg.render()
        assert 'tpu_xla_compilations_total{model="m",version="1",' \
               'bucket="8"} 1' in text
        assert "tpu_xla_compile_seconds_count" in text

    def test_scope_is_per_thread_and_cleared(self, engine):
        from client_tpu.observability.profiler import _scope

        model = engine._schedulers[MODEL].model
        model._set_state("x", spans.STEP_DECODE, 4)
        assert _scope.value == (MODEL, 1, "decode", 4)
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(getattr(_scope, "value", None)))
        t.start()
        t.join()
        assert seen == [None]
        model._clear_state()
        assert _scope.value is None


class TestStartupSpans:
    def test_engine_records_backend_init_and_model_load(self, engine):
        names = [s["name"] for s in engine.profile_snapshot()["startup"]]
        assert spans.STARTUP_MODEL_LOAD + MODEL in names
        for s in engine.profile_snapshot()["startup"]:
            assert s["end_s"] >= s["start_s"]

    def test_relative_to_the_launcher_entry(self):
        clock = [1_000_000_000]
        p = EfficiencyProfiler(now=lambda: clock[0])
        p.record_startup(spans.STARTUP_BACKEND_INIT, 400_000_000,
                         900_000_000)  # a wrapper initialised it first
        p.startup_entry()
        p.record_startup(spans.STARTUP_MODEL_LOAD + "m", 1_500_000_000,
                         2_000_000_000)
        p.record_startup(spans.STARTUP_WARMUP + "m", 2_000_000_000,
                         4_000_000_000)
        assert p.snapshot()["startup"] == [
            {"name": "startup.backend_init", "start_s": -0.6,
             "end_s": -0.1},
            {"name": "startup.model_load:m", "start_s": 0.5, "end_s": 1.0},
            {"name": "startup.warmup:m", "start_s": 1.0, "end_s": 3.0}]

    def test_launcher_marks_entry_warmup_and_frontends(self):
        import os
        import re
        import signal
        import subprocess
        import sys
        import urllib.request

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu",
                   JAX_ENABLE_COMPILATION_CACHE="false")
        proc = subprocess.Popen(
            [sys.executable, "-m", "client_tpu.server", "--zoo", "simple",
             "--warmup", "--http-port", "0", "--no-grpc",
             "--host", "127.0.0.1"],
            env=env, cwd=root, stderr=subprocess.PIPE, text=True)
        try:
            url = None
            for line in proc.stderr:
                m = re.match(r"serving http at (\S+)", line)
                if m:
                    url = m.group(1)
                    break
            assert url, "launcher did not come up"
            snap = json.load(urllib.request.urlopen(
                f"http://{url}/v2/profile", timeout=30))
        finally:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
        phases = [s for s in snap["startup"]
                  if s["name"].startswith("startup.")
                  and not s["name"].startswith(spans.STARTUP_FIRST_RUN)]
        by = {s["name"]: s for s in phases}
        assert list(by) == ["startup.process", "startup.imports",
                            "startup.backend_init",
                            "startup.model_load:simple",
                            "startup.warmup:simple", "startup.frontends"]
        assert by["startup.process"]["end_s"] == 0  # it ends at the entry
        assert by["startup.backend_init"]["start_s"] >= 0  # after the entry
        ends = [s["end_s"] for s in phases]
        assert ends == sorted(ends)
        # --warmup compiled every bucket of `simple`, and the program's
        # counter saw it without a request having been served.
        assert snap["compiles"]["count"] >= 1
        assert any(k.startswith("simple:1:apply:")
                   for k in snap["compiles"]["by_scope"])


class TestStepNames:
    """``jit_<name>`` is what the device-trace reduction and
    ``benchmark/traffic/chat.json`` (``step_module``) key on."""

    def test_generative_modules(self, engine):
        sched = engine._schedulers[MODEL]
        m = sched.model
        lane, wb = 8, 1
        z = np.zeros(lane, np.int32)
        prefill = sched._prefill.lower(
            m._params, sched._arena, z, np.zeros((lane, 4), np.int32),
            np.ones(lane, np.int32), z, np.zeros(lane, np.float32), z,
            np.ones(lane, np.float32), False).as_text()
        assert "module @jit_prefill" in prefill
        z1 = np.zeros(wb, np.int32)
        decode = sched._decode.lower(
            m._params, sched._arena, z1, z1, z1, np.zeros(wb, np.float32),
            z1, np.ones(wb, np.float32), False).as_text()
        assert "module @jit_decode " in decode \
            or "module @jit_decode\n" in decode \
            or "module @jit_decode attributes" in decode

    def test_chunked_decode_module(self, monkeypatch):
        monkeypatch.setenv("CLIENT_TPU_GEN_CHUNK", "2")
        eng = _engine(name="spans_chunk", max_streams=2, max_seq_len=16,
                      n_layers=1)
        try:
            sched = eng._schedulers["spans_chunk"]
            z1 = np.zeros(1, np.int32)
            text = sched._decode_chunk.lower(
                sched.model._params, sched._arena, z1, z1, z1,
                np.zeros(1, np.float32), z1, np.ones(1, np.float32),
                False, 2).as_text()
            assert "module @jit_decode_chunk" in text
            toks = _stream(eng, [1, 2], 6, model="spans_chunk")()
            assert len(toks) == 6
            c = _gen(eng, "spans_chunk")["counters"]
            # K waves in one dispatch; a lane for each wave, used or not.
            assert c["fetched_waves"] > c["dispatches"]
            assert c["fetched_lanes_live"] == c["fetched_waves"]
            assert c["first_tokens"] + c["fetched_lanes_live"] >= len(toks)
        finally:
            eng.shutdown()

    def test_batcher_module_is_jit_apply(self):
        from client_tpu.models import build_repository

        eng = TpuEngine(build_repository(["simple"]))
        try:
            model = eng._schedulers["simple"].model
            a = np.zeros((1, 16), np.int32)
            args = ({"INPUT0": a, "INPUT1": a},)
            if model._takes_params:
                args = (model._params,) + args
            assert "module @jit_apply" in model._apply.lower(*args).as_text()
        finally:
            eng.shutdown()

    def test_named_step_keeps_the_function(self):
        def whatever(a, b):
            return a - b

        step = spans.named_step(whatever, "decode")
        assert step.__name__ == step.__qualname__ == "decode"
        assert step(5, 3) == 2


class _FakeProfiler:
    def __init__(self):
        self.options = None
        self.active_at_start = None

    def start_trace(self, log_dir, profiler_options=None):
        self.options = profiler_options
        self.active_at_start = spans.trace_active()

    def stop_trace(self):
        self.active_at_stop = spans.trace_active()


class TestTraceClock:
    def test_trace_manager_turns_the_python_tracer_off(self, monkeypatch,
                                                       tmp_path):
        import jax

        fake = _FakeProfiler()
        monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
        tm = TraceManager()
        assert not spans.trace_active()
        tm.update({"trace_level": ["TIMESTAMPS"], "log_dir": str(tmp_path)})
        assert fake.options is not None
        assert fake.options.python_tracer_level == 0
        assert fake.active_at_start is False and spans.trace_active()
        tm.update({"trace_level": ["OFF"]})
        assert fake.active_at_stop is False and not spans.trace_active()

    def test_no_annotation_is_built_off_the_trace(self, engine, monkeypatch):
        built = []
        monkeypatch.setattr(spans, "_annotate",
                            lambda name: built.append(name))
        assert not spans.trace_active()
        _stream(engine, [1, 2, 3], 3)()
        _gen(engine)
        assert built == []
        assert spans.begin(spans.EXEC_RUN) is None
        spans.end(None)

    @pytest.mark.parametrize("way", ["one_shot", "pieces"])
    def test_cpu_trace_holds_the_host_spans(self, engine, piece_engine,
                                            tmp_path, way):
        """Every ``gen.*`` span the worker closed while the trace was on is
        in the trace, as often as the profile counted it.  The worker is
        parked in ``gen.idle`` when the trace starts and when it stops, so
        the one iteration (and its idle) that straddles each end is the
        only difference: the expected counts come from the program's own
        counters over the same stretch, not from how a few streams happened
        to fall into iterations."""
        from jax.profiler import ProfileData

        eng, model, prompt = {
            "one_shot": (engine, MODEL, [1, 2]),
            "pieces": (piece_engine, PIECE, list(range(1, 71)))}[way]
        before = _gen(eng, model)["spans"]
        eng.trace.update({"trace_level": ["TIMESTAMPS"],
                          "log_dir": str(tmp_path)})
        try:
            assert spans.trace_active()
            for _ in range(3):
                _stream(eng, prompt, 4, model=model)()
            after = _gen(eng, model)["spans"]
        finally:
            eng.trace.update({"trace_level": ["OFF"]})
        assert not spans.trace_active()
        files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        assert len(files) == 1
        pd = ProfileData.from_file(str(files[0]))
        seen = dict.fromkeys(spans.GEN_SPANS, 0)
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("gen."):
                        seen[e.name] += 1     # KeyError: not the vocabulary
        closed = {name: after[name]["count"] - before[name]["count"]
                  for name in spans.GEN_SPANS}
        straddling = (spans.GEN_LOOP, spans.GEN_IDLE)
        for name in spans.GEN_SPANS:
            if name in straddling:
                # the first stream's iteration began before the trace did
                assert closed[name] - 1 <= seen[name] <= closed[name], name
            else:
                assert seen[name] == closed[name], name
        assert seen[spans.GEN_WAVE_DISPATCH] == 9      # 3 streams x 3 waves
        assert seen[spans.GEN_LOOP] >= 2
        # a piece's staging encloses its jitted call, once a piece
        assert seen[spans.GEN_PREFILL_STAGE] == (
            0 if way == "one_shot" else seen[spans.GEN_PREFILL_DISPATCH])
        assert seen[spans.GEN_PREFILL_DISPATCH] == (
            3 if way == "one_shot" else 9)             # 70 positions by 32

    def test_batcher_phases_are_exec_annotations(self, tmp_path):
        from jax.profiler import ProfileData

        from client_tpu.models import build_repository

        eng = TpuEngine(build_repository(["simple"]))
        try:
            a = np.arange(16, dtype=np.int32).reshape(1, 16)
            req = lambda: InferRequest(  # noqa: E731
                model_name="simple", inputs={"INPUT0": a, "INPUT1": a})
            eng.infer(req())  # compile outside the trace
            eng.trace.update({"trace_level": ["TIMESTAMPS"],
                              "log_dir": str(tmp_path)})
            try:
                for _ in range(3):
                    eng.infer(req())
            finally:
                eng.trace.update({"trace_level": ["OFF"]})
        finally:
            eng.shutdown()
        pd = ProfileData.from_file(str(next(
            tmp_path.glob("plugins/profile/*/*.xplane.pb"))))
        seen = {}
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("exec."):
                        seen[e.name] = seen.get(e.name, 0) + 1
        assert seen == {spans.EXEC_STAGE: 3, spans.EXEC_RUN: 3,
                        spans.EXEC_FETCH: 3}
