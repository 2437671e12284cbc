"""A wave carried in the piece's program (models/decoder.py ``piece_wave``:
``models/cohere_moe.py``, ``models/smallthinker.py``, through the
``"state"`` kind of models/state_layer.py ``models/nemotron_h.py``, and
through a latent cache, models/latent_moe.py, ``models/pangu_moe.py`` alone and
``models/kimi_linear.py`` beside state layers) at the
tiny presets on the CPU: the one program against the piece program and then
the wave program on the same arena (the arena's leaves, a slot's state and
convolution tail among them, both programs' tokens, the records' rows, the
wave's counts, which are the wave's rows' alone), exact in
float32 with the reference products and within the tie in bfloat16 with the
kernels; with every wave lane padded it is the piece program; and through the
scheduler, a backend that declares it against a subclass that does not: one
dispatch and one fetch an iteration, the same tokens a stream, and the counter
``fetched_waves_carried`` moves for the first alone."""

import time

import jax
import numpy as np
import pytest

from client_tpu.engine import TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.models.cohere_moe import CohereMoeBackend
from client_tpu.models.decoder import record_width
from client_tpu.models.kimi_linear import KimiLinearBackend
from client_tpu.models.nemotron_h import NemotronHBackend
from client_tpu.models.pangu_moe import PanguMoeBackend
from client_tpu.models.smallthinker import SmallThinkerBackend
from client_tpu.observability import spans
from test_smallthinker import counters, stream

PIECE, CAP = 8, 4
TINY = dict(seed=5, max_seq_len=64, piece=PIECE, dtype="float32",
            record=True, max_streams=CAP)


def recorded(cls):
    """A latent family under the tests' one set of arguments: ``record``
    asks for the record that models/latent_moe.py keeps for a model that
    declares its width (``kimi_linear`` always does, ``pangu_moe`` here)."""
    def init(self, record=False, **kwargs):
        cls.__init__(self, **kwargs)
        if record:
            self.stream_record = record_width(self.n_layers - self.n_dense)

    return type(cls.__name__, (cls,), {"__init__": init})


FAMILIES = {"cohere_moe": CohereMoeBackend,
            "kimi_linear": recorded(KimiLinearBackend),
            "nemotron_h": NemotronHBackend,
            "pangu_moe": recorded(PanguMoeBackend),
            "smallthinker": SmallThinkerBackend}
# What a family's preset takes beside ``TINY``: a window for the two that
# keep rings; ``nemotron_h``'s own pattern holds all three of its kinds
# (``MEM*EME``: three state layers, an attention layer, three expert layers);
# the two latent caches (``pangu_moe``: a dense and an expert layer, both
# latent; ``kimi_linear``: two state layers and a latent one) hold every
# expert, two a token, as the other presets do.
_ALL_HELD = {"n_experts": 8, "experts_held": 8, "top_k": 2}
OWN = {"cohere_moe": {"window": 16},
       "kimi_linear": {**_ALL_HELD, "n_layers": 3, "linear_attn": {
           "kda_layers": [1, 2], "full_attn_layers": [3], "num_heads": 4,
           "head_dim": 16, "short_conv_kernel_size": 4}},
       "nemotron_h": {}, "pangu_moe": {**_ALL_HELD, "n_layers": 2},
       "smallthinker": {"window": 16}}
# (family, prompts a piece program)
CASES = [("cohere_moe", 1), ("smallthinker", 1), ("smallthinker", 2),
         ("nemotron_h", 1), ("nemotron_h", 2), ("pangu_moe", 1),
         ("kimi_linear", 1), ("kimi_linear", 2)]
# The logits' bits behind a record's words.
BITS = 9
# The tiny presets' choices a token (all experts held).
TOP_K = 2


def tiny(family, **how):
    return {**TINY, **OWN[family], **how}


def expert_layers(be):
    """The layers that route: blocks of their own where a backend has such
    (the ``"none"`` kind), else every layer behind the leading dense ones."""
    return (be.layer_kinds or ()).count("none") or (
        be.n_layers - getattr(be, "n_dense", 0))


def apart(cls):
    """The backend whose piece programs carry no wave."""
    return type(cls.__name__ + "Apart", (cls,), {"piece_wave": False})


def greedy(n):
    return (np.zeros(n, np.int32), np.zeros(n, np.float32),
            np.zeros(n, np.int32), np.ones(n, np.float32))


class Programs:
    """A family's three programs over one set of weights, and an arena in
    which two streams decode (slots 0 and 1, past a ring's first lap and
    inside it) while ``lanes`` prompts prefill (slots 2..)."""

    def __init__(self, family, lanes, **how):
        cls = FAMILIES[family]
        kwargs = tiny(family, **how)
        self.be, plain = cls(**kwargs), apart(cls)(**kwargs)
        assert self.be.piece_wave and not plain.piece_wave
        self.params = self.be.place_params(self.be._init_params())
        static = self.be.prefill_static_argnums
        self.one = jax.jit(self.be.prefill_fn(), static_argnums=static)
        self.piece = jax.jit(plain.prefill_fn(), static_argnums=static)
        self.wave = jax.jit(self.be.decode_fn(),
                            static_argnums=self.be.decode_static_argnums)
        rng = np.random.default_rng(1)
        self.prompts = [rng.integers(0, 96, n).astype(np.int32)
                        for n in (2 * PIECE + 3, PIECE)]
        self.pieces = [rng.integers(0, 96, 3 * PIECE - 2).astype(np.int32)
                       for _ in range(lanes)]
        arena = self.be.init_arena(CAP)
        for slot, ids in enumerate(self.prompts):
            for st in range(0, len(ids), PIECE):
                arena, _ = self.run_piece(self.piece, arena,
                                          [(slot, ids, st)])
        self.rows = np.asarray([0, 1] + [CAP] * (CAP - 2), np.int32)
        self.lens = np.asarray([len(p) for p in self.prompts]
                               + [0] * (CAP - 2), np.int32)
        for _ in range(3):
            arena, _ = self.wave(self.params, arena, self.rows, self.lens,
                                 *greedy(CAP), False)
            self.lens = self.lens + (self.lens > 0)
        self.arena, _ = self.run_piece(
            self.piece, arena,
            [(2 + i, ids, 0) for i, ids in enumerate(self.pieces)])

    def run_piece(self, fn, arena, lanes, wave=()):
        k = len(lanes)
        ids = np.zeros((k, PIECE), np.int32)
        lens, starts, ends = (np.zeros(k, np.int32) for _ in range(3))
        for i, (_, toks, st) in enumerate(lanes):
            part = toks[st:st + PIECE]
            ids[i, :len(part)] = part
            lens[i], starts[i] = len(part), st
            ends[i] = st + len(part) >= len(toks)
        return fn(self.params, arena,
                  np.asarray([slot for slot, _, _ in lanes], np.int32), ids,
                  lens, *greedy(k), False, starts, ends, *wave)

    def step(self, start, live=True):
        """The piece from ``start`` of every prefilling slot beside a wave:
        ((arena, piece's result, wave's) of the two programs, of the one).
        ``live`` False: the one program with every wave lane padded, and no
        wave program."""
        lanes = [(2 + i, ids, start) for i, ids in enumerate(self.pieces)]
        a, piece = self.run_piece(self.piece, self.arena, lanes)
        wave = None
        if live:
            a, wave = self.wave(self.params, a, self.rows, self.lens,
                                *greedy(CAP), False)
            carried = (self.rows, self.lens, *greedy(CAP))
        else:
            carried = (np.full(CAP, CAP, np.int32), np.zeros(CAP, np.int32),
                       *greedy(CAP))
        a1, both = self.run_piece(self.one, self.arena, lanes, (carried,))
        cut = len(lanes) * (1 + PIECE * self.be.stream_record)
        both = np.asarray(both)
        return (a, piece, wave), (a1, both[:cut], both[cut:])


_PROGRAMS = {}


def programs(family, lanes, **how):
    key = (family, lanes, *sorted(how.items()))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = Programs(family, lanes, **how)
    return _PROGRAMS[key]


def same_but_last_bits(one, two, lanes, width, rtol=1e-5):
    """Two programs' ``[lanes tokens | rows x width of the record]``: the
    same tokens and words; the logits the same numbers but for the order of
    a product's sums (the head's product holds another count of rows)."""
    assert np.array_equal(one[:lanes], two[:lanes])
    one = one[lanes:].reshape(-1, width)
    two = two[lanes:].reshape(-1, width)
    assert np.array_equal(one[:, :-BITS], two[:, :-BITS])
    assert np.allclose(one[:, -BITS:].copy().view(np.float32),
                       two[:, -BITS:].copy().view(np.float32), rtol=rtol,
                       atol=rtol)


# A piece in a prompt's middle (no lane ends: the head runs for the wave's
# rows alone) and its last (a lane ends).
@pytest.mark.parametrize("start", [PIECE, 2 * PIECE])
@pytest.mark.parametrize("family,lanes", CASES)
def test_one_program_is_the_piece_and_then_the_wave(family, lanes, start):
    p = programs(family, lanes)
    (a2, piece2, wave2), (a1, piece1, wave1) = p.step(start)
    for name in a2:
        one, two = np.asarray(a1[name]), np.asarray(a2[name])
        if name == "tok" and start == PIECE:
            one, two = one[:2], two[:2]      # (below: a lane that goes on)
        assert np.array_equal(one, two), name
    piece2 = np.asarray(piece2)
    if start == PIECE:
        # (No lane ends: the piece program skips its head and leaves zeros
        # where the carrying one, whose head runs for the wave, leaves the
        # token and bits that nothing reads.)
        width = p.be.stream_record
        assert not piece2[:lanes].any()
        rec1 = piece1[lanes:].reshape(-1, width)
        rec2 = piece2[lanes:].reshape(-1, width)
        assert np.array_equal(rec1[:, :-BITS], rec2[:, :-BITS])
    else:
        same_but_last_bits(piece1, piece2, lanes, p.be.stream_record)
    wave2 = np.asarray(wave2)
    width = p.be.stream_record
    assert wave1.shape == wave2.shape == (CAP * (1 + width) + 3,)
    # Tokens, the live lanes' rows of the record, the three counts.
    live = np.r_[0:2, CAP:CAP + 2 * width]
    same_but_last_bits(wave1[live], wave2[live], 2, width)
    assert np.array_equal(wave1[:CAP], wave2[:CAP])
    assert np.array_equal(wave1[-3:], wave2[-3:])


@pytest.mark.parametrize("start", [PIECE, 2 * PIECE])
@pytest.mark.parametrize("family,lanes", CASES)
def test_with_the_kernels_it_is_the_two_programs_within_the_tie(
        family, lanes, start):
    """bfloat16, the Pallas kernels (interpreted): the same tokens and the
    same choices; the logits within the harness's tie of a lone wave's."""
    p = programs(family, lanes, dtype="bfloat16", attn_impl="fused")
    (a2, _, wave2), (a1, _, wave1) = p.step(start)
    wave2 = np.asarray(wave2)
    width = p.be.stream_record
    assert np.array_equal(wave1[:CAP], wave2[:CAP])
    rec1 = wave1[CAP:CAP + 2 * width].reshape(2, width)
    rec2 = wave2[CAP:CAP + 2 * width].reshape(2, width)
    assert np.array_equal(rec1[:, :-BITS], rec2[:, :-BITS])
    logits1 = rec1[:, -BITS:].astype(np.int32).view(np.float32)
    logits2 = rec2[:, -BITS:].astype(np.int32).view(np.float32)
    scale = max(1.0, float(np.abs(logits2).max()))
    assert float(np.abs(logits1 - logits2).max()) <= 0.003 * scale * 10
    assert np.array_equal(wave1[-3:], wave2[-3:])
    for name in a2:
        one = np.asarray(a1[name]).astype(np.float32)
        two = np.asarray(a2[name]).astype(np.float32)
        # (The junk slot apart: the padded lanes' rows, tails and states.)
        keep, axis = (range(2), 0) if name == "tok" else (range(CAP), 1)
        assert np.allclose(np.take(one, keep, axis), np.take(two, keep, axis),
                           atol=0.05), name


@pytest.mark.parametrize("family,lanes", CASES)
def test_the_waves_counts_are_of_its_own_rows(family, lanes):
    """Two live lanes, ``top_k`` choices each in every expert layer, all
    held: the pairs are the wave's, whatever the piece's rows chose."""
    p = programs(family, lanes)
    _, (_, _, wave) = p.step(PIECE)
    pairs, busiest, touched = wave[-3:]
    layers = expert_layers(p.be)
    assert pairs == 2 * p.be.top_k * layers
    assert layers <= busiest <= 2 * layers
    assert pairs // 2 <= touched <= pairs


@pytest.mark.parametrize("start", [PIECE, 2 * PIECE])
@pytest.mark.parametrize("family,lanes", CASES)
def test_with_every_wave_lane_padded_it_is_the_piece_program(family, lanes,
                                                             start):
    """The junk slot apart (the padded lanes' rows and token land there): the
    same leaves, the same piece tokens and record.  Where no lane ends
    either, the one conditional is not taken and the wave's part is zeros;
    its counts are zeros always (padded lanes route nowhere)."""
    p = programs(family, lanes)
    (a2, piece2, _), (a1, piece1, wave1) = p.step(start, live=False)
    for name in a2:
        one, two = np.asarray(a1[name]), np.asarray(a2[name])
        axis = 0 if name == "tok" else 1
        assert np.array_equal(np.take(one, range(CAP), axis),
                              np.take(two, range(CAP), axis)), name
    same_but_last_bits(piece1, np.asarray(piece2), lanes,
                       p.be.stream_record)
    assert not wave1[-3:].any()
    if start == PIECE:
        width = p.be.stream_record
        assert not wave1[:CAP].any()
        assert not wave1[CAP:-3].reshape(CAP, width)[:, -BITS:].any()


def test_the_ring_and_the_state_backends_declare_it_and_no_other():
    """Five declare it, the two latent caches among them; the looped family
    and the dense hybrid keep their programs."""
    from client_tpu.models.decoder import DecoderBackend
    from client_tpu.models.granite_hybrid import GraniteHybridBackend
    from client_tpu.models.ouro import OuroBackend

    assert DecoderBackend.piece_wave is False
    for cls in (GraniteHybridBackend, OuroBackend):
        assert cls.piece_wave is False, cls
    assert sorted(cls.__name__ for cls in FAMILIES.values()) == [
        "CohereMoeBackend", "KimiLinearBackend", "NemotronHBackend",
        "PanguMoeBackend", "SmallThinkerBackend"]
    for cls in (*FAMILIES.values(), KimiLinearBackend, PanguMoeBackend):
        assert cls.piece_wave is True


# -- through the scheduler -------------------------------------------------------

def scheduler_of(be):
    repo = ModelRepository()
    repo.register_backend(be)
    engine = TpuEngine(repo)
    try:
        return engine._schedulers[be.config.name]
    finally:
        engine.shutdown()


def test_a_carrying_backends_waves_go_one_at_a_time(monkeypatch):
    """A piece's program carries one wave: ``CLIENT_TPU_GEN_CHUNK`` is not
    read for such a backend (as for one with transitions), so every piece
    that has lanes beside it carries them."""
    monkeypatch.setenv("CLIENT_TPU_GEN_CHUNK", "4")
    sched = scheduler_of(CohereMoeBackend(name="one_at_a_time",
                                          **tiny("cohere_moe")))
    assert sched._piece_wave == CAP and sched._chunk == 1
    assert sched._decode_chunk is None
    plain = scheduler_of(apart(CohereMoeBackend)(name="chunked",
                                                 **tiny("cohere_moe")))
    assert not plain._piece_wave and plain._chunk == 4


def test_a_carrying_backend_with_transitions_is_refused():
    """A transition is ordered before a stream's next wave, and the piece's
    program holds that wave: a backend may declare one or the other."""
    import copy

    from client_tpu.engine.generative import GenerativeScheduler

    sched = scheduler_of(SmallThinkerBackend(name="both",
                                             **tiny("smallthinker")))
    model = copy.copy(sched.model)
    model.backend = copy.copy(sched.model.backend)
    model.backend.transition_due = lambda n: False
    model.backend.transition_fn = lambda: (lambda p, arena, row: arena)
    with pytest.raises(ValueError, match="declares both"):
        GenerativeScheduler(model, sched.stats)


# (prompt length, tokens): pieces of later prompts stand in the gaps of the
# earlier streams' waves; slots are reused.
PLAN = [(3, 9), (21, 8), (16, 6), (37, 5), (12, 7)]


def span_counts(engine, model):
    snap = engine.profile_snapshot(model=model)
    return {name: s["count"] for name, s in
            snap["models"][f"{model}:1"]["generative"]["spans"].items()}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    """family -> per backend (the one that declares, the subclass that does
    not): (the streams' tokens and records, the counters' moves, the spans'
    counts); and the family's ``expert_layers``."""
    cls, out = FAMILIES[request.param], {}
    for which, kind in (("carries", cls), ("apart", apart(cls))):
        name = f"{request.param}_{which}"
        be = kind(name=name, **tiny(request.param, dtype="bfloat16",
                                    max_streams=2, attn_impl="fused"))
        out["expert_layers"] = expert_layers(be)
        repo = ModelRepository()
        repo.register_backend(be)
        engine = TpuEngine(repo)
        try:
            engine._schedulers[name].warmup()
            before = counters(engine, name)
            spans_before = span_counts(engine, name)
            rng = np.random.default_rng(7)
            joins = [stream(engine, rng.integers(0, 96, n).tolist(), m, name,
                            record=True) for n, m in PLAN]
            got = [j() for j in joins]
            after = counters(engine, name)
            spans_after = span_counts(engine, name)
        finally:
            engine.shutdown()
        out[which] = (got, {k: after[k] - before[k] for k in after},
                      {k: spans_after[k] - spans_before[k]
                       for k in spans_after})
    return out


def test_the_streams_get_the_same_tokens_either_way(served):
    carries, apart_ = served["carries"][0], served["apart"][0]
    assert [t for t, _ in carries] == [t for t, _ in apart_]
    assert [len(t) for t, _ in carries] == [m for _, m in PLAN]


def test_the_streams_records_hold_the_same_choices_either_way(served):
    """Every position's words (which held experts each layer chose); the
    logits' bits behind them differ by the order of a product's sums over
    another count of rows."""
    for (_, one), (_, two) in zip(served["carries"][0], served["apart"][0]):
        assert one.shape == two.shape
        assert np.array_equal(one[:, :-BITS], two[:, :-BITS])


def test_only_the_backend_that_declares_it_counts_carried_waves(served):
    carries, apart_ = served["carries"][1], served["apart"][1]
    assert apart_["fetched_waves_carried"] == 0
    assert 0 < carries["fetched_waves_carried"] <= carries["fetched_waves"]
    # A program a piece either way, and a wave is a wave wherever it ran.
    pieces = sum(-(-n // PIECE) for n, _ in PLAN)
    assert carries["prefill_pieces"] == apart_["prefill_pieces"] == pieces
    assert carries["fetched_waves"] == carries["dispatches"]
    tokens = sum(m for _, m in PLAN)
    for moved in (carries, apart_):
        assert moved["emitted_tokens_callback"] == tokens
        assert moved["expert_pairs_local"] > 0


def test_a_carried_wave_is_one_dispatch_and_one_fetch(served):
    """A program and a fetch an iteration where the wave rode: the decode
    program and its fetch are the lone waves' alone; a backend that does not
    carry dispatches two programs as before."""
    for which in ("carries", "apart"):
        _, moved, count = served[which]
        rode = moved["fetched_waves_carried"]
        lone = moved["fetched_waves"] - rode
        assert count[spans.GEN_WAVE_DISPATCH] == lone
        assert 0 < count[spans.GEN_PREFILL_DISPATCH] \
            <= moved["prefill_pieces"]
        assert count[spans.GEN_FETCH_WAIT] == \
            count[spans.GEN_PREFILL_DISPATCH] + lone
        assert rode <= count[spans.GEN_PREFILL_DISPATCH]


def test_the_carried_waves_stats_count_its_lanes_alone(served):
    """A wave's lane chooses ``top_k`` experts a layer, all held: the pairs
    are the waves' lanes', not the pieces' rows beside them."""
    for which in ("carries", "apart"):
        _, moved, _ = served[which]
        assert moved["expert_pairs_local"] == \
            moved["fetched_lanes_live"] * TOP_K * served["expert_layers"]


def test_a_gap_behind_a_carried_wave_is_behind_a_piece(served):
    """The gap counters see a piece's fetch between two decode fetches
    whether the wave rode in it or not."""
    carries, apart_ = served["carries"][1], served["apart"][1]
    assert carries["gap_lanes_behind_prefill"] > 0
    assert carries["gap_lanes"] >= carries["gap_lanes_behind_prefill"]
    assert apart_["gap_lanes_behind_prefill"] > 0


class Tap:
    """A profiler or ledger that notes one method's calls on their way."""
    def __init__(self, real, method, log):
        self._real, self._method, self._log = real, method, log

    def __getattr__(self, name):
        call = getattr(self._real, name)
        if name != self._method:
            return call

        def noted(*args, **kwargs):
            self._log.append((args, kwargs))
            return call(*args, **kwargs)
        return noted


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_carried_wave_is_charged_its_rows_share(family, monkeypatch):
    """The carrying program's interval is one clock's: a wave that rode is
    recorded and billed its rows' share of the frame's (the bucket's lanes
    over those and the piece's positions) of the time since the fetch
    before it, a lone wave the whole of its own, and what is recorded is
    what is billed (one charge a recorded wave, the same seconds)."""
    from client_tpu.engine import generative as gen

    waves, bills, fetches = [], [], []
    real_profiler, real_ledger = gen.profiler, gen.ledger
    monkeypatch.setattr(gen, "profiler", lambda: Tap(
        real_profiler(), "record_wave", waves))
    monkeypatch.setattr(gen, "ledger", lambda: Tap(
        real_ledger(), "charge_batch", bills))
    take = gen.GenerativeScheduler._take_fetch

    def noted(self, head, toks, since, share=1.0):
        n = len(waves)
        take(self, head, toks, since, share)
        fetches.append((head, share, time.monotonic_ns() - since,
                        waves[n:]))
    monkeypatch.setattr(gen.GenerativeScheduler, "_take_fetch", noted)
    name = f"{family}_billed"
    be = FAMILIES[family](name=name, **tiny(family, dtype="bfloat16",
                                            max_streams=2))
    repo = ModelRepository()
    repo.register_backend(be)
    engine = TpuEngine(repo)
    try:
        engine._schedulers[name].warmup()
        rng = np.random.default_rng(11)
        for join in [stream(engine, rng.integers(0, 96, n).tolist(), m, name)
                     for n, m in PLAN]:
            join()
    finally:
        engine.shutdown()
    rode = [f for f in fetches if f[0].bucket and f[1] != 1.0]
    lone = [f for f in fetches if f[0].bucket and f[1] == 1.0]
    assert rode and lone and len(waves) == len(rode) + len(lone)
    shares = {2 / (2 + n * PIECE)
              for n in range(1, be.prefill_piece[1] + 1)}
    for head, share, interval_ns, noted_waves in rode + lone:
        assert share == 1.0 or share in shares
        (_, kwargs), = noted_waves
        assert kwargs["bucket"] == head.bucket
        assert 0 <= kwargs["duration_ns"] <= share * interval_ns
    # A piece's own fetch records and bills nothing.
    assert all(not f[3] for f in fetches if not f[0].bucket)
    assert len(bills) == len(waves)
    for (args, kwargs), (_, wave) in zip(bills, waves):
        assert kwargs["component"] == "wave"
        assert args[3] == pytest.approx(wave["duration_ns"] / 1e9)
