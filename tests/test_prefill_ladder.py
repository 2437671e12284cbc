"""Prefill lanes follow the prompts an admit holds (PR 31): a prompt bucket
large enough to be worth it has a ladder of lane counts, each a compiled
program; a prompt gets the same first token and the same arena rows whatever
program carries it; after ``warmup()`` no admit size compiles; the lane
counters add up to the lanes dispatched.

A tiny decoder on the CPU with a 1024-position bucket (counts and equalities
only, never a time)."""

import threading

import numpy as np
import pytest

from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.engine.generative import _LANE_WORTH_TOKENS
from client_tpu.engine.repository import ModelRepository
from client_tpu.models.generate import TinyGptBackend
from client_tpu.observability import spans

MODEL = "ladder_gpt"
GEOMETRY = dict(n_layers=1, d_model=64, n_heads=2, d_ff=128, vocab=64,
                max_seq_len=1024, max_streams=8)


def gen_counters(eng):
    g = eng.profile_snapshot(model=MODEL)["models"][f"{MODEL}:1"]["generative"]
    return g["counters"]


@pytest.fixture(scope="module")
def served():
    repo = ModelRepository()
    repo.register_backend(TinyGptBackend(name=MODEL, **GEOMETRY))
    eng = TpuEngine(repo)
    sched = eng._schedulers[MODEL]
    sched.warmup()
    yield eng, sched
    eng.shutdown()


def prompts(n, length=700):
    rng = np.random.default_rng(5)
    return [rng.integers(1, GEOMETRY["vocab"], length + 7 * i).astype(np.int32)
            for i in range(n)]


def test_the_ladder_exists_only_where_a_lane_is_worth_a_program(served):
    """Lane counts halve down from 8 while the program they halve holds more
    tokens than the mark: one more program at 1024 positions (4 lanes), one
    program a bucket below (tiny_gpt's whole range, and the 512 bucket), and
    a longer bucket halves further."""
    _, sched = served
    assert _LANE_WORTH_TOKENS == 4096
    assert sched._ladders[1024] == [4, 8]
    for bucket in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512):
        assert sched._ladders[bucket] == [8]
    assert sum(len(v) - 1 for v in sched._ladders.values()) == 1
    assert sched._lane_ladder(2048) == [2, 4, 8]
    assert sched._lane_ladder(8192) == [1, 2, 4, 8]
    # a backend that prefills by pieces has every power of two up to its
    # own lanes, whatever the piece holds (a lane with no prompt costs a
    # piece's mixers for nothing); one lane is a ladder of one
    sched._piece_len = 2048
    try:
        assert sched._lane_ladder(2048) == [1, 2, 4, 8]
        assert sched._lane_ladder(16) == [1, 2, 4, 8]
        sched._admit_lane = 1
        assert sched._lane_ladder(2048) == [1]
    finally:
        sched._piece_len, sched._admit_lane = 0, 8


@pytest.mark.parametrize("lanes", [1, 2, 4])
def test_a_prompt_is_prefilled_alike_in_every_program_of_the_ladder(
        served, lanes):
    """The same prompts through the ``lanes``-lane program and through the
    8-lane one, each into a fresh arena: equal first tokens, and the arena
    rows they wrote equal position for position (the program is one
    function of its lane count: a longer bucket's ladder uses 1 and 2)."""
    eng, sched = served
    backend = sched.model.backend
    ids = prompts(lanes)

    def run(width):
        arena = backend.init_arena(sched._cap)
        rows = np.full(width, sched._dummy, np.int32)
        mat = np.zeros((width, 1024), np.int32)
        lens = np.ones(width, np.int32)
        for i, p in enumerate(ids):
            rows[i], lens[i] = i, len(p)
            mat[i, :len(p)] = p
        zi, zf = np.zeros(width, np.int32), np.zeros(width, np.float32)
        arena, tokens = sched._prefill(
            sched.model._params, arena, rows, mat, lens, zi, zf, zi,
            np.ones(width, np.float32), False)
        return np.asarray(tokens)[:lanes], arena

    tok_small, arena_small = run(lanes)
    tok_full, arena_full = run(8)
    assert tok_small.tolist() == tok_full.tolist()
    for leaf in ("k", "v"):
        for i, p in enumerate(ids):
            np.testing.assert_array_equal(
                np.asarray(arena_small[leaf][:, i, :len(p)]),
                np.asarray(arena_full[leaf][:, i, :len(p)]))
    assert np.asarray(arena_small["tok"])[:lanes].tolist() == \
        tok_full.tolist()


def admit_together(sched, eng, batch, max_tokens=2, model=MODEL):
    """Submit ``batch`` while the worker is held, so that one admit holds
    them all; returns their token lists."""
    hold, held = threading.Event(), threading.Event()
    orig = sched._precompile

    def blocked():
        held.set()
        assert hold.wait(60)

    sched._precompile = blocked
    warm = threading.Thread(target=sched.warmup)
    warm.start()
    assert held.wait(60)
    sched._precompile = orig
    out = [[] for _ in batch]
    done = [threading.Event() for _ in batch]

    def callback(i):
        def cb(resp):
            assert resp.error is None, resp.error
            if resp.final:
                done[i].set()
            else:
                out[i].append(int(resp.outputs["TOKEN"][0]))
        return cb

    for i, p in enumerate(batch):
        eng.async_infer(InferRequest(
            model_name=model, inputs={"INPUT_IDS": p},
            parameters={"max_tokens": max_tokens}), callback(i))
    hold.set()
    warm.join(60)
    assert not warm.is_alive()
    for d in done:
        assert d.wait(120), "stream did not finish"
    return out


def test_after_warmup_no_admit_size_compiles_and_the_lanes_add_up(served):
    eng, sched = served
    solo = [admit_together(sched, eng, [p])[0] for p in prompts(8)]
    compiles = eng.profile_snapshot()["compiles"]["count"]
    before = gen_counters(eng)
    dispatched = []
    jitted = sched._prefill

    def counting(params, arena, rows, *rest):
        dispatched.append(int(rows.shape[0]))
        return jitted(params, arena, rows, *rest)

    sched._prefill = counting
    try:
        for n in (1, 2, 3, 4, 5, 8):
            got = admit_together(sched, eng, prompts(n))
            # whatever program carried them, the tokens are the solo ones
            assert got == solo[:n]
    finally:
        sched._prefill = jitted
    # one program an admit, the smallest of the ladder that holds it
    assert dispatched == [4, 4, 4, 4, 8, 8]
    after = gen_counters(eng)
    live = after["prefill_lanes_live"] - before["prefill_lanes_live"]
    padded = after["prefill_lanes_padded"] - before["prefill_lanes_padded"]
    assert live == 1 + 2 + 3 + 4 + 5 + 8
    assert live + padded == sum(dispatched)
    assert eng.profile_snapshot()["compiles"]["count"] == compiles
    assert set(spans.GEN_COUNTERS) >= {"prefill_lanes_live",
                                       "prefill_lanes_padded"}


def test_a_bucket_first_used_under_load_warms_its_whole_ladder():
    """A server started without ``warmup()`` (the benchmark's): the first
    prompt of a bucket runs every other lane count of its ladder first, so
    the admits that follow compile nothing."""
    repo = ModelRepository()
    repo.register_backend(TinyGptBackend(name=MODEL, **{
        **GEOMETRY, "seed": 3}))
    eng = TpuEngine(repo)
    try:
        sched = eng._schedulers[MODEL]
        assert sched._ladders[1024] == [4, 8] and not sched._ladders_warm

        def prefill_compiles():
            scope = eng.profile_snapshot()["compiles"]["by_scope"]
            return scope[f"{MODEL}:1:prefill:1024"]["count"]

        first = admit_together(sched, eng, prompts(1))
        assert sched._ladders_warm == {(1024, False)}
        compiled = prefill_compiles()
        assert compiled >= 2             # a program a lane count, at once
        for n in (5, 8, 2):              # (decode waves compile as they come)
            admit_together(sched, eng, prompts(n))
        assert prefill_compiles() == compiled
        assert admit_together(sched, eng, prompts(1)) == first
    finally:
        eng.shutdown()


# -- a piece's head runs only where a prompt ends (PR 51) -----------------------

PIECES = "ladder_ouro"      # pieces of 16 positions, two prompts a program


@pytest.fixture(scope="module")
def piece_served():
    """A backend that runs the decoder's piece frame (float32, so that a
    token is the plain reference's to the bit of an argmax), and the plain
    reference's greedy continuation of a prompt: no cache, no piece, no
    conditional (``benchmark/models/ouro.py``)."""
    import os
    import sys

    import jax

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    import family

    from client_tpu.models.ouro import OuroBackend

    fam = family.load("ouro")
    be = OuroBackend(name=PIECES, seed=5, max_seq_len=64, piece=16,
                     dtype="float32", max_streams=2)
    params = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                    be._init_params())

    def one_shot(prompt, n):
        seq = [int(t) for t in prompt]
        for _ in range(n):
            logits, _ = fam.backend_forward(params, be,
                                            np.asarray(seq, np.int32), 1)
            seq.append(int(np.asarray(logits)[-1].argmax()))
        return seq[len(prompt):]

    repo = ModelRepository()
    repo.register_backend(be)
    eng = TpuEngine(repo)
    sched = eng._schedulers[PIECES]
    sched.warmup()
    yield eng, sched, one_shot
    eng.shutdown()


# The prompts of a line (their lengths) -> (piece programs, those of them in
# which some lane ended).  40 positions are three pieces, 16 exactly one full
# piece: nothing in the program says that it is the prompt's last.
PIECE_LINES = {
    "three_pieces_alone": ([40], (3, 1)),
    "exactly_one_full_piece_alone": ([16], (1, 1)),
    "one_lane_ends_beside_one_that_goes_on": ([40, 16], (3, 2)),
    "both_lanes_end_in_a_full_piece": ([16, 16], (1, 1)),
}


@pytest.mark.parametrize("line", sorted(PIECE_LINES))
def test_a_piece_without_a_head_loses_no_token(piece_served, line):
    """Served through the scheduler, a prompt emits the tokens of the
    one-shot reference whether its pieces ran alone or beside another
    prompt's, in a program whose other lane ended or went on: the head ran
    exactly where the worker said a lane ends."""
    eng, sched, one_shot = piece_served
    lengths, (programs, heads) = PIECE_LINES[line]
    rng = np.random.default_rng(11)
    batch = [rng.integers(1, 96, n).astype(np.int32) for n in lengths]

    def profile():
        g = eng.profile_snapshot(model=PIECES)["models"][f"{PIECES}:1"][
            "generative"]
        return (g["spans"][spans.GEN_PREFILL_DISPATCH]["count"],
                g["counters"]["prefill_heads"],
                g["counters"]["prefill_pieces"])

    before = profile()
    got = admit_together(sched, eng, batch, max_tokens=3, model=PIECES)
    assert got == [one_shot(p, 3) for p in batch]
    after = profile()
    assert tuple(a - b for a, b in zip(after, before)) == (
        programs, heads, sum(-(-n // 16) for n in lengths))
