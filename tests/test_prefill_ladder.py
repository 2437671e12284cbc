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


def admit_together(sched, eng, batch, max_tokens=2):
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
            model_name=MODEL, inputs={"INPUT_IDS": p},
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
