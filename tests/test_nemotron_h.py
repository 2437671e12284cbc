"""The decoder whose state-space, attention and expert layers are each a block
of their own (models/nemotron_h.py) at a tiny preset on the CPU, seeded
weights, Pallas interpreted: the served path (chunked pieces, single-step
waves through the state, the tail and the key/value rows) against the plain
reference's token-by-token forward pass on logits; what a slot's life asks of
a state that ``lens`` cannot mask (a reused slot, padded lanes, padded
positions, any cut into pieces, a prompt shorter than the convolution); the
two shares of a stage adding up to the uncut expert layer; the scheduler's
counters and a stream's record; the benchmark family's arithmetic, readers
and comparison."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "testdata"))

import family  # noqa: E402

from client_tpu.engine import TpuEngine  # noqa: E402
from client_tpu.engine.repository import ModelRepository  # noqa: E402
from client_tpu.engine.types import InferRequest  # noqa: E402
from client_tpu.models.nemotron_h import NemotronHBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402

fam = family.load("nemotron_h")
kimi = family.load("kimi_linear")
SEQ, PIECE, N = 64, 16, 44
TOL_F32 = 2e-4
# bfloat16 matmuls, rows and convolution tail against the float32 reference
# with the routing followed, at the tiny preset (logits of magnitude 3).
TOL_BF16 = 0.15


def backend(**kw):
    """The tiny preset (``MEM*EME``: 3 M, 3 E, 1 *), pieces of two chunks."""
    return NemotronHBackend(**{"seed": 5, "max_seq_len": SEQ, "piece": PIECE,
                               "chunk": 8, **kw})


def f32_params(be):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  be._init_params())


def ids_of(n=N, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def words_of(be, routes):
    """Choices ``[expert layers, n, top_k]`` -> the record's words ``[n,
    expert layers x held_words]`` (numpy's form of ``_words``)."""
    e = np.asarray(routes, np.int64) - be.first_expert       # [L, n, k]
    out = []
    for layer in e:
        for w in range(be.held_words):
            here = layer - 32 * w
            ok = (here >= 0) & (here < min(32, be.experts_held - 32 * w))
            out.append(np.where(ok, 1 << np.clip(here, 0, 31), 0).sum(-1))
    return np.stack(out, axis=1).astype(np.uint32).view(np.int32)


def reference(be, ids, follow=None):
    """``follow``: the program's choices, followed as a record's words."""
    layers = be.layer_kinds.count("none")
    with jax.default_matmul_precision("highest"):
        logits, chosen, flips = fam.backend_forward(
            f32_params(be), be, ids, len(ids),
            follow=None if follow is None else words_of(be, follow).reshape(
                len(ids), layers, be.held_words))
    return np.asarray(logits), chosen, flips


class Served:
    """A backend's jitted piece and wave, an arena of three slots and the
    junk one, and the teacher-forced walk of a prompt through them."""

    def __init__(self, be):
        self.be = be
        self.params = be.place_params(be._init_params())
        self.arena = be.init_arena(3)
        self.piece = jax.jit(be.piece_hidden_fn())
        self.hidden = jax.jit(be._decode_hidden_fn())

    def prefill(self, ids, slot=1):
        be, logits, routes = self.be, [], []
        for st in range(0, len(ids), be.piece):
            n = min(be.piece, len(ids) - st)
            buf = np.zeros((1, be.piece), np.int32)
            buf[0, :n] = ids[st:st + n]
            self.arena, x, route = self.piece(
                self.params, self.arena, np.asarray([slot], np.int32), buf,
                np.asarray([n], np.int32), np.asarray([st], np.int32))
            logits.append(np.asarray(be._logits(self.params, x[:n])))
            routes.append(np.asarray(route)[:, :n])
        return np.concatenate(logits), np.concatenate(routes, axis=1)

    def wave(self, tokens, lengths, slots):
        """One wave of the given lanes and one more, padded onto the junk
        slot.  -> logits ``[lanes, vocab]``, choices ``[layers, lanes, k]``."""
        tok = self.arena["tok"]
        for s, t in zip(slots, tokens):
            tok = tok.at[s].set(int(t))
        self.arena = {**self.arena, "tok": tok}
        self.arena, x = self.hidden(
            self.params, self.arena, np.asarray([*slots, 3], np.int32),
            np.asarray([*lengths, 0], np.int32))
        n = len(slots)
        return (np.asarray(self.be._logits(self.params, x))[:n],
                np.stack([np.asarray(r)[:n] for r in x["route"]]))

    def walk(self, ids, n_prompt, slot=1):
        logits, routes = self.prefill(ids[:n_prompt], slot)
        logits, routes = [logits], [routes]
        for t in range(n_prompt, len(ids)):
            row, route = self.wave([ids[t]], [t], [slot])
            logits.append(row)
            routes.append(route)
        return np.concatenate(logits), np.concatenate(routes, axis=1)

    def slot(self, slot):
        """What the slot holds of a stream: its states and tails."""
        return (np.asarray(self.arena["s"][:, slot]),
                np.asarray(self.arena["conv"][:, slot].astype(jnp.float32)))


# -- the served path against the plain reference, on logits -------------------

@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
@pytest.mark.parametrize("n_prompt", [37, 2, 16])
def test_float32_pieces_then_waves_match_the_token_by_token_reference(
        attn_impl, n_prompt):
    """float32 weights, caches and matmuls: chunked pieces (two and a bit,
    with a boundary inside a chunk's worth of padding; a prompt shorter than
    the convolution; exactly one piece), then single-step waves through the
    state, the tail and the rows, give the logits of the reference's full
    forward pass at every position, and the same experts."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    ids = ids_of()
    want, chosen, _ = reference(be, ids)
    got, routes = Served(be).walk(ids, n_prompt)
    assert np.array_equal(np.sort(routes, -1), np.sort(chosen, -1))
    assert np.abs(got - want).max() < TOL_F32


def test_bfloat16_pieces_then_waves_match_the_reference_that_follows():
    be = backend()
    ids = ids_of()
    got, routes = Served(be).walk(ids, 37)
    want, _, _ = reference(be, ids, follow=routes)
    assert np.abs(got - want).max() < TOL_BF16


def test_the_full_context_apply_is_the_reference_too():
    be = backend(dtype="float32")
    apply, params = be.make_apply_params()
    ids = ids_of()
    with jax.default_matmul_precision("highest"):
        out = apply(params, {"INPUT_IDS": jnp.asarray(ids)})
    want, chosen, _ = reference(be, ids)
    assert np.abs(np.asarray(out["logits"]) - want).max() < TOL_F32
    assert np.array_equal(np.sort(np.asarray(out["routing"]), -1),
                          np.sort(chosen, -1))


def test_the_layers_are_of_three_kinds_and_an_expert_layer_owns_no_leaf():
    be = backend()
    assert be.layer_kinds == ("state", "none", "state", "rows", "none",
                              "state", "none")
    assert [be._layer_kind(li) for li in range(7)] == [
        ("state", 0), ("none", 0), ("state", 1), ("rows", 0), ("none", 1),
        ("state", 2), ("none", 2)]
    arena = jax.eval_shape(lambda: be.init_arena(3))
    assert arena["k"].shape == arena["v"].shape == (1, 4, SEQ, 2 * 16)
    # Two heads of a group side by side: 4 heads of [16, 16] as 2 of [16, 32].
    assert be.pack == 2 and arena["s"].shape == (3, 4, 2, 16, 32)
    assert arena["s"].dtype == jnp.float32
    assert arena["conv"].shape == (3, 4, 3 * (4 * 16 + 2 * 2 * 16))
    assert be.cache_rows_by_kind(11) == (0, 11, 0)
    for lp, kind in zip(be._init_params()["layers"], be.layer_kinds):
        assert ("eu" in lp) == (kind == "none")
        assert ("wxbc" in lp) == (kind == "state")
        assert ("wq" in lp) == (kind == "rows")
        assert "ln" in lp and "ln2" not in lp
    with pytest.raises(ValueError):
        backend(pattern="MEMEME")               # no attention layer
    with pytest.raises(ValueError):
        backend(pattern="MEM*X")
    # The published pattern, cut to its first thirteen letters.
    published = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    cut = backend(pattern=published, n_layers=13)
    assert (cut.layer_kinds.count("state"), cut.layer_kinds.count("none"),
            cut.layer_kinds.count("rows")) == (6, 5, 2)


# -- a slot's life ---------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_a_slot_reused_by_a_second_stream_equals_a_fresh_slot(attn_impl):
    """The first stream leaves rows, a state and a tail behind; the second
    stream's first piece starts from zeros whatever is there."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    first, second = ids_of(50, seed=1), ids_of(30, seed=2)
    used, fresh = Served(be), Served(be)
    used.walk(first, 41)
    got, _ = used.walk(second, 21)
    want, _ = fresh.walk(second, 21)
    assert np.array_equal(got, want)
    for a, b in zip(used.slot(1), fresh.slot(1)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_padded_lanes_and_padded_positions_leave_a_live_slot_bit_for_bit(
        attn_impl):
    """Slot 0 holds a live stream.  A piece of another slot, padded past its
    prompt, and waves whose other lanes are padded (on the junk slot) leave
    slot 0's state and tail bit for bit; the padded piece leaves its own
    slot the state of its valid positions alone."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    srv = Served(be)
    srv.walk(ids_of(30, seed=3), 20, slot=0)
    before = srv.slot(0)
    other = ids_of(21, seed=4)          # a piece of 16 and one of 5 + 11 padded
    srv.walk(np.concatenate([other, ids_of(4, seed=5)]), 21, slot=1)
    for a, b in zip(srv.slot(0), before):
        assert np.array_equal(a, b)
    srv2 = Served(be)
    srv2.prefill(other, slot=2)
    exact = Served(backend(dtype="float32", attn_impl=attn_impl, piece=32,
                           chunk=1))
    exact.prefill(other, slot=2)
    for a, b in zip(srv2.slot(2), exact.slot(2)):
        assert np.abs(a - b).max() < 2e-5


# Two lanes of one piece call: (tokens prefilled before the call, tokens the
# call holds) a lane, each lane's prompt its own.
TWO_LANES = {
    "two_first_pieces": [(0, 16), (0, 16)],
    "a_first_piece_beside_a_third": [(0, 16), (32, 9)],
    "a_third_beside_a_second_cut_short": [(32, 16), (16, 5)],
    "a_lane_shorter_than_the_convolution": [(0, 2), (16, 16)],
    "a_first_piece_of_one_token": [(16, 11), (0, 1)],
}


def _piece_args(lanes, slots):
    """A piece call's (rows, ids, lens, starts) for ``lanes`` [(prompt,
    before, held)]."""
    buf = np.zeros((len(lanes), PIECE), np.int32)
    for i, (ids, before, held) in enumerate(lanes):
        buf[i, :held] = ids[before:before + held]
    return (np.asarray(slots, np.int32), buf,
            np.asarray([held for _, _, held in lanes], np.int32),
            np.asarray([before for _, before, _ in lanes], np.int32))


def _two_lanes(dtype, attn_impl, case):
    """The case's two lanes through one two-lane call (``pair``) and through
    two one-lane calls (``solo``), both after the same one-lane pieces
    before.  -> (pair, solo, per lane (x, routes) of each, the lanes)."""
    be = backend(dtype=dtype, attn_impl=attn_impl)
    pair, solo = Served(be), Served(be)
    lanes = [(ids_of(48, seed=30 + i), before, held)
             for i, (before, held) in enumerate(TWO_LANES[case])]
    for srv in (pair, solo):
        for slot, (ids, before, _) in enumerate(lanes):
            if before:
                srv.prefill(ids[:before], slot=slot)
    pair.arena, x, routes = pair.piece(pair.params, pair.arena,
                                       *_piece_args(lanes, [0, 1]))
    x, routes = np.asarray(x), np.asarray(routes)
    got = [(x[i * PIECE:(i + 1) * PIECE],
            routes[:, i * PIECE:(i + 1) * PIECE]) for i in range(2)]
    want = []
    for slot, lane in enumerate(lanes):
        solo.arena, x, routes = solo.piece(solo.params, solo.arena,
                                           *_piece_args([lane], [slot]))
        want.append((np.asarray(x), np.asarray(routes)))
    return pair, solo, got, want, lanes


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
@pytest.mark.parametrize("case", sorted(TWO_LANES))
def test_two_lanes_of_a_piece_are_the_lanes_alone_bit_for_bit(attn_impl,
                                                              case):
    """float32: two prompts' pieces in one program (the projections and the
    expert block over both lanes' positions at once, the mixers a lane at a
    time, each from its own slot and its own ``start``) leave every slot's
    states, tails and rows, and give every valid position's activations and
    choices, exactly as the same two pieces do one lane at a time."""
    pair, solo, got, want, lanes = _two_lanes("float32", attn_impl, case)
    for leaf in ("s", "conv", "k", "v"):
        assert np.array_equal(np.asarray(pair.arena[leaf][:, :3]),
                              np.asarray(solo.arena[leaf][:, :3])), leaf
    for (x, routes), (x1, routes1), (_, _, held) in zip(got, want, lanes):
        assert np.array_equal(x[:held], x1[:held])
        assert np.array_equal(routes[:, :held], routes1[:, :held])


@pytest.mark.parametrize("case", ["a_first_piece_beside_a_third",
                                  "a_lane_shorter_than_the_convolution"])
def test_two_bfloat16_lanes_are_the_lanes_alone_within_the_files_limits(case):
    """bfloat16: a matmul over twice the rows may round an activation the
    other way, so the two forms agree to the file's limits and not to the
    bit; the states are float32 and agree far closer."""
    pair, solo, got, want, lanes = _two_lanes("bfloat16", "fused", case)
    for slot in (0, 1):
        for a, b in zip(pair.slot(slot), solo.slot(slot)):
            assert np.abs(a - b).max() < TOL_BF16 / 10
    for (x, _), (x1, _), (_, _, held) in zip(got, want, lanes):
        logits, logits1 = (np.asarray(pair.be._logits(pair.params, t[:held]))
                           for t in (x, x1))
        assert np.abs(logits - logits1).max() < TOL_BF16


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_a_padded_second_lane_leaves_every_live_slot_untouched(attn_impl):
    """A two-lane call whose second lane holds no prompt (the junk slot, one
    position, as the scheduler pads it): slots 0 and 2 hold live streams and
    stand bit for bit, and slot 1's piece is the one-lane program's."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    pair, solo = Served(be), Served(be)
    ids = ids_of(40, seed=41)
    for srv in (pair, solo):
        srv.walk(ids_of(30, seed=42), 20, slot=0)
        srv.walk(ids_of(25, seed=43), 18, slot=2)
        srv.prefill(ids[:16], slot=1)
    lane = (ids, 16, 16)
    rows, buf, lens, starts = _piece_args([lane], [1])
    pair.arena, x, _ = pair.piece(
        pair.params, pair.arena, np.asarray([1, 3], np.int32),
        np.concatenate([buf, np.zeros_like(buf)]), np.asarray([16, 1],
                                                              np.int32),
        np.asarray([16, 0], np.int32))
    solo.arena, x1, _ = solo.piece(solo.params, solo.arena, rows, buf, lens,
                                   starts)
    assert np.array_equal(np.asarray(x)[:16], np.asarray(x1))
    for leaf in ("s", "conv", "k", "v"):
        assert np.array_equal(np.asarray(pair.arena[leaf][:, :3]),
                              np.asarray(solo.arena[leaf][:, :3])), leaf


@pytest.mark.parametrize("sample", [False, True])
def test_two_lanes_tokens_and_record_rows_are_the_lanes_alone(sample):
    """The whole prefill program: a token a lane from its own last valid
    position into its own slot, and the record laid ``[L | L x piece x
    stream_record]`` as the scheduler cuts it, lane after lane."""
    be = backend(dtype="float32", record=True)
    step = jax.jit(be.prefill_fn(), static_argnums=be.prefill_static_argnums)
    params = be.place_params(be._init_params())
    lanes = [(ids_of(48, seed=50), 0, 7), (ids_of(48, seed=51), 0, 16)]
    width = PIECE * be.stream_record

    def run(which, slots):
        rows, buf, lens, starts = _piece_args(which, slots)
        n = len(which)
        arena, out = step(
            params, be.init_arena(3), rows, buf, lens,
            np.asarray(slots, np.int32) + 5,          # a seed a slot
            np.full(n, 0.9 if sample else 0.0, np.float32),
            np.full(n, 8, np.int32), np.full(n, 0.95, np.float32), sample,
            starts, np.ones(n, np.int32))
        out = np.asarray(out)
        assert out.shape == (n * (1 + width),)
        return (np.asarray(arena["tok"]), out[:n],
                out[n:].reshape(n, PIECE, be.stream_record))

    tok, tokens, record = run(lanes, [2, 0])
    for i, (lane, slot) in enumerate(zip(lanes, (2, 0))):
        tok1, tokens1, record1 = run([lane], [slot])
        assert tokens[i] == tokens1[0] == tok[slot] == tok1[slot]
        assert np.array_equal(record[i, :lane[2]], record1[0, :lane[2]])
        # A lane's logits stand in its last valid row and nowhere else.
        assert (record[i, :lane[2] - 1, -9:] == 0).all()
        assert record[i, lane[2] - 1, -9:].any()


@pytest.mark.parametrize("piece,chunk", [(64, 16), (32, 8), (8, 8), (8, 2)])
def test_a_prompt_cut_into_1_2_and_5_pieces_gives_one_state(piece, chunk):
    """A prompt of 40 positions as one piece, two and five: the same state,
    the same tail and the same rows, the token-by-token walk's (a piece of
    chunks of one position)."""
    ids = ids_of(40, seed=6)
    want = Served(backend(dtype="float32", piece=64, chunk=1))
    want.prefill(ids)
    got = Served(backend(dtype="float32", piece=piece, chunk=chunk))
    got.prefill(ids)
    for a, b in zip(got.slot(1), want.slot(1)):
        assert np.abs(a - b).max() < 2e-5
    for leaf in ("k", "v"):
        assert np.abs(np.asarray(got.arena[leaf][:, 1, :40], np.float32)
                      - np.asarray(want.arena[leaf][:, 1, :40], np.float32)
                      ).max() < 2e-5


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_a_wave_of_mixed_lengths_equals_the_streams_alone(attn_impl):
    """Three streams of 5, 19 and 33 positions advanced together, four waves,
    give each the logits it gets alone in a wave of one."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    streams = [ids_of(n + 4, seed=20 + i) for i, n in enumerate((5, 19, 33))]
    lens = [len(s) - 4 for s in streams]
    both, alone = Served(be), Served(be)
    for srv in (both, alone):
        for slot, (s, n) in enumerate(zip(streams, lens)):
            srv.prefill(s[:n], slot=slot)
    for step in range(4):
        got, _ = both.wave([s[n + step] for s, n in zip(streams, lens)],
                           [n + step for n in lens], [0, 1, 2])
        for slot, (s, n) in enumerate(zip(streams, lens)):
            want, _ = alone.wave([s[n + step]], [n + step], [slot])
            assert np.abs(got[slot] - want[0]).max() < 2e-5


def test_the_chunked_step_of_three_is_three_waves():
    be = backend(dtype="float32")
    ids = ids_of(30, seed=7)
    one, three = Served(be), Served(be)
    for srv in (one, three):
        srv.prefill(ids[:20])
        srv.arena = {**srv.arena,
                     "tok": srv.arena["tok"].at[1].set(int(ids[20]))}
    rows, lens = np.asarray([1, 3], np.int32), np.asarray([20, 0], np.int32)
    zeros_i, zeros_f = np.zeros(2, np.int32), np.zeros(2, np.float32)
    ones_f = np.ones(2, np.float32)
    decode = jax.jit(be.decode_fn(), static_argnums=be.decode_static_argnums)
    chunk = jax.jit(be.decode_chunk_fn(),
                    static_argnums=be.decode_chunk_static_argnums)
    toks = []
    for step in range(3):
        one.arena, t = decode(one.params, one.arena, rows, lens + step *
                              np.asarray([1, 0], np.int32), zeros_i, zeros_f,
                              zeros_i, ones_f, False)
        toks.append(np.asarray(t)[:2])
    three.arena, got = chunk(three.params, three.arena, rows, lens, zeros_i,
                             zeros_f, zeros_i, ones_f, False, 3)
    assert np.array_equal(np.asarray(got)[:, 0], np.stack(toks)[:, 0])
    for a, b in zip(one.slot(1), three.slot(1)):
        assert np.abs(a - b).max() < 2e-5


# -- the two shares of a stage ---------------------------------------------------

def test_two_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """One chip of a stage holds experts 0-3 of 8 and the other 4-7; each
    computes the shared expert.  The two shares' routed parts and the shared
    expert counted once add up to the uncut reference's expert layer, in the
    program (float32) and in the reference."""
    whole = backend(dtype="float32")
    halves = [backend(dtype="float32", experts_held=4, first_expert=f)
              for f in (0, 4)]
    h = jnp.asarray(np.random.default_rng(3).normal(size=(11, 64)),
                    jnp.float32)
    live = jnp.ones(11, bool)

    def routed(be):
        lp = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, np.float32)),
            be._init_params()["layers"][1])
        with jax.default_matmul_precision("highest"):
            y, counts, top_i = be._experts(lp, h, live, 16)
            return (np.asarray(y), np.asarray(counts), np.asarray(top_i),
                    np.asarray(be._dense_expert(h, lp["su"], lp["sd"])), lp)

    y_all, counts_all, top_all, shared, lp_all = routed(whole)
    parts = [routed(be) for be in halves]
    assert np.array_equal(parts[0][2], top_all)      # one router, one choice
    assert counts_all[0] == 22 == parts[0][1][0] + parts[1][1][0]
    assert np.abs(parts[0][0] + parts[1][0] - y_all).max() < 1e-5
    assert np.abs(parts[0][3] - shared).max() == 0
    # The reference's uncut layer is the program's routed parts and the
    # shared expert once; its share leaves out what the absent half adds.
    with jax.default_matmul_precision("highest"):
        ref_all, chosen, _ = fam.expert_layer(
            {k: np.asarray(v) for k, v in lp_all.items()}, h, top_k=2,
            scale=2.5, first=0)
        ref_half, _, _ = fam.expert_layer(
            {k: np.asarray(v) for k, v in parts[1][4].items()}, h, top_k=2,
            scale=2.5, first=4)
    assert np.array_equal(np.sort(chosen, -1), np.sort(top_all, -1))
    assert np.abs(np.asarray(ref_all) - (y_all + shared)).max() < 1e-4
    assert np.abs(np.asarray(ref_half) - (parts[1][0] + shared)).max() < 1e-4


def test_an_expert_is_two_matrices_and_a_width_off_the_lanes_is_never_minor():
    be = backend()
    lp = be._init_params()["layers"][1]
    assert "egu" not in lp and lp["eu"].shape == lp["ed"].shape == (8, 24, 64)
    assert lp["su"].shape == (64, 48) and lp["sd"].shape == (48, 64)
    h = jnp.asarray([[-1.0, 2.0]])
    assert np.array_equal(np.asarray(be._between(h)), [[0.0, 4.0]])


# -- the scheduler -----------------------------------------------------------------

def stream(engine, prompt, max_tokens, model, record=False):
    tokens, err, done, final = [], [], threading.Event(), []

    def cb(resp):
        if resp.error is not None:
            err.append(resp.error)
            done.set()
        elif resp.final:
            final.append(resp.outputs.get("RECORD"))
            done.set()
        else:
            tokens.append(int(resp.outputs["TOKEN"][0]))

    engine.async_infer(InferRequest(
        model_name=model, inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
        parameters={"max_tokens": max_tokens, "seed": 0,
                    **({"record": True} if record else {})}), cb)

    def join():
        assert done.wait(300), "stream did not finish"
        assert not err, err
        return (tokens, final[0]) if record else tokens

    return join


def counters(engine, model):
    sched = engine._schedulers[model]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if not sched._streams and not sched._inflight \
                and sched._rec.open is sched._rec.span[spans.S_IDLE]:
            break
        time.sleep(0.005)
    snap = engine.profile_snapshot(model=model)
    return snap["models"][f"{model}:1"]["generative"]["counters"]


# (prompt length, tokens): one, two and three pieces, one shorter than the
# convolution; slots are reused.
PLAN = [(2, 6), (20, 5), (40, 6), (21, 4)]


@pytest.fixture(scope="module", params=["reference", "fused"])
def served(request):
    name = f"nemotron_{request.param}"
    be = backend(name=name, attn_impl=request.param, max_streams=2,
                 record=True)
    repo = ModelRepository()
    repo.register_backend(be)
    engine = TpuEngine(repo)
    engine._schedulers[name].warmup()
    before = counters(engine, name)
    prompts = [ids_of(n, seed=10 + i).tolist()
               for i, (n, _) in enumerate(PLAN)]
    joins = [stream(engine, p, m, name, record=True)
             for p, (_, m) in zip(prompts, PLAN)]
    together = [j() for j in joins]
    alone = [stream(engine, p, m, name, record=True)()
             for p, (_, m) in zip(prompts, PLAN)]
    after = counters(engine, name)
    plain = stream(engine, prompts[0], PLAN[0][1], name)()
    yield (be, prompts, [t for t, _ in together], [t for t, _ in alone],
           before, after, [r for _, r in together], [r for _, r in alone],
           plain)
    engine.shutdown()


class TestScheduler:
    def test_together_equals_alone_token_for_token(self, served):
        _, _, together, alone, *_ = served
        assert together == alone
        assert [len(t) for t in together] == [m for _, m in PLAN]

    def test_a_stream_that_asks_gets_its_record_and_no_other(self, served):
        be, prompts, together, _, _, _, records, _, plain = served
        assert plain == together[0]
        words_n = be.layer_kinds.count("none") * be.held_words
        for p, (_, m), toks, rec in zip(prompts, PLAN, together, records):
            assert rec.shape == (len(p) + m - 1, be.stream_record)
            assert rec.dtype == np.int32
            words, logits = kimi.record_columns(rec, words_n)
            assert ((words >= 0) & (words < 1 << be.experts_held)).all()
            assert (logits[len(p) - 1:, 0] >= logits[len(p) - 1:, 1:].max(-1)
                    ).all()                            # greedy: the row's best

    def test_the_reference_accepts_every_token(self, served):
        """Following each stream's served routing, on its served logits:
        at the tiny preset's own limits (logits of magnitude 3)."""
        (be, prompts, together, alone, _, _, rec_together, rec_alone,
         _) = served
        params = f32_params(be)
        layers, words = be.layer_kinds.count("none"), be.held_words

        def rows_fn(prompt, emitted, record_words):
            seq = np.asarray(prompt + emitted, np.int32)
            with jax.default_matmul_precision("highest"):
                logits, _, flips = fam.backend_forward(
                    params, be, seq[:-1], len(emitted),
                    follow=np.asarray(record_words).reshape(-1, layers,
                                                            words))
            return logits, flips

        for i, (p, (_, m)) in enumerate(zip(prompts, PLAN)):
            one = {"prompts": [p], "max_tokens": m,
                   "concurrent": [together[i]], "solo": [alone[i]],
                   "concurrent_record": [rec_together[i]],
                   "solo_record": [rec_alone[i]]}
            verdict = kimi.judge(one, rows_fn, layers * words,
                                 margin=TOL_BF16,
                                 logit_rms_alone=TOL_BF16 / 3,
                                 logit_rms_together=TOL_BF16 / 3,
                                 logit_max=TOL_BF16, tie=0.02)
            assert verdict["ok"], verdict
            assert verdict["tokens_checked"] == 2 * m

    def test_piece_positions_rows_and_routing_reach_the_counters(self, served):
        be, _, _, _, before, after, *_ = served
        c = {k: after[k] - before[k] for k in after}
        pieces = sum(-(-n // PIECE) for n, _ in PLAN) * 2
        assert c["prefill_pieces"] == pieces
        assert c["prefill_positions_valid"] == 2 * sum(n for n, _ in PLAN)
        assert c["prefill_positions_padded"] == pieces * PIECE \
            - c["prefill_positions_valid"]
        assert c["fetched_waves"] > 0 and c["expert_pairs_local"] > 0
        # One attention layer reads every live position's row; no ring.
        assert c["fetched_rows_global"] == c["fetched_positions_valid"]
        assert c["fetched_rows_window"] == 0
        assert c["experts_touched"] <= c["fetched_waves"] * 3 * 8


# -- the benchmark family ----------------------------------------------------------

def _config():
    from traffic import load_json

    return load_json(os.path.join(BENCH, "configs",
                                  "nemotron3_nano_30b.json"))


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts():
    cfg = _config()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: row["config"][k] for k in cfg["reduced"]}
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "nemotron3_nano_30b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_backend_built_from_the_file_is_the_issues_arena():
    from serve import backend_kwargs

    cfg = _config()
    be = NemotronHBackend(name="n", **backend_kwargs(cfg, 7, None))
    assert be.layer_kinds.count("state") == 6
    assert be.layer_kinds.count("none") == 5
    assert be.layer_kinds.count("rows") == 2
    assert (be.d_inner, be.conv_dim, be.pack, be.chunk) == (4096, 6144, 2,
                                                            128)
    assert (be.n_experts, be.experts_held, be.held_words) == (128, 64, 2)
    arena = jax.eval_shape(lambda: be.init_arena(be.max_streams))
    assert arena["s"].shape == (6, 257, 32, 128, 128)
    assert arena["conv"].shape == (6, 257, 3 * 6144)
    assert arena["k"].shape == (2, 257, 4096, 256)
    cache = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in arena.values())
    assert 5.44e9 < cache < 5.46e9
    n_params = sum(int(np.prod(w.shape)) for w in jax.tree_util.tree_leaves(
        be._init_params(), is_leaf=lambda w: hasattr(w, "shape")))
    assert 3.92e9 < n_params < 3.93e9                # the issue's 3926M
    assert be.stream_record == 5 * 2 + 1 + 8


def test_step_arithmetic_by_hand():
    """The issue's reckoning of a wave of 250 live lanes at 2000 positions:
    1.05 GB a state call, 6.39 GB of touched experts, 13-14 GB a wave."""
    cfg = _config()
    _, state = fam.ssm_update(cfg, 250)
    assert 1.05e9 < state < 1.06e9
    _, experts = fam.expert_ffn(cfg, 750, 64)
    assert 1.27e9 < experts < 1.30e9                 # a layer: 64 x 2 x 9.98 MB
    _, rows = fam.decode_attention(cfg, 250, 2000)
    assert 0.51e9 < rows < 0.52e9
    flops, total = fam.decode_step(cfg, 250, 2000, 750, 64)
    assert 14.4e9 < total < 15.2e9
    s_bytes, r_bytes = fam.cache_bytes(cfg, 250, 250 * 2000)
    assert 0.85 < s_bytes / (s_bytes + r_bytes) < 0.87
    assert fam.wave_rows(cfg) == 2496


def _ctx(cfg, counters, trace=None):
    def snap(c):
        return {"profile": {"models": {"m:1": {"generative": {
            "spans": {}, "counters": c}}}}}
    return {"cfg": cfg, "traffic": {"max_model_len": 4096},
            "snap_before": snap({k: 0 for k in counters}),
            "snap_after": snap(counters), "trace": trace,
            "device": {"kind": "TPU v5 lite"}}


COUNTERS = {"fetched_waves": 100, "fetched_lanes_live": 25000,
            "fetched_lanes_padded": 600,
            "fetched_positions_valid": 25000 * 2000,
            "fetched_rows_global": 2 * 25000 * 2000, "fetched_rows_window": 0,
            "expert_pairs_local": 100 * 5 * 750,
            "expert_pairs_busiest": 100 * 5 * 24,
            "experts_touched": 100 * 5 * 63}


@pytest.mark.parametrize("name,want", [
    ("expert_rows_per_expert.obs", 750 / 64),
    ("expert_imbalance.obs", 24 * 64 / 750),
    ("experts_touched_share.itl", 100 * 63 / 64),
    ("arena_live_share.itl", 100 * 250 * 2000 / (256 * 4096)),
])
def test_accepted_counter_readers_take_the_cell(name, want):
    from run import load_reader

    got = load_reader(name)(_ctx(_config(), COUNTERS))
    assert abs(got - want) < 1e-9 * max(1, want)


def test_the_kernels_shares_multiply_by_the_calls_they_found():
    """On paper: every call at twice its least time reads 50%, the piece's
    events of the same name left out; a program without the kernels (the
    parent) and a family without them read nothing and raise nothing."""
    import roofline
    from run import load_reader
    from traffic import load_json

    cfg = _config()
    peaks = roofline.peaks_for("TPU v5 lite")
    state = roofline.min_seconds(*fam.ssm_update(cfg, 250), peaks)[0]
    up = roofline.min_seconds(*fam.expert_ffn(cfg, 750, 63, "up"), peaks)[0]
    down = roofline.min_seconds(*fam.expert_ffn(cfg, 750, 63, "down"),
                                peaks)[0]
    attn = roofline.min_seconds(*fam.decode_attention(cfg, 250, 2000),
                                peaks)[0]
    table = {"jit_decode": {
        "ssd_wave_update_f32_6_257_32_128_128_": [2 * state * 600, 600],
        "grouped_matmul_f32_2496_1856_": [2 * up * 500, 500],
        "grouped_matmul_f32_2496_2688_": [2 * down * 500, 500],
        "grouped_matmul_f32_1216_1856_": [9.0, 40],      # a smaller bucket
        "decode_wave_attention_bf16_2_257_4096_256_": [2 * attn * 200, 200],
        "fusion_f32_256_2688_": [0.5, 4500]},
        "jit_prefill": {"grouped_matmul_f32_7104_1856_": [3.0, 50],
                        "ssd_wave_update_f32_6_257_32_128_128_": [1.0, 6]}}
    trace = {"window_s": 4.0, "program_ops": table,
             "modules": {"jit_decode": {"count": 100}}}
    ctx = _ctx(cfg, COUNTERS, trace)
    for name in ("ssm_state_roofline.itl", "expert_mlp_roofline.itl",
                 "decode_attn_roofline.itl"):
        assert abs(load_reader(name)(ctx) - 50.0) < 1e-6, name
    assert abs(load_reader("state_bytes_share.obs")(ctx)
               - 100 * 6 * 4194304 / (6 * 4194304 + 2000 * 2 * 1024)) < 1e-9
    parent = _ctx(cfg, COUNTERS, {**trace, "program_ops": {
        "jit_decode": {"fusion_f32_": [0.2, 150]}}})
    bare = dict(ctx, snap_before=None, snap_after=None, trace=None)
    other = dict(ctx, cfg=load_json(os.path.join(
        BENCH, "configs", "kimi_linear.json")))
    for name in ("ssm_state_roofline.itl", "expert_mlp_roofline.itl"):
        for c in (parent, bare, other):
            assert load_reader(name)(c) is None, name


def test_the_reference_follows_a_record_and_says_how_far_it_flipped():
    be = backend(dtype="float32")
    ids = ids_of(24, seed=8)
    _, own, flips = reference(be, ids)
    assert (flips == 0).all()
    forced = np.array(own)
    # Make position 5 of the first expert layer choose another expert.
    other = next(e for e in range(8) if e not in forced[0, 5])
    forced[0, 5, 0] = other
    _, chosen, flips = reference(be, ids, follow=forced)
    assert other in chosen[0, 5] and flips[5] > 0
    assert (np.delete(flips, 5) == 0).all()


def _judged(**fault):
    """``judge`` on one hand-made stream whose served logits are the
    reference's, moved by a fault."""
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(4, 20))
    toks = rows.argmax(-1).tolist()
    served = np.concatenate([rows[np.arange(4), toks][:, None],
                             rows[:, :8]], axis=1).astype(np.float32)
    served = served + np.float32(fault.get("offset", 0.0))
    if "one" in fault:
        served[2, 3] += fault["one"]
    rec = np.zeros((3 + 4 - 1, 1 + 9), np.int32)
    rec[2:, 1:] = served.view(np.int32)
    flips = np.zeros(3 + 4 - 1)
    flips[1] = fault.get("flip", 0.0)
    probe = {"prompts": [[1, 2, 3]], "max_tokens": 4,
             "concurrent": [toks], "solo": [toks],
             "concurrent_record": [rec.tolist()],
             "solo_record": [rec.tolist()]}
    return kimi.judge(probe, lambda p, e, w: (rows, flips), 1,
                      margin=fam.MARGIN, logit_rms_alone=fam.LOGIT_RMS_ALONE,
                      logit_rms_together=fam.LOGIT_RMS_TOGETHER,
                      logit_max=fam.LOGIT_MAX, tie=fam.TIE)


@pytest.mark.parametrize("fault,ok", [
    ({}, True),
    ({"offset": 2 * fam.LOGIT_RMS_TOGETHER}, False),
    ({"one": 2 * fam.LOGIT_MAX}, False),
    ({"flip": 2 * fam.TIE}, False),
    ({"flip": fam.TIE / 2}, True),
])
def test_the_comparison_fails_by_each_of_its_limits(fault, ok):
    assert _judged(**fault)["ok"] is ok


@pytest.mark.parametrize("which", ["bf16_state", "e4m3", "rotated",
                                   "norm_all", "no_skip"])
def test_a_control_is_the_served_backend_with_one_thing_wrong(which):
    """Each control serves other logits than the backend it derives from, on
    the same weights, by more than the float32 tolerance; the reference it is
    judged by stays the published model."""
    import nemotron_h_controls as controls

    kw = dict(seed=5, max_seq_len=SEQ, piece=PIECE, chunk=8,
              dtype="bfloat16" if which in ("bf16_state", "e4m3")
              else "float32")
    served = NemotronHBackend(**kw)
    wrong = controls.CONTROLS[which](**kw)
    assert isinstance(wrong, NemotronHBackend)
    ids = ids_of(30, seed=9)
    a, _ = Served(served).walk(ids, 21)
    b, _ = Served(wrong).walk(ids, 21)
    assert np.abs(a - b).max() > (1e-3 if which == "bf16_state" else 1e-2)
    if which == "bf16_state":
        assert jax.eval_shape(lambda: wrong.init_arena(2))["s"].dtype \
            == jnp.bfloat16


def test_a_launch_of_another_model_imports_none_of_it():
    code = ("import sys; from client_tpu.models import build_repository; "
            "build_repository(['simple']); "
            "print(any(m in sys.modules for m in "
            "('client_tpu.models.nemotron_h', 'client_tpu.ops.ssd')))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().endswith("False"), out.stdout + out.stderr
    from client_tpu.models import model_names

    assert "nemotron_h" in model_names()
