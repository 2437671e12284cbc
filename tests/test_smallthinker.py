"""The decoder with window and global layers (models/smallthinker.py: a ring
beside whole-context rows in one arena, grouped-query heads, ReGLU experts
routed before attention) at a tiny preset on the CPU, seeded weights, Pallas
interpreted: the served path (pieces, then single-step waves through the ring
and the global rows) against the plain reference's full forward pass on
logits; what a ring asks of a slot's life (a last piece that keeps the rows
behind it, a reused slot, any cut into pieces); the expert layer against a
loop over experts; the shares of an expert-parallel group; the scheduler's
counters and a stream's record; the benchmark family's arithmetic, readers
and configuration file."""

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
from client_tpu.models.smallthinker import SmallThinkerBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402

fam = family.load("smallthinker")
kimi = family.load("kimi_linear")
# A window of 8, pieces of 4 and contexts to 40: the ring wraps five times.
SEQ, WINDOW, PIECE, N = 48, 8, 4, 40
# float32 weights, cache and matmuls against the float32 reference: what is
# left is the order of the sums (logits of magnitude 3).
TOL_F32 = 2e-4
# bfloat16 matmuls and rows against the float32 reference with the routing
# followed, at the tiny preset (logits of magnitude 3).
TOL_BF16 = 0.15


def backend(**kw):
    return SmallThinkerBackend(**{"seed": 5, "max_seq_len": SEQ,
                                  "window": WINDOW, "piece": PIECE, **kw})


def f32_params(be):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  be._init_params())


def ids_of(n=N, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def words_of(be, routes):
    """Choices ``[layers, n, top_k]`` -> the record's words ``[n, layers,
    held_words]`` (numpy's form of ``held_mask``)."""
    e = np.asarray(routes, np.int64) - be.first_expert
    out = np.zeros((*e.shape[:2], be.held_words), np.int64)
    for w in range(be.held_words):
        held = (e >= 32 * w) & (e < min(32 * w + 32, be.experts_held))
        out[..., w] = np.where(held, 1 << np.clip(e - 32 * w, 0, 31),
                               0).sum(-1)
    return out.transpose(1, 0, 2).astype(np.uint32).view(np.int32)


def reference(be, ids, follow=None):
    """``follow``: the program's choices, followed as a record's words."""
    with jax.default_matmul_precision("highest"):
        logits, chosen, flips = fam.backend_forward(
            f32_params(be), be, ids, len(ids),
            follow=None if follow is None else words_of(be, follow))
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

    def wave(self, token, length, slot=1):
        """One wave of two lanes, the other padded onto the junk slot."""
        self.arena = {**self.arena,
                      "tok": self.arena["tok"].at[slot].set(int(token))}
        self.arena, x = self.hidden(
            self.params, self.arena, np.asarray([slot, 3], np.int32),
            np.asarray([length, 0], np.int32))
        return (np.asarray(self.be._logits(self.params, x))[:1],
                np.stack([np.asarray(r)[:1] for r in x["route"]]))

    def walk(self, ids, n_prompt, slot=1):
        logits, routes = self.prefill(ids[:n_prompt], slot)
        logits, routes = [logits], [routes]
        for t in range(n_prompt, len(ids)):
            row, route = self.wave(ids[t], t, slot)
            logits.append(row)
            routes.append(route)
        return np.concatenate(logits), np.concatenate(routes, axis=1)


# -- the served path against the plain reference, on logits -------------------

# A prompt under a piece (every later position a wave: the ring's first
# overwrite at 8 and four more wraps), one that ends inside a piece with the
# ring wrapped inside prefill, one of whole pieces; a window that fills its
# ring and two that do not (7 and 6 keys in a ring of 8 rows).
@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
@pytest.mark.parametrize("window", [8, 7, 6])
@pytest.mark.parametrize("n_prompt", [3, 18, 28])
def test_float32_pieces_then_waves_match_the_full_forward_pass(
        attn_impl, window, n_prompt):
    """float32 weights, caches and matmuls: pieces of 4, then single-step
    waves through the ring and the global rows, give the logits of the
    reference's full forward pass (a dense band mask, nothing cached) at
    every position to 40, and the same experts."""
    be = backend(dtype="float32", attn_impl=attn_impl, window=window)
    ids = ids_of()
    got, routes = Served(be).walk(ids, n_prompt)
    want, chosen, _ = reference(be, ids)
    assert np.abs(got - want).max() < TOL_F32
    assert (np.sort(routes, -1) == np.sort(chosen, -1)).all()


def test_bfloat16_pieces_then_waves_match_the_reference_that_follows():
    be = backend(attn_impl="fused")
    ids = ids_of()
    got, routes = Served(be).walk(ids, 18)
    want, _, flips = reference(be, ids, follow=routes)
    assert np.abs(got - want).max() < TOL_BF16
    assert flips.max() < 0.05     # a flip lies at the edge


def test_the_reference_computes_a_twin_against_its_prompts_keys():
    """The reference's one economy (``forward``'s ``keep`` and ``prompt``):
    a second stream over the same prompt, computed against the first's keys
    and values, reads what its own full pass reads."""
    be = backend(dtype="float32")
    params, ids = f32_params(be), ids_of()
    other = np.concatenate([ids[:26], ids_of(14, seed=9)])
    with jax.default_matmul_precision("highest"):
        _, _, _, kept = fam.backend_forward(params, be, ids, 1, keep=26)
        got, chosen, _ = fam.backend_forward(params, be, other[26:], 14,
                                             prompt=kept)
        want, whole, _ = fam.backend_forward(params, be, other, 14)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert (chosen == whole[:, 26:]).all()
    # Two continuations side by side, each under a window of its own: the
    # first is the pass above, the second that pass under one key fewer.
    with jax.default_matmul_precision("highest"):
        both, _, _ = fam.backend_forward(
            params, be, np.tile(other[26:], 2), 28, prompt=kept,
            branches=[(14, WINDOW), (14, WINDOW - 1)])
        fewer, _, _ = fam.backend_forward(
            params, backend(dtype="float32", window=WINDOW - 1), other[26:],
            14, prompt=kept)
    assert np.abs(np.asarray(both[:14]) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(both[14:]) - np.asarray(fewer)).max() < 1e-5
    assert np.abs(np.asarray(fewer) - np.asarray(want)).max() > 1e-3


def test_the_flash_piece_is_the_einsum_piece():
    """Heads of a whole lane tile (the flash kernel's grouped-query heads),
    a ring that wraps inside prefill: the piece's band through the kernel,
    interpreted, equals the dense band."""
    kw = dict(dtype="float32", head_dim=128, n_heads=2, n_kv_heads=1,
              piece=8, window=16, max_seq_len=48, n_layers=2,
              window_layout=(0, 1))
    ids = ids_of(44)
    got, _ = Served(backend(attention_impl="flash", **kw)).prefill(ids)
    want, _ = Served(backend(**kw)).prefill(ids)
    assert np.abs(got - want).max() < TOL_F32


def test_the_layers_are_of_two_kinds_with_leaves_of_their_own_length():
    be = backend(n_layers=8)
    assert be.layer_kinds == ("rows", "ring", "ring", "ring") * 2
    assert be.rotate == {"rows": False, "ring": True}
    arena = jax.eval_shape(lambda: be.init_arena(3))
    assert arena["kg"].shape == arena["vg"].shape == (2, 4, SEQ, 32)
    assert arena["kw"].shape == arena["vw"].shape == (6, 4, WINDOW, 32)
    assert [be._layer_kind(li) for li in (0, 1, 4, 7)] == [
        ("rows", 0), ("ring", 0), ("rows", 1), ("ring", 5)]
    # A window of 7 keys keeps a ring of whole pieces and masks a row more.
    odd = backend(window=7)
    assert (odd.ring_rows, odd.ring_window) == (8, 7)
    assert be.ring_window is None
    with pytest.raises(ValueError, match="rotate alike"):
        backend(rope_layout=(0, 1, 0, 1))
    # (ring rows, whole-context rows, past the window) of a step at n.
    assert be.cache_rows_by_kind(5) == (6 * 5, 2 * 5, 0)
    assert be.cache_rows_by_kind(8) == (6 * 7, 2 * 8, 0)
    assert be.cache_rows_by_kind(30) == (6 * 7, 2 * 30, 1)


# -- what a ring asks of a slot's life -------------------------------------------

@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_a_slot_reused_by_a_second_stream_equals_a_fresh_slot(attn_impl):
    """A long stream fills the slot's ring and rows; a short one that takes
    the slot after it reads none of them."""
    be = backend(attn_impl=attn_impl)
    served = Served(be)
    served.walk(ids_of(seed=1), 30)
    ids = ids_of(14, seed=2)
    got, _ = served.walk(ids, 6)
    want, _ = Served(be).walk(ids, 6)
    np.testing.assert_array_equal(got, want)


def test_a_prompts_last_piece_keeps_the_ring_rows_behind_it():
    """A last piece of one valid position writes one ring row: the other
    three of its block hold positions the next waves still read.  A global
    layer's rows behind a prompt are masked by its length, and written."""
    be = backend(dtype="float32")
    served = Served(be)
    ids = ids_of(25)
    served.prefill(ids[:24])
    ring = np.asarray(served.arena["kw"][:, 1])
    buf = np.zeros((1, PIECE), np.int32)
    buf[0, 0] = ids[24]
    served.arena, _, _ = served.piece(
        served.params, served.arena, np.asarray([1], np.int32), buf,
        np.asarray([1], np.int32), np.asarray([24], np.int32))
    changed = (ring != np.asarray(served.arena["kw"][:, 1])).any(-1)
    assert [np.nonzero(c)[0].tolist() for c in changed] == [[24 % WINDOW]] * 3


# -- two prompts' pieces in one program -------------------------------------------

# Two lanes of one piece call: (whole pieces prefilled before the call,
# positions the call holds) a lane, each lane's prompt its own.  A ring is two
# pieces in both geometries below, so a lane with three or more pieces before
# it reads its ring rolled, and one that holds fewer positions than a piece is
# at its prompt's last piece: the rows behind its valid ones stay.
TWO_LANES = {
    "a_first_piece_beside_one_past_the_window": [(0, 4), (3, 4)],
    "a_last_piece_beside_one_that_goes_on": [(5, 1), (1, 4)],
    "both_past_the_window_at_different_starts": [(3, 4), (5, 3)],
    "a_ring_just_filled_beside_a_first_piece_cut_short": [(2, 4), (0, 2)],
}
# The tiny preset (dense scores, either decode path's expert products) and
# heads of a whole lane tile through the flash kernel, interpreted.
GEOMETRIES = {
    "reference": dict(attn_impl="reference"),
    "fused": dict(attn_impl="fused"),
    "flash": dict(attention_impl="flash", head_dim=128, n_heads=2,
                  n_kv_heads=1, piece=8, window=16, n_layers=2,
                  window_layout=(0, 1)),
}
LEAVES = ("kw", "vw", "kg", "vg")


def _piece_args(lanes, slots, piece):
    """A piece call's (rows, ids, lens, starts) for ``lanes`` [(prompt,
    positions before, positions held)]."""
    buf = np.zeros((len(lanes), piece), np.int32)
    for i, (ids, before, held) in enumerate(lanes):
        buf[i, :held] = ids[before:before + held]
    return (np.asarray(slots, np.int32), buf,
            np.asarray([held for _, _, held in lanes], np.int32),
            np.asarray([before for _, before, _ in lanes], np.int32))


def _pair_and_solo(be, lanes):
    """``lanes`` [(prompt, positions before, positions held)] through one
    two-lane call (``pair``) and through two one-lane calls (``solo``), both
    after the same one-lane pieces before.  -> (pair, solo, per lane (x,
    routes) of each)."""
    pair, solo = Served(be), Served(be)
    for srv in (pair, solo):
        for slot, (ids, before, _) in enumerate(lanes):
            if before:
                srv.prefill(ids[:before], slot=slot)
    pair.arena, x, routes = pair.piece(
        pair.params, pair.arena, *_piece_args(lanes, [0, 1], be.piece))
    x, routes = np.asarray(x), np.asarray(routes)
    got = [(x[i * be.piece:(i + 1) * be.piece],
            routes[:, i * be.piece:(i + 1) * be.piece]) for i in range(2)]
    want = []
    for slot, lane in enumerate(lanes):
        solo.arena, x, routes = solo.piece(
            solo.params, solo.arena, *_piece_args([lane], [slot], be.piece))
        want.append((np.asarray(x), np.asarray(routes)))
    return pair, solo, got, want


def _two_lanes(dtype, geometry, case):
    """A case of ``TWO_LANES`` in a geometry.  -> (``_pair_and_solo``'s
    four, the lanes)."""
    be = backend(dtype=dtype, **GEOMETRIES[geometry])
    lanes = [(ids_of(SEQ, seed=30 + i), pieces * be.piece, held)
             for i, (pieces, held) in enumerate(TWO_LANES[case])]
    return (*_pair_and_solo(be, lanes), lanes)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("case", sorted(TWO_LANES))
def test_two_lanes_of_a_piece_are_the_lanes_alone_bit_for_bit(geometry, case):
    """float32: two prompts' pieces in one program (the projections and the
    expert layer over both lanes' positions at once, the attention a lane at
    a time, each from its own slot and by its own ``start``'s branch, in
    window and global layers alike) leave every slot's rings and rows, and
    give every valid position's activations and choices, exactly as the same
    two pieces do one lane at a time."""
    pair, solo, got, want, lanes = _two_lanes("float32", geometry, case)
    for leaf in LEAVES:
        assert np.array_equal(np.asarray(pair.arena[leaf][:, :3]),
                              np.asarray(solo.arena[leaf][:, :3])), leaf
    for (x, routes), (x1, routes1), (_, _, held) in zip(got, want, lanes):
        assert np.array_equal(x[:held], x1[:held])
        assert np.array_equal(routes[:, :held], routes1[:, :held])


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_two_lanes_in_the_wider_tile_are_the_lanes_alone(attn_impl):
    """Two lanes whose pairs give an expert a mean share of 96 rows are sorted
    in tiles of 128 where one lane's 48 go in tiles of 64 (``_piece_tile``,
    the cell's shares at 512 positions a lane): the same choices, and the
    same rings, rows and activations to the order of float32's sums (a
    product over 96 rows is blocked otherwise than one over 48), one lane
    past the window, the other at a first piece cut short."""
    be = backend(dtype="float32", attn_impl=attn_impl, piece=48, window=96,
                 max_seq_len=288, n_experts=2, top_k=2)
    assert (be._piece_tile(48), be._piece_tile(96)) == (64, 128)
    lanes = [(ids_of(288, seed=60), 144, 48), (ids_of(288, seed=61), 0, 20)]
    pair, solo, got, want = _pair_and_solo(be, lanes)
    for (x, routes), (x1, routes1), (_, _, held) in zip(got, want, lanes):
        assert np.abs(x[:held] - x1[:held]).max() < TOL_F32
        assert np.array_equal(routes[:, :held], routes1[:, :held])
    for leaf in LEAVES:
        assert np.abs(np.asarray(pair.arena[leaf][:, :3])
                      - np.asarray(solo.arena[leaf][:, :3])).max() < TOL_F32


@pytest.mark.parametrize("geometry", ["fused", "flash"])
@pytest.mark.parametrize("case", sorted(TWO_LANES))
def test_two_bfloat16_lanes_are_the_lanes_alone_within_the_files_limits(
        geometry, case):
    """bfloat16: a matmul over twice the rows may round an activation the
    other way, so the two forms agree to the file's limit against the
    reference, on the logits of every valid position and on the rows they
    leave, and not to the bit."""
    pair, solo, got, want, lanes = _two_lanes("bfloat16", geometry, case)
    for leaf in LEAVES:
        a, b = (np.asarray(srv.arena[leaf][:, :3], np.float32)
                for srv in (pair, solo))
        assert np.abs(a - b).max() < TOL_BF16, leaf
    for (x, _), (x1, _), (_, _, held) in zip(got, want, lanes):
        logits, logits1 = (np.asarray(pair.be._logits(pair.params, t[:held]))
                           for t in (x, x1))
        assert np.abs(logits - logits1).max() < TOL_BF16


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_last_piece_in_a_pair_keeps_the_ring_rows_behind_it(geometry):
    """The pair's lane at its prompt's last piece, one valid position past
    the window, writes one row of each ring; the lane beside it writes its
    whole piece into its own slot and nothing into the first's."""
    pair, _, _, _, lanes = _two_lanes(
        "float32", geometry, "a_last_piece_beside_one_that_goes_on")
    be = pair.be
    alone = Served(be)
    alone.prefill(lanes[0][0][:lanes[0][1]], slot=0)
    changed = (np.asarray(alone.arena["kw"][:, 0])
               != np.asarray(pair.arena["kw"][:, 0])).any(-1)
    assert [np.nonzero(c)[0].tolist() for c in changed] == [
        [lanes[0][1] % be.ring_rows]] * be.layer_kinds.count("ring")


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_padded_second_lane_leaves_every_live_slot_untouched(geometry):
    """A two-lane call whose second lane holds no prompt (the junk slot, one
    position, as a scheduler would pad it): slots 0 and 2 hold live streams
    and stand bit for bit, and slot 1's piece, past the window, is the
    one-lane program's."""
    be = backend(dtype="float32", **GEOMETRIES[geometry])
    pair, solo = Served(be), Served(be)
    ids, before = ids_of(SEQ, seed=41), 3 * be.piece
    for srv in (pair, solo):
        srv.walk(ids_of(30, seed=42), 20, slot=0)
        srv.walk(ids_of(25, seed=43), 18, slot=2)
        srv.prefill(ids[:before], slot=1)
    rows, buf, lens, starts = _piece_args([(ids, before, be.piece)], [1],
                                          be.piece)
    pair.arena, x, _ = pair.piece(
        pair.params, pair.arena, np.asarray([1, 3], np.int32),
        np.concatenate([buf, np.zeros_like(buf)]),
        np.asarray([be.piece, 1], np.int32),
        np.asarray([before, 0], np.int32))
    solo.arena, x1, _ = solo.piece(solo.params, solo.arena, rows, buf, lens,
                                   starts)
    assert np.array_equal(np.asarray(x)[:be.piece], np.asarray(x1))
    for leaf in LEAVES:
        assert np.array_equal(np.asarray(pair.arena[leaf][:, :3]),
                              np.asarray(solo.arena[leaf][:, :3])), leaf


# The whole prefill program's two lanes: (whole pieces before, positions
# held, the lane's piece is its prompt's last) a lane.
TWO_ENDS = {
    "both_end": [(0, 3, 1), (3, 4, 1)],
    "one_ends_past_the_window_beside_one_that_goes_on": [(4, 2, 1),
                                                         (0, 4, 0)],
    "neither_ends": [(1, 4, 0), (3, 4, 0)],
}


@pytest.mark.parametrize("sample", [False, True])
@pytest.mark.parametrize("case", sorted(TWO_ENDS))
def test_two_lanes_tokens_and_record_rows_are_the_lanes_alone(case, sample):
    """The whole prefill program: a token a lane from its own last valid
    position into its own slot, and the record laid ``[L | L x piece x
    stream_record]`` as the scheduler cuts it, lane after lane: each lane's
    words (64 experts' bits in two words a layer) and, where a lane ends,
    its logits' bits in its last valid row and nowhere else."""
    be = backend(dtype="float32", record=True)
    step = jax.jit(be.prefill_fn(), static_argnums=be.prefill_static_argnums)
    params = be.place_params(be._init_params())
    lanes = [(ids_of(SEQ, seed=50 + i), pieces * PIECE, held)
             for i, (pieces, held, _) in enumerate(TWO_ENDS[case])]
    ends = [end for _, _, end in TWO_ENDS[case]]
    width = PIECE * be.stream_record

    def run(arena, which, slots, ends):
        rows, buf, lens, starts = _piece_args(which, slots, PIECE)
        n = len(which)
        arena, out = step(
            params, arena, rows, buf, lens,
            np.asarray(slots, np.int32) + 5,          # a seed a slot
            np.full(n, 0.9 if sample else 0.0, np.float32),
            np.full(n, 8, np.int32), np.full(n, 0.95, np.float32), sample,
            starts, np.asarray(ends, np.int32))
        out = np.asarray(out)
        assert out.shape == (n * (1 + width),)
        return (arena, out[:n],
                out[n:].reshape(n, PIECE, be.stream_record))

    def before(slots):
        arena = be.init_arena(3)
        for (ids, upto, _), slot in zip(lanes, slots):
            for st in range(0, upto, PIECE):
                arena, _, _ = run(arena, [(ids, st, PIECE)], [slot], [0])
        return arena

    slots = [2, 0]
    arena, tokens, record = run(before(slots), lanes, slots, ends)
    arena1 = before(slots)
    for i, (lane, slot) in enumerate(zip(lanes, slots)):
        # Alone a lane runs its head where the pair ran it for the lane.
        arena1, tokens1, record1 = run(arena1, [lane], [slot],
                                       [int(any(ends))])
        held = lane[2]
        assert tokens[i] == tokens1[0]
        assert np.array_equal(record[i, :held], record1[0, :held])
        assert (record[i, :held - 1, -9:] == 0).all()
        assert record[i, held - 1, -9:].any() == any(ends)
    assert np.array_equal(np.asarray(arena["tok"]), np.asarray(arena1["tok"]))
    for leaf in LEAVES:
        assert np.array_equal(np.asarray(arena[leaf][:, :3]),
                              np.asarray(arena1[leaf][:, :3])), leaf


@pytest.mark.parametrize("piece", [4, 8, 24])
def test_a_prompt_cut_into_pieces_of_any_size_gives_one_cache(piece):
    ids = ids_of(24)
    got, _ = Served(backend(dtype="float32", piece=piece,
                            window=24)).walk(np.concatenate(
                                [ids, ids_of(6, seed=3)]), 24)
    want, _ = Served(backend(dtype="float32", piece=12,
                             window=24)).walk(np.concatenate(
                                 [ids, ids_of(6, seed=3)]), 24)
    assert np.abs(got - want).max() < TOL_F32


# -- the expert layer ------------------------------------------------------------

def _loop_over_experts(be, lp, routed, h2):
    """numpy: softmax over the top_k router logits, ReGLU experts."""
    logits = routed @ np.asarray(lp["router"], np.float32)
    order = np.argsort(-logits, -1, kind="stable")[:, :be.top_k]
    top = np.take_along_axis(logits, order, -1)
    w = np.exp(top - top.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    egu = np.asarray(lp["egu"], np.float32)
    ed = np.asarray(lp["ed"], np.float32)
    f = egu.shape[-1] // 2
    y = np.zeros_like(h2)
    for t in range(len(h2)):
        for e, we in zip(order[t], w[t]):
            if be.first_expert <= e < be.first_expert + be.experts_held:
                m = egu[e - be.first_expert]
                y[t] += we * (np.maximum(h2[t] @ m[:, :f], 0)
                              * (h2[t] @ m[:, f:])) @ ed[e - be.first_expert]
    return y, order


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_relu_gated_experts_under_a_softmax_gate_equal_a_loop(attn_impl):
    """The shared expert layer (models/experts.py) with this model's
    parameters: the gate a softmax over the chosen logits, the activation
    ReLU, the routing handed in from the attention's input."""
    be = backend(dtype="float32", attn_impl=attn_impl, n_experts=16, top_k=3)
    lp = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a, np.float32)),
        be._init_params()["layers"][0])
    rng = np.random.default_rng(4)
    routed = rng.standard_normal((11, 64)).astype(np.float32)
    h2 = rng.standard_normal((11, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y, counts, top_i = be._experts(
            lp, jnp.asarray(h2), jnp.ones(11, bool), 16,
            routing=be.route(lp, jnp.asarray(routed)))
    want, order = _loop_over_experts(be, lp, routed, h2)
    assert np.abs(np.asarray(y) - want).max() < 1e-4
    assert (np.sort(np.asarray(top_i), -1) == np.sort(order, -1)).all()
    assert int(counts[0]) == 33          # every pair is held, none dropped


def test_the_shares_of_a_group_add_up_to_the_whole_layer():
    """Four shares of four experts each (models/experts.py ``first_expert``,
    ``experts_held``): the parts add up to what the backend that holds all
    sixteen gives."""
    kw = dict(dtype="float32", n_experts=16, top_k=3)
    whole = backend(**kw)
    rng = np.random.default_rng(6)
    routed = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    h2 = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    live = jnp.ones(9, bool)

    def part(be):
        lp = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, np.float32)),
            be._init_params()["layers"][1])
        return np.asarray(be._experts(lp, h2, live, 16,
                                      routing=be.route(lp, routed))[0])

    total = sum(part(backend(experts_held=4, first_expert=f, **kw))
                for f in (0, 4, 8, 12))
    assert np.abs(total - part(whole)).max() < 1e-4


def test_the_record_has_a_bit_for_each_of_64_experts():
    be = backend(n_experts=64, top_k=6, record=True)
    assert be.held_words == 2
    assert be.stream_record == be.n_layers * 2 + 1 + 8
    top_i = jnp.asarray([[0, 31, 32, 40, 63, 5]], jnp.int32)
    words = np.asarray(be._words(top_i)).view(np.uint32)[0]
    assert words.tolist() == [(1 << 0) | (1 << 31) | (1 << 5),
                              (1 << 0) | (1 << 8) | (1 << 31)]


# -- through the scheduler -------------------------------------------------------

def stream(engine, prompt, max_tokens, model, record=False):
    """-> a join giving the tokens, or (tokens, record) where asked."""
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


# (prompt length, tokens): under a piece, past the window inside prefill,
# crossing the window while decoding; slots are reused.
PLAN = [(3, 6), (21, 5), (6, 7), (30, 4)]


@pytest.fixture(scope="module", params=["reference", "fused"])
def served(request):
    name = f"st_{request.param}"
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
    after = counters(engine, name)
    alone = [stream(engine, p, m, name, record=True)()
             for p, (_, m) in zip(prompts, PLAN)]
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
        for p, (_, m), rec in zip(prompts, PLAN, records):
            assert rec.shape == (len(p) + m - 1, be.stream_record)
            words, logits = kimi.record_columns(
                rec, be.n_layers * be.held_words)
            # top_k of the held experts a layer, every one of them held.
            bits = np.unpackbits(words.astype(np.uint32).view(np.uint8),
                                 axis=-1).sum(-1)
            assert (bits == be.n_layers * be.top_k).all()
            assert (logits[len(p) - 1:, 0] >= logits[len(p) - 1:, 1:].max(-1)
                    ).all()                            # greedy: the row's best

    def test_the_reference_accepts_every_token(self, served):
        (be, prompts, together, alone, _, _, rec_together, rec_alone,
         _) = served
        params = f32_params(be)

        def rows_fn(prompt, emitted, words):
            seq = np.asarray(prompt + emitted, np.int32)
            with jax.default_matmul_precision("highest"):
                logits, _, flips = fam.backend_forward(
                    params, be, seq[:-1], len(emitted),
                    follow=np.asarray(words).reshape(-1, be.n_layers,
                                                     be.held_words))
            return logits, flips

        for i, (p, (_, m)) in enumerate(zip(prompts, PLAN)):
            one = {"prompts": [p], "max_tokens": m,
                   "concurrent": [together[i]], "solo": [alone[i]],
                   "concurrent_record": [rec_together[i]],
                   "solo_record": [rec_alone[i]]}
            verdict = kimi.judge(one, rows_fn, be.n_layers * be.held_words,
                                 margin=TOL_BF16,
                                 logit_rms_alone=TOL_BF16 / 3,
                                 logit_rms_together=TOL_BF16 / 3,
                                 logit_max=TOL_BF16, tie=0.05)
            assert verdict["ok"], verdict
            assert verdict["tokens_checked"] == 2 * m

    def test_the_cells_comparison_judges_the_streams_and_the_windows_edge(
            self, served):
        """``check`` whole, as the harness calls it (its limits are the
        published widths', so only what it computes is held here): every
        stream judged, a twin computed against its prompt's keys, and the
        served logits nearer the published window's than a window of one key
        fewer or one more (one key in 8 at this preset)."""
        (be, prompts, together, alone, _, _, rec_together, rec_alone,
         _) = served
        probe = {"prompts": prompts, "max_tokens": None,
                 "concurrent": together, "solo": alone,
                 "concurrent_record": [r.tolist() for r in rec_together],
                 "solo_record": [r.tolist() for r in rec_alone]}
        for i, (_, m) in enumerate(PLAN):    # (a length each: one at a time)
            one = {k: (v[i:i + 1] if isinstance(v, list) else m)
                   for k, v in probe.items()}
            with jax.default_matmul_precision("highest"):
                verdict = fam.check(f32_params(be), one, be)
            assert verdict["tokens_checked"] == 2 * m
            assert verdict["logit_worst_error"] < TOL_BF16
            assert max(verdict["window_lean_fewer"],
                       verdict["window_lean_more"]) < fam.WINDOW_LEAN

    def test_rows_by_kind_reach_the_counters(self, served):
        """What the backend declares (``cache_rows_by_kind``), summed by the
        scheduler over every fetched wave's live lanes."""
        be, _, _, _, before, after, *_ = served
        c = {k: after[k] - before[k] for k in after}
        want = np.zeros(3, np.int64)
        for n, m in PLAN:
            for step in range(m - 1):    # the first token is the prefill's
                want += be.cache_rows_by_kind(n + step)
        assert [c["fetched_rows_window"], c["fetched_rows_global"],
                c["fetched_lanes_past_window"]] == want.tolist()
        assert c["fetched_positions_valid"] == sum(
            n + step for n, m in PLAN for step in range(m - 1))
        assert c["fetched_lanes_live"] == sum(m - 1 for _, m in PLAN)
        assert c["expert_pairs_local"] == c["fetched_lanes_live"] \
            * be.n_layers * be.top_k
        assert c["fetched_rows_exact"] == c["fetched_rows_summary"] == 0

    @pytest.mark.parametrize("attn_impl", ["reference", "fused"])
    def test_a_piece_program_holds_the_prompts_that_wait(self, attn_impl):
        """``prefill_pieces`` counts a lane's piece each and the span
        ``gen.prefill_dispatch`` a program each: a prompt alone in line goes
        through the one-lane program piece by piece, two that wait together
        go two to a program while both have pieces left (the longer one's
        last pieces past the window, alone), every prompt gets the tokens it
        gets alone, and nothing compiles after the warm-up, which ran both
        lane counts."""
        from client_tpu.engine.generative import _WarmupReq

        name = f"st_lanes_{attn_impl}"
        repo = ModelRepository()
        repo.register_backend(backend(name=name, attn_impl=attn_impl,
                                      dtype="float32", max_streams=2))
        engine = TpuEngine(repo)
        sched = engine._schedulers[name]
        sched.warmup()

        def snap():
            c = counters(engine, name)
            g = engine.profile_snapshot(model=name)
            return (c["prefill_pieces"],
                    g["models"][f"{name}:1"]["generative"]["spans"][
                        spans.GEN_PREFILL_DISPATCH]["count"],
                    g["compiles"]["count"])

        def held(prompts):
            """One admit takes them all: the worker is held inside a
            warm-up sentinel while they queue."""
            counters(engine, name)            # parked in its blocking wait
            gate, real = threading.Event(), sched._precompile
            sched._precompile = lambda: gate.wait(60)
            hold = _WarmupReq()
            try:
                sched.queue.put(hold)
                deadline = time.monotonic() + 10
                while sched._rec.open is sched._rec.span[spans.S_IDLE] \
                        and time.monotonic() < deadline:
                    time.sleep(0.001)
                joins = [stream(engine, p, 4, name) for p in prompts]
            finally:
                gate.set()
                sched._precompile = real
            assert hold.done.wait(60)
            return [j() for j in joins]

        try:
            prompts = [ids_of(n, seed=70 + i).tolist()
                       for i, n in enumerate((18, 6))]    # 5 pieces and 2
            before = snap()
            alone = [stream(engine, p, 4, name)() for p in prompts]
            single = snap()
            assert (single[0] - before[0], single[1] - before[1]) == (7, 7)
            together = held(prompts)
            pair = snap()
            # Programs of 2, 2, 1, 1, 1 lanes.
            assert (pair[0] - single[0], pair[1] - single[1]) == (7, 5)
            assert together == alone
            assert pair[2] == before[2]
        finally:
            engine.shutdown()

    def test_a_backend_without_a_ring_counts_none(self):
        from client_tpu.models.generate import TinyGptBackend

        assert TinyGptBackend.cache_rows_by_kind is None
        assert TinyGptBackend.ring_leaves == ()


# -- the benchmark family ----------------------------------------------------------

def _config():
    from traffic import load_json
    return load_json(os.path.join(BENCH, "configs", "smallthinker_21b.json"))


def test_the_configuration_file_is_the_catalog_row_but_for_its_depth():
    cfg = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] == 8
        else:
            assert cfg[key] == value, key
    # The harness's names for the experts' width and count, beside the
    # published keys.
    assert cfg["moe_intermediate_size"] == cfg["moe_ffn_hidden_size"] == 768
    assert cfg["n_routed_experts"] == cfg["moe_num_primary_experts"] == 64
    from client_tpu.models import experts
    assert cfg["serve"]["expert_tile_rows"] == experts.TILE_M_WAVE
    for key in ("assumed", "departures", "deployment", "memory"):
        assert cfg[key]


def test_the_backend_built_from_the_file_is_the_issues_arena():
    import serve as serve_mod
    cfg = _config()
    be = SmallThinkerBackend(name="s", **serve_mod.backend_kwargs(
        cfg, 7, 16384))
    assert be.layer_kinds == ("rows", "ring", "ring", "ring") * 2
    assert be.rotate == {"rows": False, "ring": True}
    arena = jax.eval_shape(lambda: be.init_arena(be.max_streams))
    assert arena["kw"].shape == arena["vw"].shape == (6, 49, 4096, 512)
    assert arena["kg"].shape == arena["vg"].shape == (2, 49, 16384, 512)
    assert all(arena[k].dtype == jnp.bfloat16 for k in "kw vw kg vg".split())
    assert be.prefill_piece == (512, 2) and be.ring_window is None
    assert (be.router_score, be.expert_act) == ("softmax", "relu")
    total = sum(int(np.prod(w.shape))
                for w in jax.tree_util.tree_leaves(be._init_params()))
    assert 3.96e9 < total < 3.98e9          # the issue's 7.93 GB
    cache = sum(int(np.prod(arena[k].shape)) * 2 for k in "kw vw kg vg".split())
    assert 5.74e9 < cache < 5.77e9          # the issue's 5.75 GB


def test_the_traffic_file_is_the_issues_cell():
    from traffic import load_json
    t = load_json(os.path.join(BENCH, "traffic", "mixed.json"))
    assert (t["loop"], t["clients"], t["cycle_requests"]) == ("closed", 48, 48)
    assert (t["stagger_s"], t["preroll_s"], t["trace_seconds"]) == (24, 36, 4)
    assert t["prompt_len"] == {"dist": "uniform", "min": 256, "max": 12288}
    assert t["output_len"] == {"dist": "loguniform", "min": 512, "max": 2048}
    assert t["max_model_len"] == 16384
    # One short, one of a piece and a bit, one whose decoding crosses the
    # window, one whose prompt wraps the ring inside prefill.
    short, piece, cross, wrap = t["probe_prompt_lens"]
    assert short < 16 and 512 < piece < 1024
    assert cross < 4096 < cross + t["probe_max_tokens"]
    assert wrap > 4096 + 512
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "smallthinker_21b.mixed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker_21b", "mixed", 1)


def test_the_new_readers_and_the_family_arithmetic():
    import check_smallthinker

    cfg = _config()
    assert check_smallthinker.readers(cfg) == 0
    assert check_smallthinker.arithmetic(cfg) == 0


def test_step_arithmetic_by_hand():
    cfg = _config()
    # 48 lanes x 4096 rows x 2 KB, 28 heads x 128 x 4 operations a row.
    flops, nbytes = fam.window_attention(cfg, 48, 4096)
    assert flops == 4 * 48 * 4096 * 28 * 128
    assert nbytes == 2 * 48 * 4097 * 512 * 2
    # 288 pairs over all 64 experts: each matrix once, bfloat16.
    _, up = fam.expert_ffn(cfg, 288, 64, "up")
    _, down = fam.expert_ffn(cfg, 288, 64, "down")
    assert up == 64 * 2 * 2560 * 768 * 2 + 288 * (2560 * 2 + 2 * 768 * 4)
    assert down == 64 * 768 * 2560 * 2 + 288 * (768 * 2 + 2560 * 4)
    assert fam.wave_rows(cfg) == 1248


@pytest.mark.parametrize("which", ["window_plus", "window_minus",
                                   "rotated_global", "router_reads_x",
                                   "e4m3"])
def test_a_control_is_the_served_backend_with_one_thing_wrong(which):
    """``benchmark/testdata/smallthinker_controls.py``: same weights, one
    thing about the model wrong (or every dense operand rounded further);
    at the tiny preset in float32, where nothing is left but the fault, the
    reference, which stays the published model, reads the program far off on
    logits or at the router's edge."""
    import smallthinker_controls as controls

    kw = {"seed": 5, "max_seq_len": SEQ, "window": WINDOW, "piece": PIECE,
          "dtype": "float32"}
    be, served = controls.CONTROLS[which](**kw), backend(dtype="float32")
    for a, b in zip(jax.tree_util.tree_leaves(be._init_params()),
                    jax.tree_util.tree_leaves(served._init_params())):
        assert (a.seed, a.shape, a.dtype) == (b.seed, b.shape, b.dtype)
    assert getattr(be, "published_window", be.window) == WINDOW
    ids = ids_of()
    got, routes = Served(be).walk(ids, 18)
    with jax.default_matmul_precision("highest"):
        want, _, flips = fam.backend_forward(
            f32_params(be), be, ids, len(ids), follow=words_of(be, routes))
    assert np.isfinite(got).all()
    assert np.abs(got - np.asarray(want)).max() > 0.05 or flips.max() > 0.05


def test_the_windows_edge_is_told_by_the_direction_it_moves_the_logits():
    """``window_edge`` on hand-made records: served logits that are the
    reference's plus noise three times what a key moves them by lean 0, those
    that are the other window's lean 1."""
    rng = np.random.default_rng(0)
    prompt, toks, layers = [1, 2, 3], list(range(1, 41)), 4
    ref = rng.standard_normal((39, 9))       # a row's nine record logits
    alts = [ref + 0.001 * rng.standard_normal(ref.shape) for _ in range(2)]

    def probe_of(logits):
        rows = np.zeros((len(prompt) + 39, layers + 9), np.int32)
        noisy = logits + 0.003 * rng.standard_normal(logits.shape)
        rows[len(prompt):, layers:] = noisy.astype(np.float32).view(np.int32)
        return {"prompts": [prompt], "concurrent": [toks], "solo": [toks],
                "concurrent_record": [rows.tolist()],
                "solo_record": [rows.tolist()]}, rows

    for served, want in ((ref, (0, 0)), (alts[0], (1, 0)), (alts[1], (0, 1))):
        probe, rows = probe_of(served)
        key = (tuple(prompt), tuple(toks), rows[:, :layers].tobytes())
        lean = fam.window_edge(probe, {key: (ref, alts)}, layers)
        assert np.abs(np.asarray(lean) - want).max() < 0.45, lean
    assert fam.window_edge(probe, {}, layers) == (0.0, 0.0)


def test_a_launch_of_another_model_imports_none_of_it():
    code = ("import sys, client_tpu.models as zoo; zoo._import_all(); "
            "assert 'smallthinker' in zoo.model_names(); "
            "hit = [m for m in sys.modules if 'smallthinker' in m "
            "or 'models.experts' in m or 'latent_moe' in m]; "
            "assert not hit, hit")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
