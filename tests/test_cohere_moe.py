"""The parallel-block decoder over window and full layers
(models/cohere_moe.py: one LayerNorm read by the attention, the router, the
routed and the averaged shared experts; one add; interleaved rotary pairs on
the window layers alone; a tied head) at a tiny preset on the CPU, seeded
weights, Pallas interpreted: the served path (pieces, then single-step waves
through the rings and the full layer's rows) against the plain reference's
full forward pass on logits, for prompts that end inside a piece, on its
edge, at the window's edge and past two rings, alone, several to a wave and
two to a piece program; the two small functions against their definitions;
the tied head; what the scheduler counts of a piece's attention."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "testdata"))

import family  # noqa: E402

from client_tpu.models.cohere_moe import CohereMoeBackend  # noqa: E402
from client_tpu.models.layers import layer_norm, rope  # noqa: E402

fam = family.load("cohere_moe")
# The issue's preset: [W, W, W, F], hidden 64, 8 query heads over 2 key heads
# of 16, a window of 16, 8 routed experts of 32 with 2 a token, 4 shared
# experts of 32, a vocabulary of 96, pieces of 8.  Contexts to 44: the ring
# wraps twice and more.
SEQ, WINDOW, PIECE, N = 64, 16, 8, 44
# float32 weights, cache and matmuls against the float32 reference: what is
# left is the order of the sums (tied logits of magnitude 30).
TOL_F32 = 1e-3
# bfloat16 matmuls and rows against the float32 reference with the routing
# followed, at the tiny preset (tied logits of magnitude 30).
TOL_BF16 = 1.5


def backend(**kw):
    return CohereMoeBackend(**{"seed": 5, "max_seq_len": SEQ,
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
    held = (e >= 0) & (e < be.experts_held)
    out = np.where(held, 1 << np.clip(e, 0, 31), 0).sum(-1)[..., None]
    return out.transpose(1, 0, 2).astype(np.uint32).view(np.int32)


def reference(be, ids, follow=None):
    with jax.default_matmul_precision("highest"):
        logits, chosen, flips = fam.backend_forward(
            f32_params(be), be, ids, len(ids),
            follow=None if follow is None else words_of(be, follow))
    return np.asarray(logits), chosen, flips


class Served:
    """A backend's jitted piece and wave, an arena of three slots and the
    junk one, and the teacher-forced walk of prompts through them."""

    def __init__(self, be):
        self.be = be
        self.params = be.place_params(be._init_params())
        self.arena = be.init_arena(3)
        self.piece = jax.jit(be.piece_hidden_fn())
        self.hidden = jax.jit(be._decode_hidden_fn())

    def pieces(self, lanes):
        """One piece program: ``lanes`` [(slot, ids, start)] -> per lane
        (logits of its valid rows, routes)."""
        be = self.be
        buf = np.zeros((len(lanes), be.piece), np.int32)
        lens = []
        for i, (_, ids, st) in enumerate(lanes):
            part = ids[st:st + be.piece]
            buf[i, :len(part)] = part
            lens.append(len(part))
        self.arena, x, route = self.piece(
            self.params, self.arena,
            np.asarray([s for s, _, _ in lanes], np.int32), buf,
            np.asarray(lens, np.int32),
            np.asarray([st for _, _, st in lanes], np.int32))
        return [(np.asarray(be._logits(
            self.params, x[i * be.piece:i * be.piece + n])),
            np.asarray(route)[:, i * be.piece:i * be.piece + n])
            for i, n in enumerate(lens)]

    def prefill(self, ids, slot=1):
        got = [self.pieces([(slot, ids, st)])[0]
               for st in range(0, len(ids), self.be.piece)]
        return (np.concatenate([g[0] for g in got]),
                np.concatenate([g[1] for g in got], axis=1))

    def wave(self, lanes):
        """One wave: ``lanes`` [(slot, token, length)] and a padded lane on
        the junk slot -> (logits ``[lanes, vocab]``, routes ``[layers,
        lanes, k]``)."""
        tok = self.arena["tok"]
        for slot, token, _ in lanes:
            tok = tok.at[slot].set(int(token))
        self.arena = {**self.arena, "tok": tok}
        self.arena, x = self.hidden(
            self.params, self.arena,
            np.asarray([s for s, _, _ in lanes] + [3], np.int32),
            np.asarray([n for _, _, n in lanes] + [0], np.int32))
        return (np.asarray(self.be._logits(self.params, x))[:len(lanes)],
                np.stack([np.asarray(r)[:len(lanes)] for r in x["route"]]))

    def walk(self, ids, n_prompt, slot=1):
        logits, routes = self.prefill(ids[:n_prompt], slot)
        logits, routes = [logits], [routes]
        for t in range(n_prompt, len(ids)):
            row, route = self.wave([(slot, ids[t], t)])
            logits.append(row)
            routes.append(route)
        return np.concatenate(logits), np.concatenate(routes, axis=1)


# -- the two small functions against their definitions ---------------------------

def test_layer_norm_subtracts_the_mean_and_has_no_bias():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 64)).astype(np.float32) + 3.0
    g = rng.standard_normal(64).astype(np.float32)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * g
    assert np.abs(np.asarray(layer_norm(x, g, 1e-5)) - want).max() < 1e-5
    assert np.abs(np.asarray(fam.layer_norm(jnp.asarray(x), g, 1e-5))
                  - want).max() < 1e-5


def test_the_interleaved_rope_turns_adjacent_lanes_as_one_complex_number():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((6, 3, 16)).astype(np.float32)
    pos = np.asarray([0, 1, 5, 17, 40, 4099])
    z = x[..., 0::2] + 1j * x[..., 1::2]
    turn = np.exp(1j * pos[:, None, None]
                  * 50000.0 ** (-np.arange(0, 16, 2) / 16)[None, None])
    want = np.stack([(z * turn).real, (z * turn).imag], -1).reshape(x.shape)
    got = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), 50000.0,
                          interleaved=True))
    assert np.abs(got - want).max() < 2e-4
    assert np.abs(np.asarray(fam.rope_interleaved(
        jnp.asarray(x), jnp.asarray(pos), 50000.0)) - want).max() < 2e-4
    # The other pairing is another function.
    half = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), 50000.0))
    assert np.abs(half - want).max() > 0.1


def test_the_tied_head_is_the_embedding_one_leaf():
    be = backend(dtype="float32")
    params = be._init_params()
    assert "head" not in params and set(params) == {"embed", "layers", "lnf"}
    placed = be.place_params(params)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((3, 64)),
                    jnp.float32)
    want = np.asarray(layer_norm(x, placed["lnf"], be.norm_eps)) @ np.asarray(
        placed["embed"]).T
    assert np.abs(np.asarray(be._logits(placed, x)) - want).max() < 1e-4


# -- the served path against the plain reference, on logits -------------------

# A prompt that ends inside a piece (every later position a wave: the ring's
# first overwrite at 16), on a piece's edge, at the window's edge, and past
# two rings (the ring wrapped twice inside prefill).
PROMPTS = {"inside_a_piece": 3, "on_a_pieces_edge": 8,
           "at_the_windows_edge": 16, "past_two_rings": 37}


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
@pytest.mark.parametrize("case", sorted(PROMPTS))
def test_float32_pieces_then_waves_match_the_full_forward_pass(
        attn_impl, case):
    be = backend(dtype="float32", attn_impl=attn_impl)
    ids = ids_of()
    got, routes = Served(be).walk(ids, PROMPTS[case])
    want, chosen, _ = reference(be, ids)
    assert (np.sort(routes, -1) == np.sort(chosen, -1)).all()
    assert np.abs(got - want).max() < TOL_F32


@pytest.mark.parametrize("window", [15, 12])
def test_a_window_that_does_not_fill_its_ring(window):
    be = backend(dtype="float32", window=window, attn_impl="fused")
    assert be.ring_rows == 16 and be.ring_window == window
    ids = ids_of()
    got, _ = Served(be).walk(ids, 21)
    assert np.abs(got - reference(be, ids)[0]).max() < TOL_F32


def test_bfloat16_pieces_then_waves_match_the_reference_that_follows():
    be = backend(attn_impl="fused")
    ids = ids_of()
    got, routes = Served(be).walk(ids, 21)
    want, _, flips = reference(be, ids, follow=routes)
    assert np.abs(got - want).max() < TOL_BF16
    assert flips.max() < 0.05


def test_several_lanes_to_a_wave_are_each_their_own_stream():
    """Three streams of different lengths (one short of the window, one past
    it, one past two rings) advance in the same waves."""
    be = backend(dtype="float32", attn_impl="fused")
    srv = Served(be)
    prompts = [(0, ids_of(N, seed=3), 5), (1, ids_of(N, seed=4), 18),
               (2, ids_of(N, seed=5), 37)]
    got = {slot: [srv.prefill(ids[:n], slot)[0]] for slot, ids, n in prompts}
    for step in range(6):
        rows, _ = srv.wave([(slot, ids[n + step], n + step)
                            for slot, ids, n in prompts])
        for (slot, _, _), row in zip(prompts, rows):
            got[slot].append(row[None])
    for slot, ids, n in prompts:
        want = reference(be, ids[:n + 6])[0]
        assert np.abs(np.concatenate(got[slot]) - want).max() < TOL_F32


@pytest.mark.parametrize("geometry", ["einsum", "flash"])
def test_two_prompts_to_a_piece_program_are_the_prompts_alone(geometry):
    """A lane past two rings beside a lane at its first piece, cut short."""
    kw = dict(dtype="float32") if geometry == "einsum" else dict(
        dtype="float32", attention_impl="flash", head_dim=128, n_heads=2,
        n_kv_heads=1)
    be = backend(**kw)
    a, b = ids_of(N, seed=6), ids_of(5, seed=7)
    pair, solo = Served(be), Served(be)
    for srv in (pair, solo):
        srv.prefill(a[:32], slot=0)
    both = pair.pieces([(0, a, 32), (2, b, 0)])
    alone = [solo.pieces([(0, a, 32)])[0], solo.pieces([(2, b, 0)])[0]]
    for (x, r), (y, s) in zip(both, alone):
        assert np.abs(x - y).max() < TOL_F32 and (r == s).all()
    for leaf in ("kw", "vw", "kg", "vg"):
        assert (np.asarray(pair.arena[leaf][:, :3])
                == np.asarray(solo.arena[leaf][:, :3])).all()
    want = reference(be, a[:40])[0]
    assert np.abs(both[0][0] - want[32:40]).max() < TOL_F32


def test_the_flash_piece_is_the_einsum_piece():
    kw = dict(dtype="float32", head_dim=128, n_heads=4, n_kv_heads=2)
    ids = ids_of(30)
    got = [Served(backend(attention_impl=impl, **kw)).prefill(ids)[0]
           for impl in ("einsum", "flash")]
    assert np.abs(got[0] - got[1]).max() < TOL_F32


# -- what the scheduler counts ------------------------------------------------------

def test_a_pieces_pairs_by_kind_against_a_count_by_hand():
    be = backend()
    for start, valid in [(0, 8), (8, 8), (16, 3), (40, 8), (8, 1)]:
        ring = sum(min(t + 1, WINDOW) for t in range(start, start + valid))
        whole = sum(t + 1 for t in range(start, start + valid))
        assert be.piece_pairs_by_kind(start, valid) == (3 * ring, whole)
    # A wave's rows by kind, as ``smallthinker``'s: one copy of both.
    assert be.cache_rows_by_kind(40) == (3 * 15, 40, 1)
    assert be.cache_rows_by_kind(16) == (3 * 15, 16, 0)
    assert be.cache_rows_by_kind(4) == (3 * 4, 4, 0)


def test_the_ring_parts_are_one_copy():
    from client_tpu.models import grouped_query, smallthinker
    for name in ("_read_ring", "_write_ring", "_piece_ring_layer",
                 "_full_ring_layer", "init_arena", "cache_rows_by_kind",
                 "_ring_setup"):
        assert name not in vars(CohereMoeBackend)
        assert name not in vars(smallthinker.SmallThinkerBackend)
        assert name in vars(grouped_query.RingPieces)
