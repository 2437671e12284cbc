"""The hybrid decoder (models/kimi_linear.py: a recurrent state beside a
latent cache in one arena) at a tiny preset on the CPU, seeded weights, Pallas
interpreted: the served path (chunked pieces, single-step waves through both
caches) against the plain reference's token-by-token forward pass on logits;
the chunked form against the recurrence under strong decay; the wave kernel
against its oracle; what a slot's life asks of a state that ``lens`` cannot
mask (a reused slot, padded lanes, padded positions, any cut into pieces); the
chunked step; the scheduler's counters; the benchmark family's arithmetic and
readers."""

import os
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

import family  # noqa: E402

from client_tpu.engine import TpuEngine  # noqa: E402
from client_tpu.engine.repository import ModelRepository  # noqa: E402
from client_tpu.engine.types import InferRequest  # noqa: E402
from client_tpu.models.kimi_linear import KimiLinearBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402
from client_tpu.ops import kda  # noqa: E402

fam = family.load("kimi_linear")
SEQ, PIECE, N = 64, 16, 44
TOL_F32 = 2e-4
# bfloat16 matmuls, rows and convolution tail against the float32 reference
# with the routing followed, at the tiny preset (logits of magnitude 3).
TOL_BF16 = 0.15


def backend(chunk=None, **kw):
    """The tiny preset; ``chunk`` overrides the chunked form's chunk, which
    the backend takes from ops/kda.py (``chunk=1``: the token-by-token
    walk)."""
    kw = {"seed": 5, "max_seq_len": SEQ, "piece": PIECE, **kw}
    be = KimiLinearBackend(**kw)
    if chunk is not None:
        be.chunk = chunk
    return be


def f32_params(be):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  be._init_params())


def ids_of(n=N, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def words_of(be, routes):
    """Choices ``[expert layers, n, top_k]`` -> the record's words ``[n,
    expert layers]`` (numpy's form of ``held_mask``)."""
    e = np.asarray(routes, np.int64) - be.first_expert
    held = (e >= 0) & (e < be.experts_held)
    return np.where(held, 1 << np.clip(e, 0, 31), 0).sum(-1).T


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
        """-> logits ``[len(ids), vocab]``, choices ``[expert layers,
        len(ids), top_k]``."""
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

    def slot(self, slot):
        """What the slot holds of a stream: its states and tails."""
        return (np.asarray(self.arena["s"][:, slot]),
                np.asarray(self.arena["conv"][:, slot].astype(jnp.float32)))


# -- the served path against the plain reference, on logits -------------------

@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_float32_pieces_then_waves_match_the_token_by_token_reference(
        attn_impl):
    """float32 weights, caches and matmuls: two and a half chunked pieces,
    then single-step waves through the state, the tail and the latent rows,
    give the logits of the reference's full forward pass at every position,
    and the same experts."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    ids = ids_of()
    want, chosen, _ = reference(be, ids)
    got, routes = Served(be).walk(ids, 37)
    assert np.array_equal(np.sort(routes, -1), np.sort(chosen, -1))
    assert np.abs(got - want).max() < TOL_F32


def test_bfloat16_pieces_then_waves_match_the_reference_that_follows():
    be = backend()
    ids = ids_of()
    got, routes = Served(be).walk(ids, 37)
    want, _, _ = reference(be, ids, follow=routes)
    assert np.abs(got - want).max() < TOL_BF16


def test_the_layers_are_of_two_kinds_with_leaves_of_their_own_depth():
    be = backend()
    assert be.layer_kinds == ("state", "state", "state", "rows")
    assert [be._layer_kind(li) for li in range(4)] == [
        ("state", 0), ("state", 1), ("state", 2), ("rows", 0)]
    arena = jax.eval_shape(lambda: be.init_arena(3))
    assert arena["c"].shape == (1, 4, SEQ, be.row_width)
    assert arena["s"].shape == (3, 4, 4, 16, 16)
    assert arena["s"].dtype == jnp.float32
    assert arena["conv"].shape == (3, 4, 3 * 3 * 4 * 16)
    with pytest.raises(ValueError):
        backend(n_layers=5)         # layer 5 is of neither kind in the preset
    # The published pattern, cut to its first eight layers.
    lin = {"kda_layers": [1, 2, 3, 5, 6, 7, 9], "full_attn_layers": [4, 8, 12],
           "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4}
    assert backend(n_layers=8, linear_attn=lin).layer_kinds == (
        "state", "state", "state", "rows") * 2


# -- the chunked form and the kernel --------------------------------------------

def _kda_operands(n, heads, d, strong, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((n, heads, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    g = -rng.uniform(0, 8.0 if strong else 0.1,
                     (n, heads, d)).astype(np.float32)
    beta = rng.uniform(0, 1, (n, heads)).astype(np.float32)
    s0 = rng.standard_normal((heads, d, d)).astype(np.float32)
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("chunk", [1, 4, 16, 64])
@pytest.mark.parametrize("strong", [False, True])
def test_the_chunked_form_is_the_recurrence(chunk, strong):
    """Under weak decay and under ``g`` down to -8 a step, where ``1 /
    Gamma`` of a chunk of 64 would be ``exp(512)``: nothing overflows,
    because no ``exp`` here has a positive argument."""
    ops = _kda_operands(64, 2, 16, strong)
    with jax.default_matmul_precision("highest"):
        o_want, s_want = kda.kda_recurrence(*ops)
        o_got, s_got = kda.kda_chunk_scan(*ops, chunk=chunk)
    assert np.isfinite(np.asarray(o_got)).all()
    assert np.abs(np.asarray(o_got) - np.asarray(o_want)).max() < 2e-5
    assert np.abs(np.asarray(s_got) - np.asarray(s_want)).max() < 2e-5


def test_a_padded_position_moves_nothing_in_the_chunked_form():
    q, k, v, g, beta, s0 = _kda_operands(32, 2, 16, False)
    g[20:], beta[20:] = 0.0, 0.0
    _, s_all = kda.kda_chunk_scan(q, k, v, g, beta, s0, chunk=8)
    _, s_cut = kda.kda_recurrence(q[:20], k[:20], v[:20], g[:20], beta[:20],
                                  s0)
    assert np.abs(np.asarray(s_all) - np.asarray(s_cut)).max() < 2e-5


@pytest.mark.parametrize("layer", [1, "traced"])
@pytest.mark.parametrize("heads,head_block", [(4, 2), (4, 32), (64, 32)])
def test_wave_kernel_parity(heads, head_block, layer, monkeypatch):
    """The kernel (interpreted) against its oracle: the lanes' slots
    advanced alike, every other slot and layer untouched bit for bit; with
    all heads in one grid step, and in two blocks (of 2 with the constant
    lowered, of the served 32 with 64 heads)."""
    monkeypatch.setattr(kda, "HEAD_BLOCK", head_block)
    q, k, v, g, beta, _ = _kda_operands(3, heads, 16, False, seed=1)
    arena = np.random.default_rng(2).standard_normal(
        (2, 6, heads, 16, 16)).astype(np.float32)
    rows = jnp.asarray([4, 0, 2], jnp.int32)
    want_a, want_o = kda.reference_kda_update(
        jnp.asarray(arena), q, k, v, g, beta, rows, layer=1)

    def run(a, li):
        return kda.kda_wave_update(a, q, k, v, g, beta, rows, layer=li,
                                   interpret=True)

    got_a, got_o = (run(jnp.asarray(arena), 1) if layer == 1 else
                    jax.jit(run)(jnp.asarray(arena), jnp.int32(1)))
    assert np.abs(np.asarray(got_o) - np.asarray(want_o)).max() < 1e-5
    assert np.abs(np.asarray(got_a) - np.asarray(want_a)).max() < 1e-5
    got_a = np.asarray(got_a)
    assert np.array_equal(got_a[0], arena[0])
    assert np.array_equal(got_a[1, [1, 3, 5]], arena[1, [1, 3, 5]])


# -- a slot's life: what ``lens`` cannot mask -------------------------------------

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
    # The padded positions of slot 1's last piece moved nothing: its state
    # after the prompt is the recurrence's over the 21 valid positions.
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
    """float32: two prompts' pieces in one program (``wqkv``, the latent
    projections and the feed-forward over both lanes' positions at once, the
    mixers a lane at a time, each from its own slot and its own ``start``,
    fresh or continued) leave every slot's states, tails and latent rows, and
    give every valid position's activations and choices, exactly as the same
    two pieces do one lane at a time."""
    pair, solo, got, want, lanes = _two_lanes("float32", attn_impl, case)
    for leaf in ("s", "conv", "c"):
        assert np.array_equal(np.asarray(pair.arena[leaf][:, :3]),
                              np.asarray(solo.arena[leaf][:, :3])), leaf
    for (x, routes), (x1, routes1), (_, _, held) in zip(got, want, lanes):
        assert np.array_equal(x[:held], x1[:held])
        assert np.array_equal(routes[:, :held], routes1[:, :held])


@pytest.mark.parametrize("case", ["a_first_piece_beside_a_third",
                                  "a_lane_shorter_than_the_convolution"])
def test_two_bfloat16_lanes_are_the_lanes_alone_within_the_files_limits(case):
    """bfloat16: a matmul over twice the rows, and a sorted layout in other
    tiles, may round an activation the other way, so the two forms agree to
    the file's limits and not to the bit; the states are float32 and agree
    far closer."""
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
    stand bit for bit in state, tail and latent rows, and slot 1's piece is
    the one-lane program's."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    pair, solo = Served(be), Served(be)
    ids = ids_of(40, seed=41)
    for srv in (pair, solo):
        srv.walk(ids_of(30, seed=42), 20, slot=0)
        srv.walk(ids_of(25, seed=43), 18, slot=2)
        srv.prefill(ids[:16], slot=1)
    rows, buf, lens, starts = _piece_args([(ids, 16, 16)], [1])
    pair.arena, x, _ = pair.piece(
        pair.params, pair.arena, np.asarray([1, 3], np.int32),
        np.concatenate([buf, np.zeros_like(buf)]), np.asarray([16, 1],
                                                              np.int32),
        np.asarray([16, 0], np.int32))
    solo.arena, x1, _ = solo.piece(solo.params, solo.arena, rows, buf, lens,
                                   starts)
    assert np.array_equal(np.asarray(x)[:16], np.asarray(x1))
    for leaf in ("s", "conv", "c"):
        assert np.array_equal(np.asarray(pair.arena[leaf][:, :3]),
                              np.asarray(solo.arena[leaf][:, :3])), leaf


@pytest.mark.parametrize("sample", [False, True])
def test_two_lanes_tokens_and_record_rows_are_the_lanes_alone(sample):
    """The whole prefill program: a token a lane from its own last valid
    position into its own slot, and the record laid ``[L | L x piece x
    stream_record]`` as the scheduler cuts it, lane after lane."""
    be = backend(dtype="float32")
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


@pytest.mark.parametrize("piece,chunk", [(64, 16), (32, 8), (8, 8)])
def test_a_prompt_cut_into_1_2_and_5_pieces_gives_one_state(piece, chunk):
    """A prompt of 40 positions as one piece, two and five: the same state,
    the same tail and the same latent rows, the token-by-token walk's (a
    piece of chunks of one position)."""
    ids = ids_of(40, seed=6)
    want = Served(backend(dtype="float32", piece=64, chunk=1))
    want.prefill(ids)
    got = Served(backend(dtype="float32", piece=piece, chunk=chunk))
    got.prefill(ids)
    for a, b in zip(got.slot(1), want.slot(1)):
        assert np.abs(a - b).max() < 2e-5
    assert np.abs(np.asarray(got.arena["c"][:, 1, :40], np.float32)
                  - np.asarray(want.arena["c"][:, 1, :40], np.float32)
                  ).max() < 2e-5


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_the_chunked_step_of_three_is_three_waves(attn_impl):
    """``decode_chunk_fn`` works unchanged: the state is in the carried
    arena."""
    be = backend(attn_impl=attn_impl)
    params = be.place_params(be._init_params())
    srv = Served(be)
    srv.prefill(ids_of(20, seed=7), slot=0)
    srv.prefill(ids_of(9, seed=8), slot=2)
    rows = np.asarray([0, 2, 3, 3], np.int32)
    lens = np.asarray([20, 9, 0, 0], np.int32)
    zeros, ones = np.zeros(4, np.int32), np.ones(4, np.float32)
    args = (zeros, ones * 0, zeros, ones, False)
    wave = jax.jit(be.decode_fn(), static_argnums=be.decode_static_argnums)
    chunk = jax.jit(be.decode_chunk_fn(),
                    static_argnums=be.decode_chunk_static_argnums)
    arena, toks = dict(srv.arena), []
    for step in range(3):
        arena, t = wave(params, arena, rows, lens + step * (lens > 0), *args)
        toks.append(np.asarray(t))
    # Padded lanes keep length 0 in the single waves; the chunk advances
    # every lane's length (so its padded lanes route, and its counts behind
    # the tokens differ): compare the live lanes and the live slots.
    arena_c, toks_c = chunk(params, dict(srv.arena), rows, lens, *args, 3)
    assert np.array_equal(np.stack(toks)[:, :2], np.asarray(toks_c)[:, :2])
    for name in ("s", "conv"):
        assert np.array_equal(np.asarray(arena[name][:, [0, 2]], np.float32),
                              np.asarray(arena_c[name][:, [0, 2]],
                                         np.float32))


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


# (prompt length, tokens): one, two and three pieces; slots are reused.
PLAN = [(5, 6), (20, 5), (40, 6), (21, 4)]


@pytest.fixture(scope="module", params=["reference", "fused"])
def served(request):
    name = f"kimi_{request.param}"
    be = backend(name=name, attn_impl=request.param, max_streams=2)
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
        """Four streams over two slots, then each alone in a slot another
        stream has used: the same tokens."""
        _, _, together, alone, *_ = served
        assert together == alone
        assert [len(t) for t in together] == [m for _, m in PLAN]

    def test_a_stream_that_asks_gets_its_record_and_no_other(self, served):
        """A row a position consumed (the prompt's, and one a wave): the
        expert layers' words, then the logits its token was chosen from,
        which only a position that emitted a token has; a stream that did
        not ask ends on an empty final response, with the same tokens."""
        be, prompts, together, _, _, _, records, _, plain = served
        assert plain == together[0]
        for p, (_, m), toks, rec in zip(prompts, PLAN, together, records):
            assert rec.shape == (len(p) + m - 1, be.stream_record)
            assert rec.dtype == np.int32
            words, logits = fam.record_columns(rec, be.n_layers - be.n_dense)
            assert ((words >= 0) & (words < 1 << be.experts_held)).all()
            emitted = np.zeros(len(rec), bool)
            emitted[len(p) - 1:] = True
            emitted[PIECE - 1:len(p):PIECE] = True    # a piece's junk token
            assert (logits[~emitted] == 0).all()
            assert (logits[len(p) - 1:, 0] >= logits[len(p) - 1:, 1:].max(-1)
                    ).all()                            # greedy: the row's best

    def test_the_reference_accepts_every_token(self, served):
        """Following each stream's served routing, on its served logits:
        at the tiny preset's own limits (logits of magnitude 3)."""
        (be, prompts, together, alone, _, _, rec_together, rec_alone,
         _) = served
        params = f32_params(be)

        def rows_fn(prompt, emitted, words):
            seq = np.asarray(prompt + emitted, np.int32)
            with jax.default_matmul_precision("highest"):
                logits, _, flips = fam.backend_forward(
                    params, be, seq[:-1], len(emitted), follow=words)
            return logits, flips

        for i, (p, (_, m)) in enumerate(zip(prompts, PLAN)):
            one = {"prompts": [p], "max_tokens": m,
                   "concurrent": [together[i]], "solo": [alone[i]],
                   "concurrent_record": [rec_together[i]],
                   "solo_record": [rec_alone[i]]}
            verdict = fam.judge(one, rows_fn, be.n_layers - be.n_dense,
                                margin=TOL_BF16,
                                logit_rms_alone=TOL_BF16 / 3,
                                logit_rms_together=TOL_BF16 / 3,
                                logit_max=TOL_BF16, tie=0.02)
            assert verdict["ok"], verdict
            assert verdict["tokens_checked"] == 2 * m

    def test_piece_positions_and_routing_reach_the_counters(self, served):
        be, _, _, _, before, after, *_ = served
        c = {k: after[k] - before[k] for k in after}
        pieces = sum(-(-n // PIECE) for n, _ in PLAN) * 2
        assert c["prefill_pieces"] == pieces
        assert c["prefill_positions_valid"] == 2 * sum(n for n, _ in PLAN)
        assert c["prefill_positions_padded"] == pieces * PIECE \
            - c["prefill_positions_valid"]
        assert c["fetched_waves"] > 0 and c["expert_pairs_local"] > 0
        assert c["experts_touched"] <= c["fetched_waves"] * (
            be.n_layers - be.n_dense) * be.experts_held


# -- the benchmark family ----------------------------------------------------------

def _config():
    from traffic import load_json
    return load_json(os.path.join(BENCH, "configs", "kimi_linear.json"))


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts():
    import json
    cfg = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    # The harness's name for the experts held, beside the published key.
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 32
    from client_tpu.models import experts
    assert cfg["serve"]["expert_tile_rows"] == experts.TILE_M_WAVE


def test_the_backend_built_from_the_file_is_the_issues_arena():
    import serve as serve_mod
    cfg = _config()
    kw = serve_mod.backend_kwargs(cfg, 7, 8192)
    be = KimiLinearBackend(name="kimi_linear", **kw)
    assert be.layer_kinds == ("state", "state", "state", "rows") * 2
    arena = jax.eval_shape(lambda: be.init_arena(be.max_streams))
    assert arena["c"].shape == (2, 257, 8192, 640)
    assert arena["s"].shape == (6, 257, 32, 128, 128)
    assert arena["conv"].shape == (6, 257, 3 * 12288)
    assert be.prefill_piece == (512, 2)
    params = be._init_params()
    total = sum(int(np.prod(w.shape))
                for w in jax.tree_util.tree_leaves(params))
    assert 2.08e9 < total < 2.10e9          # the issue's 2.09e9


def test_step_arithmetic_by_hand():
    cfg = _config()
    flops, nbytes = fam.kda_update(cfg, 256)
    state = 32 * 128 * 128
    assert flops == 7 * 256 * state
    assert nbytes == 256 * (2 * state + 32 * (5 * 128 + 1)) * 4
    states, rows = fam.cache_bytes(cfg, 250, 250 * 3900)
    assert states == 250 * 6 * 2 * state * 4
    assert rows == 250 * 3900 * 2 * 1152
    assert fam.wave_rows(cfg) == 2528
    # A wave of 256 lanes at 3900 rows, 8 rows a held expert, all touched:
    # the issue's 12.9 GB, the caches two thirds of it.
    _, total = fam.decode_step(cfg, 256, 3900, 256, 32)
    assert 12.5e9 < total < 13.3e9
    assert 0.62 < (6 * 256 * 2 * state * 4 + rows / 250 * 256) / total < 0.72


def _ctx(counters, trace=None):
    snap = lambda c: {"profile": {"models": {"m": {"generative": {  # noqa: E731
        "spans": {}, "counters": c}, "decode_waves": [
            {"bucket": 256, "waves": c.get("fetched_waves", 0),
             "device_s": 0.0}]}}}}
    return {"cfg": _config(), "traffic": {"max_model_len": 8192},
            "snap_before": snap({k: 0 for k in counters}),
            "snap_after": snap(counters), "trace": trace,
            "device": {"kind": "TPU v5 lite"}}


COUNTERS = {"fetched_waves": 100, "fetched_lanes_live": 25000,
            "fetched_positions_valid": 25000 * 3900,
            "prefill_positions_valid": 9000, "prefill_positions_padded": 1000,
            "expert_pairs_local": 100 * 7 * 250, "expert_pairs_busiest": 9100,
            "experts_touched": 100 * 7 * 31}


@pytest.mark.parametrize("name,want", [
    ("prefill_padded_position_share", 10.0),
    ("state_bytes_share", 100 * 6 * 2 * 2097152 / (
        6 * 2 * 2097152 + 3900 * 2 * 1152)),
    ("expert_rows_per_expert", 250 / 32),
    ("experts_touched_share", 100 * 31 / 32),
    ("arena_live_share", 100 * 250 * 3900 / (256 * 8192)),
])
def test_counter_readers_on_recorded_counters(name, want):
    import run as run_mod
    assert run_mod.load_reader(name)(_ctx(COUNTERS)) == pytest.approx(want)


def test_new_readers_read_nothing_where_the_program_counts_nothing():
    import run as run_mod
    bare = {"fetched_waves": 100, "fetched_lanes_live": 25000,
            "fetched_positions_valid": 25000 * 3900}
    assert run_mod.load_reader("prefill_padded_position_share")(
        _ctx(bare)) is None
    assert run_mod.load_reader("kda_state_roofline")(_ctx(COUNTERS)) is None
    none = _ctx(COUNTERS)
    none["snap_before"] = none["snap_after"] = None
    assert run_mod.load_reader("state_bytes_share.obs")(none) is None


def test_the_state_kernels_share_multiplies_by_the_calls_it_found():
    """Four of the six layers' kernels among the ten: the share is of those
    four calls a step, not of six."""
    import roofline
    import run as run_mod
    cfg = _config()
    least, _ = roofline.min_seconds(*fam.kda_update(cfg, 250),
                                    roofline.peaks_for("TPU v5 lite"))
    steps, each = 150, 0.3
    trace = {"modules": {"jit_decode": {"count": steps}},
             "device_ops": [[f"kda_wave_update.{i}_f32_6_257_", each]
                            for i in range(4)] + [["fusion.1", 0.2]]}
    got = run_mod.load_reader("kda_state_roofline")(_ctx(COUNTERS, trace))
    assert got == pytest.approx(100 * steps * 4 * least / (4 * each))
    assert 0 < got < 100


def test_a_stale_state_moves_logits_by_more_than_any_limit():
    """What a slot not cleared for its next stream would read: the wave
    behind a first piece, with the last stream's state added back, is off
    the reference by far more than the comparison's widest limit."""
    be = backend(dtype="float32")
    srv = Served(be)
    srv.walk(ids_of(30, seed=30), 25)
    stale = srv.slot(1)[0]
    prompt = ids_of(17, seed=31)
    want, _, _ = reference(be, prompt)
    srv.prefill(prompt[:16])
    kept = dict(srv.arena)
    good, _ = srv.wave(prompt[16], 16)
    srv.arena = {**kept, "s": kept["s"].at[:, 1].add(jnp.asarray(stale))}
    bad, _ = srv.wave(prompt[16], 16)
    assert np.abs(good[0] - want[16]).max() < TOL_F32
    assert np.abs(bad[0] - want[16]).max() > 10 * fam.LOGIT_MAX


def test_the_programs_leave_the_record_of_what_they_computed():
    """Behind the tokens of a wave and of a piece: the words of the choices
    the same programs make (``held_mask``) and the logits their tokens were
    chosen from, bit for bit."""
    be = backend(attn_impl="reference")
    srv = Served(be)
    ids = ids_of(21, seed=8)
    srv.prefill(ids[:16])
    kept = dict(srv.arena)
    n_moe, width = be.n_layers - be.n_dense, be.stream_record
    rows, lens = np.asarray([1], np.int32), np.asarray([5], np.int32)
    zeros = (np.zeros(1, np.int32), np.zeros(1, np.float32),
             np.zeros(1, np.int32), np.ones(1, np.float32))
    buf = np.zeros((1, PIECE), np.int32)
    buf[0, :5] = ids[16:]
    piece = jax.jit(be.prefill_fn(), static_argnums=be.prefill_static_argnums)
    _, out = piece(srv.params, dict(kept), rows, buf, lens, *zeros, False,
                   np.asarray([16], np.int32), np.ones(1, np.int32))
    rec = np.asarray(out[1:]).reshape(PIECE, width)
    srv.arena = dict(kept)
    logits, routes = srv.prefill(ids, slot=1)[0][16:], None
    srv.arena = dict(kept)
    _, x, route = srv.piece(srv.params, srv.arena, rows, buf, lens,
                            np.asarray([16], np.int32))
    assert np.array_equal(rec[:5, :n_moe], words_of(be, np.asarray(route))[:5])
    words, served = fam.record_columns(rec, n_moe)
    assert int(out[0]) == int(served[4, 1:].argmax()) or served[4, 0] >= \
        served[4, 1:].max()
    assert (served[:4] == 0).all() and (served[5:] == 0).all()
    # A wave of two lanes, one padded.
    wave = jax.jit(be.decode_fn(), static_argnums=be.decode_static_argnums)
    pair = (np.asarray([1, 3], np.int32), np.asarray([21, 0], np.int32))
    two = tuple(np.concatenate([z, z]) for z in zeros)
    arena, out = wave(srv.params, dict(srv.arena), *pair, *two, False)
    _, x = srv.hidden(srv.params, dict(srv.arena), *pair)
    rec = np.asarray(out[2:2 + 2 * width]).reshape(2, width)
    assert np.array_equal(
        rec[:, :n_moe],
        words_of(be, np.stack([np.asarray(r) for r in x["route"]])))
    full = np.asarray(be._logits(srv.params, x))
    _, served = fam.record_columns(rec, n_moe)
    assert np.array_equal(served[:, 0], full.max(-1))
    assert np.array_equal(served[:, 1:], full[:, :served.shape[1] - 1])
    assert out.shape == (2 + 2 * width + 3,)


def test_the_reference_follows_a_record_and_says_how_far_it_flipped():
    """Following its own choices changes nothing and flips nothing; a held
    expert forced in or out is a flip as far from the edge as its score."""
    be = backend(dtype="float32")
    ids = ids_of(12, seed=9)
    want, chosen, flips = reference(be, ids)
    assert (flips == 0).all()
    got, again, flips = reference(be, ids, follow=chosen)
    assert np.array_equal(np.sort(again, -1), np.sort(chosen, -1))
    assert (flips == 0).all() and np.abs(got - want).max() < 1e-5
    words = words_of(be, chosen)
    words[3, 1] ^= 1                         # expert 0 of layer 1, position 3
    with jax.default_matmul_precision("highest"):
        got, forced, flips = fam.backend_forward(
            f32_params(be), be, ids, len(ids), follow=words)
    assert (0 in forced[1, 3]) != (0 in chosen[1, 3])
    assert flips[3] > 0 and (np.delete(flips, 3)[:3] == 0).all()
    assert np.abs(np.asarray(got)[3:] - want[3:]).max() > 1e-3
    assert np.abs(np.asarray(got)[:3] - want[:3]).max() < 1e-5


def _judged(**fault):
    """A probe of one stream pair over a vocabulary of 12, the reference's
    rows flat but for the token's, with one fault at a time."""
    n_moe, samples, vocab = 2, 3, 12
    prompt, toks = [1, 2, 3], [4, 5]
    rows = np.zeros((2, vocab))
    rows[0, 4] = rows[1, 5] = 1.0
    served = np.concatenate([rows[[0, 1], [4, 5]][:, None],
                             rows[:, :samples]], 1).astype(np.float32)
    served[1, 2] += fault.get("logit", 0.0)
    signs = np.asarray([[1, -1, 1, -1], [-1, 1, -1, 1]], np.float32)
    alone = served + fault.get("alone", 0.0) * signs
    served += fault.get("together", 0.0) * signs
    if "below" in fault:
        rows[1, 7] = 1.0 + fault["below"]
    records = []
    for logits in (served, alone):
        record = np.zeros((4, n_moe + 1 + samples), np.int32)
        record[2:, n_moe:] = logits.view(np.int32)
        records.append(record[:fault.get("rows", 4)].tolist())
    flips = np.zeros(4)
    flips[1] = fault.get("flip", 0.0)
    probe = {"prompts": [prompt], "max_tokens": 2, "concurrent": [toks],
             "solo": [toks], "concurrent_record": [records[0]],
             "solo_record": [records[1]]}
    return fam.judge(probe, lambda p, e, w: (rows, flips), n_moe)


@pytest.mark.parametrize("fault,ok", [
    ({}, True),
    ({"below": fam.MARGIN / 2}, True),
    ({"below": fam.MARGIN * 2}, False),          # a token under the best
    ({"logit": fam.LOGIT_MAX * 2}, False),       # one served logit far off
    ({"alone": fam.LOGIT_RMS_ALONE * 0.9}, True),
    ({"alone": fam.LOGIT_RMS_ALONE * 1.1}, False),   # a stream alone, off
    ({"together": fam.LOGIT_RMS_ALONE * 1.1}, True),
    ({"together": fam.LOGIT_RMS_TOGETHER * 1.1}, False),  # four to a wave
    ({"flip": fam.TIE / 2}, True),
    ({"flip": fam.TIE * 2}, False),              # a choice far from the edge
    ({"rows": 3}, False),                        # a record cut short
])
def test_the_comparison_fails_by_each_of_its_limits(fault, ok):
    verdict = _judged(**fault)
    assert verdict["ok"] is ok, verdict
    if ok:
        assert verdict["tokens_checked"] == 4
        assert verdict["logits_compared"] == 16


@pytest.mark.parametrize("which", ["bf16_state", "bf16_decay", "e4m3"])
def test_a_control_is_the_served_backend_in_a_lower_precision(which):
    """``benchmark/testdata/kimi_linear_controls.py``: same weights, one
    thing rounded further; each walks a prompt to finite logits that are
    not the served ones."""
    sys.path.insert(0, os.path.join(BENCH, "testdata"))
    import kimi_linear_controls as controls

    kw = {"seed": 5, "max_seq_len": SEQ, "piece": PIECE}
    be, served = controls.CONTROLS[which](**kw), backend()
    for a, b in zip(jax.tree_util.tree_leaves(be._init_params()),
                    jax.tree_util.tree_leaves(served._init_params())):
        assert (a.seed, a.shape, a.dtype) == (b.seed, b.shape, b.dtype)
    arena = jax.eval_shape(lambda: be.init_arena(3))
    assert arena["s"].dtype == (jnp.bfloat16 if which == "bf16_state"
                                else jnp.float32)
    if which == "bf16_decay":
        lp = jax.tree_util.tree_map(np.asarray, be._init_params()["layers"][0])
        h = jnp.full((1, be.d_model), 40.0)      # a decay that underflows
        ext = jnp.zeros((be.taps, 3 * be.kda_heads * be.kda_dim))
        g = np.asarray(be._kda_inputs(lp, h, ext)[3])
        assert np.isfinite(g).all() and (g <= 0).all()
    ids = ids_of(24, seed=14)
    got, _ = Served(be).walk(ids, 20)
    want, _ = Served(served).walk(ids, 20)
    assert np.isfinite(got).all()
    # (At the tiny widths a routing flip moves a logit by tenths.)
    assert 0 < np.abs(got - want).max() < 3.0


def test_a_launch_of_another_model_imports_none_of_it():
    import subprocess

    code = ("import sys, client_tpu.models as zoo; zoo._import_all(); "
            "assert 'kimi_linear' in zoo.model_names(); "
            "hit = [m for m in sys.modules if 'kimi_linear' in m "
            "or 'latent_moe' in m or 'ops.kda' in m]; assert not hit, hit")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
