"""The dense decoder with a recurrent state (models/granite_hybrid.py) at a
tiny preset on the CPU that keeps the period (ten layers, the attention layer
at index 5), seeded weights, Pallas interpreted: the served path (chunked
pieces, single-step waves through the state, the tail and the key/value rows,
two lanes a piece, a prompt cut at every piece boundary) against the plain
reference's token-by-token forward pass on logits; what a slot's life asks of
a state that ``lens`` cannot mask (a reused slot, padded lanes, padded
positions); the scheduler's counters and a stream's record."""

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

import family  # noqa: E402

from client_tpu.engine import TpuEngine  # noqa: E402
from client_tpu.engine.repository import ModelRepository  # noqa: E402
from client_tpu.engine.types import InferRequest  # noqa: E402
from client_tpu.models.granite_hybrid import GraniteHybridBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402

fam = family.load("granite_hybrid")
kimi = family.load("kimi_linear")
SEQ, PIECE, N = 64, 16, 44
TOL_F32 = 2e-5
# bfloat16 matmuls, rows and convolution tail against the float32 reference
# at the tiny preset (logits of magnitude 0.1).
TOL_BF16 = 0.01


def backend(**kw):
    """The tiny preset (one period: nine state layers round an attention
    layer), pieces of two chunks."""
    return GraniteHybridBackend(**{"seed": 5, "max_seq_len": SEQ,
                                   "piece": PIECE, "chunk": 8, **kw})


def f32_params(be):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  be._init_params())


def ids_of(n=N, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def reference(be, ids):
    return np.asarray(fam.backend_forward(f32_params(be), be, ids, len(ids)))


_PROGRAMS = {}


class Served:
    """A backend's jitted piece and wave (built once a set of constructor
    arguments), an arena of three slots and the junk one, and the
    teacher-forced walk of a prompt through them."""

    def __init__(self, **kw):
        key = tuple(sorted(kw.items()))
        if key not in _PROGRAMS:
            be = backend(**kw)
            _PROGRAMS[key] = (be, be.place_params(be._init_params()),
                              jax.jit(be.piece_hidden_fn()),
                              jax.jit(be._decode_hidden_fn()))
        self.be, self.params, self.piece, self.hidden = _PROGRAMS[key]
        self.arena = self.be.init_arena(3)

    def prefill(self, ids, slot=1):
        be, logits = self.be, []
        for st in range(0, len(ids), be.piece):
            n = min(be.piece, len(ids) - st)
            buf = np.zeros((1, be.piece), np.int32)
            buf[0, :n] = ids[st:st + n]
            self.arena, x, _ = self.piece(
                self.params, self.arena, np.asarray([slot], np.int32), buf,
                np.asarray([n], np.int32), np.asarray([st], np.int32))
            logits.append(np.asarray(be._logits(self.params, x[:n])))
        return np.concatenate(logits)

    def wave(self, tokens, lengths, slots):
        """One wave of the given lanes and one more, padded onto the junk
        slot.  -> logits ``[lanes, vocab]``."""
        tok = self.arena["tok"]
        for s, t in zip(slots, tokens):
            tok = tok.at[s].set(int(t))
        self.arena = {**self.arena, "tok": tok}
        self.arena, x = self.hidden(
            self.params, self.arena, np.asarray([*slots, 3], np.int32),
            np.asarray([*lengths, 0], np.int32))
        return np.asarray(self.be._logits(self.params, x))[:len(slots)]

    def walk(self, ids, n_prompt, slot=1):
        logits = [self.prefill(ids[:n_prompt], slot)]
        for t in range(n_prompt, len(ids)):
            logits.append(self.wave([ids[t]], [t], [slot]))
        return np.concatenate(logits)

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
    forward pass at every position: the four multipliers, the one group, the
    gated norm over all channels and the tied head among them."""
    srv = Served(dtype="float32", attn_impl=attn_impl)
    ids = ids_of()
    assert np.abs(srv.walk(ids, n_prompt) - reference(srv.be, ids)
                  ).max() < TOL_F32


def test_bfloat16_pieces_then_waves_match_the_reference():
    srv = Served()
    ids = ids_of()
    assert np.abs(srv.walk(ids, 37) - reference(srv.be, ids)).max() < TOL_BF16


def test_the_flash_piece_repeats_its_narrow_key_heads_exactly():
    """Heads of 64 in groups of two through the flash kernel (interpreted):
    the key heads repeated to the query heads by a one-hot product, which
    leaves every value as it was."""
    kw = dict(dtype="float32", attn_impl="fused", d_model=256, n_heads=4,
              n_kv_heads=2, mamba_head_dim=64, attention_multiplier=1 / 64)
    ids = ids_of()
    flash, dense = Served(attention_impl="flash", **kw), Served(**kw)
    assert flash.be.head_dim == 64
    got = flash.walk(ids, 37)
    assert np.abs(got - dense.walk(ids, 37)).max() < TOL_F32
    assert np.abs(got - reference(flash.be, ids)).max() < TOL_F32


def test_the_full_context_apply_is_the_reference_too():
    be = backend(dtype="float32")
    apply, params = be.make_apply_params()
    ids = ids_of()
    with jax.default_matmul_precision("highest"):
        out = apply(params, {"INPUT_IDS": jnp.asarray(ids)})
    assert np.abs(np.asarray(out["logits"]) - reference(be, ids)
                  ).max() < TOL_F32


def test_a_layer_is_a_mixer_and_a_feed_forward_and_the_head_is_the_embedding():
    be = backend()
    assert be.layer_kinds == ("state",) * 5 + ("rows",) + ("state",) * 4
    assert [be._layer_kind(li) for li in (0, 4, 5, 6, 9)] == [
        ("state", 0), ("state", 4), ("rows", 0), ("state", 5), ("state", 8)]
    arena = jax.eval_shape(lambda: be.init_arena(3))
    assert arena["k"].shape == arena["v"].shape == (1, 4, SEQ, 2 * 16)
    # One group: all its four heads of [16, 16] side by side.
    assert (be.n_groups, be.norm_groups, be.pack) == (1, 1, 4)
    assert arena["s"].shape == (9, 4, 1, 16, 64)
    assert arena["s"].dtype == jnp.float32
    assert arena["conv"].shape == (9, 4, 3 * (4 * 16 + 2 * 1 * 16))
    assert be.cache_rows_by_kind(11) == (0, 11, 0)
    assert not be.piece_wave and be.prefill_piece == (PIECE, 2)
    p = be._init_params()
    assert "head" not in p and p["embed"].shape == (96, 64)
    assert abs(p["embed"].scale * be.embedding_multiplier - 1) < 1e-9
    for lp, kind in zip(p["layers"], be.layer_kinds):
        assert ("wxbc" in lp) == (kind == "state")
        assert ("wq" in lp) == (kind == "rows")
        assert {"ln", "wo", "ln2", "wgu", "wd"} <= set(lp)
        assert "router" not in lp and "eu" not in lp
    assert (be.attn_scale, be.residual_multiplier, be.embedding_multiplier,
            be.logits_scaling) == (0.0625, 0.22, 12.0, 8.0)
    with pytest.raises(ValueError):
        backend(layer_types=("mamba",) * 4)             # no attention layer
    with pytest.raises(ValueError):
        backend(layer_types=("mamba", "attention", "experts"))
    with pytest.raises(ValueError):
        backend(piece=24)                               # no whole chunks


# -- a slot's life ---------------------------------------------------------------

def test_a_slot_reused_by_a_second_stream_equals_a_fresh_slot():
    """The first stream leaves rows, a state and a tail behind; the second
    stream's first piece starts from zeros whatever is there."""
    kw = dict(dtype="float32", attn_impl="fused")
    first, second = ids_of(50, seed=1), ids_of(30, seed=2)
    used, fresh = Served(**kw), Served(**kw)
    used.walk(first, 41)
    assert np.array_equal(used.walk(second, 21), fresh.walk(second, 21))
    for a, b in zip(used.slot(1), fresh.slot(1)):
        assert np.array_equal(a, b)


def test_padded_lanes_and_padded_positions_leave_a_live_slot_bit_for_bit():
    """Slot 0 holds a live stream.  A piece of another slot, padded past its
    prompt, and waves whose other lanes are padded (on the junk slot) leave
    slot 0's state and tail bit for bit; the padded piece leaves its own
    slot the state of its valid positions alone."""
    kw = dict(dtype="float32", attn_impl="fused")
    srv = Served(**kw)
    srv.walk(ids_of(30, seed=3), 20, slot=0)
    before = srv.slot(0)
    other = ids_of(21, seed=4)          # a piece of 16 and one of 5 + 11 padded
    srv.walk(np.concatenate([other, ids_of(4, seed=5)]), 21, slot=1)
    for a, b in zip(srv.slot(0), before):
        assert np.array_equal(a, b)
    padded, exact = Served(**kw), Served(piece=32, chunk=1, **kw)
    padded.prefill(other, slot=2)
    exact.prefill(other, slot=2)
    for a, b in zip(padded.slot(2), exact.slot(2)):
        assert np.abs(a - b).max() < 2e-5


# Two lanes of one piece call: (tokens prefilled before the call, tokens the
# call holds) a lane, each lane's prompt its own.
TWO_LANES = {
    "two_first_pieces": [(0, 16), (0, 16)],
    "a_first_piece_beside_a_third": [(0, 16), (32, 9)],
    "a_lane_shorter_than_the_convolution": [(0, 2), (16, 16)],
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


@pytest.mark.parametrize("case", sorted(TWO_LANES))
def test_two_lanes_of_a_piece_are_the_lanes_alone_bit_for_bit(case):
    """float32: two prompts' pieces in one program (the projections and the
    feed-forwards over both lanes' positions at once, the mixers a lane at a
    time, each from its own slot and its own ``start``) leave every slot's
    states, tails and rows, and give every valid position's activations,
    exactly as the same two pieces do one lane at a time."""
    kw = dict(dtype="float32", attn_impl="fused")
    pair, solo = Served(**kw), Served(**kw)
    lanes = [(ids_of(48, seed=30 + i), before, held)
             for i, (before, held) in enumerate(TWO_LANES[case])]
    for srv in (pair, solo):
        for slot, (ids, before, _) in enumerate(lanes):
            if before:
                srv.prefill(ids[:before], slot=slot)
    pair.arena, x, _ = pair.piece(pair.params, pair.arena,
                                  *_piece_args(lanes, [0, 1]))
    x = np.asarray(x)
    for slot, lane in enumerate(lanes):
        solo.arena, x1, _ = solo.piece(solo.params, solo.arena,
                                       *_piece_args([lane], [slot]))
        held = lane[2]
        assert np.array_equal(x[slot * PIECE:slot * PIECE + held],
                              np.asarray(x1)[:held])
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
    assert be.stream_record == 9

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
        # (The logits' bits: a head over two lanes' rows may sum a product
        # in another order than over one lane's, an ulp apart.)
        both, solo = (np.ascontiguousarray(r[:lane[2]]).view(np.float32)
                      for r in (record[i], record1[0]))
        assert np.abs(both - solo).max() < 1e-6
        # A lane's logits stand in its last valid row and nowhere else.
        assert (record[i, :lane[2] - 1] == 0).all()
        assert record[i, lane[2] - 1].any()


@pytest.mark.parametrize("piece,chunk", [(64, 16), (32, 8), (8, 8), (8, 2)])
def test_a_prompt_cut_at_every_piece_boundary_gives_one_state(piece, chunk):
    """A prompt of 40 positions as one piece, two and five: the same state,
    the same tail and the same rows, the token-by-token walk's (a piece of
    chunks of one position)."""
    ids = ids_of(40, seed=6)
    want = Served(dtype="float32", piece=64, chunk=1)
    want.prefill(ids)
    got = Served(dtype="float32", piece=piece, chunk=chunk)
    got.prefill(ids)
    for a, b in zip(got.slot(1), want.slot(1)):
        assert np.abs(a - b).max() < 2e-5
    for leaf in ("k", "v"):
        assert np.abs(np.asarray(got.arena[leaf][:, 1, :40], np.float32)
                      - np.asarray(want.arena[leaf][:, 1, :40], np.float32)
                      ).max() < 2e-5


def test_a_wave_of_mixed_lengths_equals_the_streams_alone():
    """Three streams of 5, 19 and 33 positions advanced together, four waves,
    give each the logits it gets alone in a wave of one."""
    kw = dict(dtype="float32", attn_impl="fused")
    streams = [ids_of(n + 4, seed=20 + i) for i, n in enumerate((5, 19, 33))]
    lens = [len(s) - 4 for s in streams]
    both, alone = Served(**kw), Served(**kw)
    for srv in (both, alone):
        for slot, (s, n) in enumerate(zip(streams, lens)):
            srv.prefill(s[:n], slot=slot)
    for step in range(4):
        got = both.wave([s[n + step] for s, n in zip(streams, lens)],
                        [n + step for n in lens], [0, 1, 2])
        for slot, (s, n) in enumerate(zip(streams, lens)):
            want = alone.wave([s[n + step]], [n + step], [slot])
            assert np.abs(got[slot] - want[0]).max() < 2e-6


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


@pytest.fixture(scope="module")
def served():
    name = "granite_served"
    be = backend(name=name, attn_impl="fused", max_streams=2, record=True)
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
        for p, (_, m), rec in zip(prompts, PLAN, records):
            assert rec.shape == (len(p) + m - 1, be.stream_record)
            assert rec.dtype == np.int32
            _, logits = kimi.record_columns(rec, 0)
            assert (logits[len(p) - 1:, 0] >= logits[len(p) - 1:, 1:].max(-1)
                    ).all()                            # greedy: the row's best

    def test_the_reference_accepts_every_token(self, served):
        """Each stream teacher-forced on its own tokens, on its served
        logits: at the tiny preset's own limits (logits of magnitude 0.1)."""
        (be, prompts, together, alone, _, _, rec_together, rec_alone,
         _) = served
        params = f32_params(be)

        def rows_fn(prompt, emitted, _words):
            seq = np.asarray(prompt + emitted, np.int32)
            return (fam.backend_forward(params, be, seq[:-1], len(emitted)),
                    np.zeros(len(seq) - 1))

        for i, (p, (_, m)) in enumerate(zip(prompts, PLAN)):
            one = {"prompts": [p], "max_tokens": m,
                   "concurrent": [together[i]], "solo": [alone[i]],
                   "concurrent_record": [rec_together[i]],
                   "solo_record": [rec_alone[i]]}
            verdict = kimi.judge(one, rows_fn, 0, margin=TOL_BF16,
                                 logit_rms_alone=TOL_BF16 / 3,
                                 logit_rms_together=TOL_BF16 / 3,
                                 logit_max=TOL_BF16, tie=0.0)
            assert verdict["ok"], verdict
            assert verdict["tokens_checked"] == 2 * m

    def test_piece_positions_and_rows_reach_the_counters(self, served):
        _, _, _, _, before, after, *_ = served
        c = {k: after[k] - before[k] for k in after}
        pieces = sum(-(-n // PIECE) for n, _ in PLAN) * 2
        assert c["prefill_pieces"] == pieces
        assert c["prefill_positions_valid"] == 2 * sum(n for n, _ in PLAN)
        assert c["prefill_positions_padded"] == pieces * PIECE \
            - c["prefill_positions_valid"]
        assert c["fetched_waves"] > 0 and c["fetched_lanes_live"] > 0
        # One attention layer reads every live position's row; no ring; no
        # piece carries a wave.
        assert c["fetched_rows_global"] == c["fetched_positions_valid"]
        assert c["fetched_rows_window"] == 0
        assert c.get("fetched_waves_carried", 0) == 0
        # A prompt's pieces score its triangle in the one attention layer,
        # however the prompt was cut.
        assert c["prefill_pairs_global"] == 2 * sum(
            n * (n + 1) // 2 for n, _ in PLAN)
        assert c["prefill_pairs_window"] == 0


@pytest.mark.parametrize("how", ["ring", "kv_shards", "latent"])
def test_a_scale_of_the_models_is_refused_where_no_step_takes_it(how):
    """``attn_scale`` reaches the wave's kernel over one chip's whole-context
    rows and nothing else: every other step is refused when it is built."""
    be = backend()
    assert be.attn_scale is not None
    if how == "kv_shards":
        be.kv_shards = 2
    elif how == "latent":
        be.latent_attention = 8
    with pytest.raises(NotImplementedError, match="attn_scale"):
        be._decode_attend(ring=how == "ring")
    assert callable(backend()._decode_attend())


def test_a_launch_of_another_model_imports_none_of_it():
    code = ("import sys; from client_tpu.models import build_repository; "
            "build_repository(['simple']); "
            "print(any(m in sys.modules for m in "
            "('client_tpu.models.granite_hybrid', 'client_tpu.models.mamba2', "
            "'client_tpu.ops.ssd')))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().endswith("False"), out.stdout + out.stderr
    from client_tpu.models import model_names

    assert "granite_hybrid" in model_names()
