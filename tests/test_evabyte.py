"""EvaByte (EVA chunked linear attention) at the tiny preset on the CPU,
seeded weights, Pallas interpreted: the plain reference against a literal
loop of the layer's sets, the served path (prefill by pieces, decode waves
through the cache, window dumps) against the reference, the scheduler end to
end, and the benchmark family's comparison and arithmetic."""

import json
import math
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
from client_tpu.models import evabyte as eva_mod  # noqa: E402
from client_tpu.models.evabyte import EvaByteBackend  # noqa: E402
from client_tpu.models.generate import TinyGptBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402

fam = family.load("evabyte")
W, C, SEQ = 32, 4, 128
# bfloat16 matmuls and a bfloat16 cache against the float32 reference, at
# the tiny preset: logits of magnitude 4 agree to about 0.02.
TOL = 0.06


def backend(**kw):
    kw = {"seed": 3, "max_seq_len": SEQ, "window": W, "chunk": C, **kw}
    return EvaByteBackend(**kw)


@pytest.fixture(scope="module")
def setup():
    be = backend()
    params = be._init_params()
    as_f32 = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                    params)
    ids = np.random.default_rng(0).integers(0, 320, SEQ).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(fam.backend_forward(as_f32, be, ids, SEQ))
    return be, params, as_f32, ids, ref


# -- the reference against the layer's sets, literally -----------------------

def literal_eva(q, k, v, phi, mu, window, chunk):
    """out_i = softmax over {q_i.k_m : m in E_i} and {q_i.k~_t : t in C_i},
    one position and one head at a time, in float64."""
    n, h, d = q.shape
    out = np.zeros((n, h, d))
    for hh in range(h):
        for i in range(n):
            j = i // window
            keys, vals = [], []
            for m in range(n):
                if m // window == j and m <= i:
                    keys.append(k[m, hh])
                    vals.append(v[m, hh])
            for t in range((j * window) // chunk):
                km = k[t * chunk:(t + 1) * chunk, hh]
                vm = v[t * chunk:(t + 1) * chunk, hh]
                a = np.exp(km @ phi[hh] - (km @ phi[hh]).max())
                a /= a.sum()
                keys.append(km.mean(0) + mu[hh])
                vals.append(a @ vm)
            s = np.asarray(keys) @ q[i, hh] / math.sqrt(d)
            p = np.exp(s - s.max())
            out[i, hh] = (p / p.sum()) @ np.asarray(vals)
    return out


@pytest.mark.parametrize("n", [5, 32, 70])
def test_reference_attention_is_the_literal_sets(n):
    rng = np.random.default_rng(n)
    q, k, v = (rng.standard_normal((n, 2, 8)) for _ in range(3))
    phi, mu = rng.standard_normal((2, 8)), rng.standard_normal((2, 8))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fam.eva_attention(
            *(jnp.asarray(a, jnp.float32) for a in (q, k, v, phi, mu)),
            16, 4, q_block=7))
    np.testing.assert_allclose(got, literal_eva(q, k, v, phi, mu, 16, 4),
                               atol=2e-5)


def test_weights_are_already_bfloat16(setup):
    be, params, as_f32, _, _ = setup
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == jnp.bfloat16
    # ... so the reference's float32 copy holds exactly what the chip holds
    again = jax.tree_util.tree_map(
        lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32),
        as_f32)
    for a, b in zip(jax.tree_util.tree_leaves(as_f32),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(a, b)


def restacked(served):
    """The host's stacked tree again, of the served one (``split_layers``
    undone): a layer's leaves stacked, those served ``[out, in]`` turned
    back."""
    layers = served["layers"]
    return {**served, "layers": {
        name: np.stack([np.asarray(lp[name]).T if name in eva_mod.OUT_IN
                        else np.asarray(lp[name]) for lp in layers])
        for name in layers[0]}}


def bits(a):
    a = np.asarray(a)
    assert a.dtype == jnp.bfloat16
    return a.view(np.uint16)


LEAVES = ["embed", "lnf", "head"] + [
    f"layers/{name}" for name in ("ln1", "ln2", "wq", "wk", "wv", "wo", "wg",
                                  "wu", "wd", "phi", "mu")]


def leaf_at(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


@pytest.fixture(scope="module")
def placed_again(setup):
    be, params, *_ = setup
    placed = be.place_params(params)
    assert isinstance(placed["layers"], list)
    assert len(placed["layers"]) == be.n_layers
    return restacked(placed)


@pytest.mark.parametrize("path", LEAVES)
def test_the_placed_tree_holds_the_hosts_weights_bit_for_bit(
        setup, placed_again, path):
    """The reference reads ``_init_params()``, the chip what
    ``place_params`` made of it (a layer's leaves of their own, the three
    projections of its input as ``[out, in]``): re-stacked, the same bits."""
    _, params, *_ = setup
    assert jax.tree_util.tree_structure(placed_again) \
        == jax.tree_util.tree_structure(params)
    want, got = leaf_at(params, path), leaf_at(placed_again, path)
    assert got.shape == want.shape
    np.testing.assert_array_equal(bits(got), bits(want))


def test_a_checkpoint_in_the_stacked_form_still_loads(setup, tmp_path):
    """A checkpoint holds the stacked tree ``_init_params`` returns; a
    backend pointed at it serves those weights, split as the seeded ones
    are."""
    from client_tpu.engine.checkpoint import save_params

    be, params, _, ids, _ = setup
    other = backend(seed=11)                 # its own init must not show
    other.weights_path = save_params(str(tmp_path / "stacked"), params)
    apply, placed = other.make_apply_params()
    again = restacked(placed)
    for path in LEAVES:
        np.testing.assert_array_equal(bits(leaf_at(again, path)),
                                      bits(leaf_at(params, path)))
    seeded = be.make_apply_params()[1]
    np.testing.assert_array_equal(
        np.asarray(jax.jit(apply)(placed, {"INPUT_IDS": ids[:40]})["logits"]),
        np.asarray(jax.jit(apply)(seeded, {"INPUT_IDS": ids[:40]})["logits"]))


def test_apply_params_against_the_reference(setup):
    be, _, _, ids, ref = setup
    apply, placed = be.make_apply_params()
    for n in (1, 3, 100):
        got = np.asarray(jax.jit(apply)(placed, {"INPUT_IDS": ids[:n]})
                         ["logits"])
        assert got.shape == (n, 8, 320)
        if n == 100:
            assert np.abs(got - ref[:100]).max() < TOL


# -- pieces, waves and dumps through the cache --------------------------------

class Served:
    """The backend's jitted steps driven as the scheduler drives them, one
    stream in slot ``row``, teacher-forced on ``ids``."""

    def __init__(self, be, params, skip_dump=False):
        self.be, self.p = be, be.place_params(params)
        self.arena = be.init_arena(be.max_streams)
        self.piece = jax.jit(be.piece_logits_fn())
        self.step = jax.jit(be.decode_logits_fn())
        self.dump = jax.jit(be.transition_fn())
        self.skip_dump = skip_dump

    def run(self, ids, n_prompt, n_decode, row=1):
        """[(position, logits[8, 320])]: after the prompt's last byte and
        after each teacher-forced decoded byte."""
        be, rows = self.be, np.asarray([row], np.int32)
        for start in range(0, n_prompt, be.window):
            m = min(be.window, n_prompt - start)
            buf = np.zeros((1, be.window), np.int32)
            buf[0, :m] = ids[start:start + m]
            self.arena, logits = self.piece(
                self.p, self.arena, rows, buf, np.asarray([m], np.int32),
                np.asarray([start], np.int32))
        out = [(n_prompt - 1, np.asarray(logits[0]))]
        for n in range(n_prompt, n_prompt + n_decode):
            if n > n_prompt and be.transition_due(n) and not self.skip_dump:
                self.arena = self.dump(self.p, self.arena, rows,
                                       np.asarray([n], np.int32))
            self.arena = {**self.arena,
                          "tok": self.arena["tok"].at[row].set(int(ids[n]))}
            self.arena, logits = self.step(self.p, self.arena, rows,
                                           np.asarray([n], np.int32))
            out.append((n, np.asarray(logits[0])))
        return out


def worst(out, ref):
    return max(float(np.abs(lg - ref[i]).max()) for i, lg in out)


# (prompt, decoded): over three windows; shorter than one chunk; a prompt
# ending on a boundary (its last piece leaves summaries, no dump follows);
# a decode crossing a boundary; a prompt of whole windows then two dumps.
CONTEXTS = [(70, 40), (3, 20), (64, 10), (30, 10), (32, 70), (100, 27)]


@pytest.mark.parametrize("impls", [("einsum", "reference"),
                                   ("flash", "fused")])
@pytest.mark.parametrize("n_prompt,n_decode", CONTEXTS)
def test_pieces_then_waves_match_the_full_forward(setup, impls, n_prompt,
                                                  n_decode):
    """Logits of all 8 heads, every position from the prompt's end on."""
    _, params, _, ids, ref = setup
    be = backend(attention_impl=impls[0], attn_impl=impls[1])
    out = Served(be, params).run(ids, n_prompt, n_decode)
    assert out[0][1].shape == (8, 320)
    assert worst(out, ref) < TOL


def test_a_skipped_dump_fails(setup):
    be, params, _, ids, ref = setup
    assert worst(Served(be, params).run(ids, 30, 10), ref) < TOL
    assert worst(Served(be, params, skip_dump=True).run(ids, 30, 10),
                 ref) > 10 * TOL


@pytest.mark.parametrize("term", ["mu", "phi"])
def test_a_dropped_summary_term_fails(setup, term, monkeypatch):
    """Serving without ``mu`` (k~ = the plain mean) or without ``phi``
    (uniform pooling) is another model: it leaves the tolerance as soon as a
    query sees a summary, and not before."""
    be, params, _, ids, ref = setup
    true = eva_mod.summarize

    def faulty(k, v, phi, mu, chunk):
        return true(k, v, phi * (term != "phi"), mu * (term != "mu"), chunk)

    monkeypatch.setattr(eva_mod, "summarize", faulty)
    out = Served(backend(), params).run(ids, 70, 10)
    assert worst(out, ref) > 3 * TOL
    inside = Served(backend(), params).run(ids, 20, 10)   # no summary seen
    assert worst(inside, ref) < TOL


@pytest.mark.parametrize("n_sum", [0, 8, 24])
@pytest.mark.parametrize("heads,width", [(2, 128), (4, 64)])
def test_piece_attention_flash_reads_cache_rows_as_they_lie(heads, width,
                                                            n_sum):
    """``_piece_attention`` through the flash kernel (q, keys and values
    handed over as ``[B, n, H*D]`` cache rows, no operand transposed) against
    its einsum path, at the published head width of 128 and at 64 (two heads
    to a 128-lane tile), with an empty, a partly and a fully used prefix."""
    be = backend(n_heads=heads, d_model=heads * width,
                 attention_impl="flash")
    be.flash_blocks = (16, 32)
    ks = jax.random.split(jax.random.PRNGKey(width + n_sum), 5)
    pre = be.slot_rows - W
    shape = lambda n: (2, n, heads, width)               # noqa: E731
    q = jax.random.normal(ks[0], shape(W))
    k_c, v_c = (jax.random.normal(k, shape(W)).astype(jnp.bfloat16)
                for k in ks[1:3])
    pk, pv = (jax.random.normal(k, shape(pre)).astype(jnp.bfloat16)
              for k in ks[3:])
    seen = jnp.asarray([n_sum, 0], jnp.int32)
    got = be._piece_attention(q, k_c, v_c, pk, pv, seen)
    be.attention_impl = "einsum"
    want = be._piece_attention(q, k_c, v_c, pk, pv, seen)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # The kernel takes q in the cache's bfloat16, as the served path does.
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2


def test_batched_lanes_equal_solo(setup):
    """Two prompts prefilled in one two-lane piece call and decoded in one
    wave give, lane for lane, the logits each gives alone (to the rounding
    of a matmul of another shape: a bfloat16 activation may round the other
    way) and the same byte."""
    _, params, _, ids, _ = setup
    be = backend(prefill_lanes=2)
    solo = [Served(be, params).run(ids[o:], 40, 6, row=r)
            for o, r in ((0, 0), (10, 2))]
    both = Served(be, params)
    rows = np.asarray([0, 2], np.int32)
    for start in (0, 32):
        m = min(32, 40 - start)
        buf = np.zeros((2, 32), np.int32)
        buf[0, :m], buf[1, :m] = ids[start:start + m], \
            ids[10 + start:10 + start + m]
        both.arena, logits = both.piece(
            both.p, both.arena, rows, buf, np.asarray([m, m], np.int32),
            np.asarray([start, start], np.int32))
    got = [[np.asarray(logits[0])], [np.asarray(logits[1])]]
    for n in range(40, 46):
        both.arena = {**both.arena, "tok": both.arena["tok"].at[rows].set(
            jnp.asarray([ids[n], ids[10 + n]]))}
        both.arena, logits = both.step(both.p, both.arena, rows,
                                       np.asarray([n, n], np.int32))
        got[0].append(np.asarray(logits[0]))
        got[1].append(np.asarray(logits[1]))
    for lane in (0, 1):
        for (_, want), have in zip(solo[lane], got[lane]):
            np.testing.assert_array_equal(want.argmax(-1), have.argmax(-1))
            np.testing.assert_allclose(want, have, atol=0.02)


def test_cache_hooks():
    be = backend()
    assert be.prefill_piece == (32, 1)
    assert be.sums_per_window == 8 and be.slot_rows == 64
    assert be.cache_rows(0) == (0, 0)
    assert be.cache_rows(31) == (0, 31)
    assert be.cache_rows(32) == (8, 0)
    assert be.cache_rows(100) == (24, 4)
    assert [n for n in range(130) if be.transition_due(n)] == [32, 64, 96, 128]
    big = EvaByteBackend(n_layers=1, d_model=4096, n_heads=32, d_ff=11008,
                         max_seq_len=32768, window=2048, chunk=16,
                         max_streams=16)
    assert big.slot_rows == 4096            # 15 x 128 + 2048 = 3968 used
    assert big.cache_rows(32767) == (1920, 2047)
    with open(os.path.join(BENCH, "configs", "evabyte_6b5.json")) as f:
        assert json.load(f)["serve"]["cache_slot_rows"] == big.slot_rows


# -- the scheduler, end to end -------------------------------------------------

MODEL = "eva_t"


def stream(engine, prompt, max_tokens, model=MODEL):
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
        parameters={"max_tokens": max_tokens, "seed": 0}), cb)

    def join():
        assert done.wait(300), "stream did not finish"
        assert not err, err
        return tokens

    return join


def gen_profile(engine, model=MODEL):
    sched = engine._schedulers[model]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if not sched._streams and not sched._inflight \
                and sched._rec.open is sched._rec.span[spans.S_IDLE]:
            break
        time.sleep(0.005)
    snap = engine.profile_snapshot(model=model)
    return snap["models"][f"{model}:1"]["generative"], snap["compiles"]


PLAN = [(3, 12), (30, 40), (70, 20), (64, 12), (100, 20)]


@pytest.fixture(scope="module", params=[("einsum", "reference", 1),
                                        ("flash", "fused", 2)],
                ids=["xla", "pallas"])
def served(request, setup):
    """The plan's streams sent together, then one at a time."""
    impl, attn, lanes = request.param
    name = f"{MODEL}_{attn}"      # the profiler's totals are by model name
    be = backend(name=name, attention_impl=impl, attn_impl=attn,
                 prefill_lanes=lanes)
    repo = ModelRepository()
    repo.register_backend(be)
    engine = TpuEngine(repo)
    engine._schedulers[name].warmup()
    _, compiles0 = gen_profile(engine, name)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 320, n).tolist() for n, _ in PLAN]
    joins = [stream(engine, p, m, name) for p, (_, m) in zip(prompts, PLAN)]
    together = [j() for j in joins]
    alone = [stream(engine, p, m, name)()
             for p, (_, m) in zip(prompts, PLAN)]
    profile, compiles1 = gen_profile(engine, name)
    yield be, prompts, together, alone, profile, compiles0, compiles1
    engine.shutdown()


class TestScheduler:
    def test_together_equals_alone_token_for_token(self, served):
        _, _, together, alone, *_ = served
        assert together == alone
        assert [len(t) for t in together] == [m for _, m in PLAN]

    def test_the_reference_accepts_every_byte(self, served, setup):
        be, prompts, together, alone, *_ = served
        _, _, as_f32, _, _ = setup
        # one max_tokens for the probe's shape: judge each stream's first 12
        probe = {"prompts": prompts, "max_tokens": 12,
                 "concurrent": [t[:12] for t in together],
                 "solo": [t[:12] for t in alone]}
        with jax.default_matmul_precision("highest"):
            verdict = fam.check(as_f32, probe, be)
        assert verdict["ok"], verdict
        assert verdict["worst_margin_below_max"] < fam.MARGIN / 2
        # and the whole of the longest decode (two dumps), teacher-forced
        seq = np.asarray(prompts[1] + together[1], np.int32)
        with jax.default_matmul_precision("highest"):
            rows = np.asarray(fam.backend_forward(
                as_f32, be, seq[:-1], len(together[1])))[:, 0]
        below = [float(r.max() - r[t]) for r, t in zip(rows, together[1])]
        assert max(below) < fam.MARGIN / 2

    def test_pieces_dumps_and_rows_are_counted(self, served):
        _, prompts, together, _, profile, *_ = served
        c = profile["counters"]
        pieces = sum(-(-len(p) // W) for p in prompts)
        assert c["prompts_admitted"] == 2 * len(PLAN)
        assert c["prefill_pieces"] == 2 * pieces
        assert c["first_tokens"] == 2 * len(PLAN)
        # a dump wherever a stream decoded its way onto a boundary
        dumps = sum(1 for p, t in zip(prompts, together)
                    for n in range(len(p) + 1, len(p) + len(t))
                    if n % W == 0)
        assert dumps == 2
        assert c["transitions"] == 2 * dumps
        span = profile["spans"]["gen.transition_dispatch"]
        assert span["count"] == 2 * dumps and span["total_ns"] > 0
        # every decode step read cache_rows(context) rows, by kind
        want_sum = want_exact = want_pos = 0
        for p, t in zip(prompts, together):
            for n in range(len(p), len(p) + len(t) - 1):
                want_sum += (n // W) * (W // C)
                want_exact += n % W
                want_pos += n
        assert c["fetched_rows_summary"] == 2 * want_sum
        assert c["fetched_rows_exact"] == 2 * want_exact
        assert c["fetched_positions_valid"] == 2 * want_pos

    def test_pieces_interleave_with_waves(self, served):
        """Prompts of up to four pieces were prefilled while other streams
        decoded: more prefill dispatches than prompts, and waves that held
        fewer lanes than streams were open."""
        _, prompts, _, _, profile, *_ = served
        spans_ = profile["spans"]
        assert spans_["gen.prefill_dispatch"]["count"] > len(prompts)
        c = profile["counters"]
        assert c["fetched_lanes_live"] < len(PLAN) * c["fetched_waves"]

    def test_nothing_compiles_after_warm_up(self, served):
        *_, compiles0, compiles1 = served
        assert compiles1["count"] == compiles0["count"]


def test_a_backend_without_the_hooks_is_served_as_before():
    """``TinyGptBackend`` declares none of the hooks: one-shot prefill over
    its power-of-two prompt buckets, the same count of programs, the new
    counters at 0 and the new span never opened."""
    repo = ModelRepository()
    repo.register_backend(TinyGptBackend(name="gpt_t", n_layers=1,
                                         max_seq_len=32, max_streams=4))
    engine = TpuEngine(repo)
    try:
        sched = engine._schedulers["gpt_t"]
        assert sched._piece_len == 0 and sched._transition is None
        assert sched._prompt_buckets == [1, 2, 4, 8, 16, 32]
        assert sched._admit_lane == 4
        sched.warmup()
        _, before = gen_profile(engine, "gpt_t")
        assert stream(engine, [1, 2, 3], 5, "gpt_t")() == \
            stream(engine, [1, 2, 3], 5, "gpt_t")()
        profile, after = gen_profile(engine, "gpt_t")
        assert after["count"] == before["count"]
        assert sched._prefill._cache_size() == 6      # one per prompt bucket
        assert sched._decode._cache_size() == 3       # wave buckets 1, 2, 4
        for name in ("fetched_rows_exact", "fetched_rows_summary",
                     "prompts_admitted", "prefill_pieces", "transitions"):
            assert profile["counters"][name] == 0
        assert profile["spans"]["gen.transition_dispatch"]["count"] == 0
        assert profile["spans"]["gen.prefill_dispatch"]["count"] == 2
    finally:
        engine.shutdown()


def test_the_limit_message_of_a_backend_that_prefills_by_pieces(served):
    be = served[0]
    repo = ModelRepository()
    repo.register_backend(backend(name="eva_v"))
    engine = TpuEngine(repo)
    try:
        err, done = [], threading.Event()

        def cb(resp):
            err.append(resp.error)
            done.set()

        engine.async_infer(InferRequest(
            model_name="eva_v",
            inputs={"INPUT_IDS": np.zeros(120, np.int32)},
            parameters={"max_tokens": 9}), cb)
        assert done.wait(60)
        assert "prefilled 32 positions a piece" in str(err[0])
    finally:
        engine.shutdown()
    assert be.max_seq_len == SEQ


# -- the benchmark family ------------------------------------------------------

def test_check_rejects_a_corrupted_stream(setup):
    be, _, as_f32, ids, ref = setup
    prompt = ids[:40].tolist()
    # teacher-force the reference's own greedy bytes
    emitted, seq = [], list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(5):
            row = np.asarray(fam.backend_forward(
                as_f32, be, np.asarray(seq, np.int32), 1))[0, 0]
            emitted.append(int(row.argmax()))
            seq.append(emitted[-1])
        good = {"prompts": [prompt], "concurrent": [emitted],
                "solo": [emitted], "max_tokens": 5}
        assert fam.check(as_f32, good, be)["ok"]
        bad = list(emitted)
        bad[2] = (bad[2] + 7) % 320
        verdict = fam.check(as_f32, dict(good, concurrent=[bad]), be)
        assert not verdict["ok"]
        assert verdict["worst_margin_below_max"] > fam.MARGIN
        short = fam.check(as_f32, dict(good, solo=[emitted[:3]]), be)
        assert not short["ok"] and not short["all_tokens_arrived"]
        failed = fam.check(as_f32, dict(good, solo=[{"error": "x"}]), be)
        assert not failed["ok"]


def test_step_arithmetic_by_hand():
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "vocab_size": 5, "num_pred_heads": 2, "window_size": 4,
           "chunk_size": 2}
    weights = 2 * (4 * 64 + 3 * 128) + 8 * 10           # 1360
    # decode: 3 lanes, 6 live rows each
    flops, nbytes = fam.decode_step(cfg, 3, 6)
    assert flops == 2 * (2 * 3 * 640 + 4 * 3 * 6 * 8) + 2 * 3 * 80
    assert nbytes == weights * 2 + 2 * 2 * 3 * 7 * 8 * 2 + 3 * 8 * 2
    assert fam.decode_attention(cfg, 3, 6) == (4 * 3 * 6 * 8,
                                               2 * 3 * 7 * 8 * 2)
    # a piece: 1 lane, 3 valid positions, 2 summaries
    flops, nbytes = fam.piece_step(cfg, 1, 3, 2)
    attn = 4 * (3 * 4 / 2 + 3 * 2) * 8
    assert flops == 2 * (2 * 4 * 640 + attn) + 2 * 80
    assert nbytes == weights * 2 + 4 * 8 * 2 + 4 * 4 + 2 * 2 * 5 * 8 * 2
    assert fam.piece_attention(cfg, 1, 3, 2) == (attn, (8 + 12) * 8 * 2)
    # a dump: 4 rows read, 2 summaries written, K and V, 2 layers
    assert fam.dump_step(cfg) == (2 * 4 * 4 * 8, 2 * 2 * 6 * 8 * 2)
