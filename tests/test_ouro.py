"""``ouro``: a layer stack that runs four passes over one set of weights, a
key/value cache for every pass (models/ouro.py; the pass axis of
models/decoder.py's two frames), at the preset of the configuration's
``rehearse_cpu`` (3 layers, hidden 64, 4 heads of 16, 4 passes) on the CPU.

The reference is ``benchmark/models/ouro.py``'s plain forward (its copy of
the model: float32, no cache, no pieces, no kernels); the served side is the
backend's own piece and wave programs over one arena.
"""

import functools
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
from client_tpu.models.ouro import OuroBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402

fam = family.load("ouro")
kimi = family.load("kimi_linear")

PIECE, SEQ, PASSES, LAYERS = 16, 48, 4, 3
TOL_F32, TOL_BF16 = 2e-4, 0.08


def backend(**kw):
    kw = {"seed": 5, "max_seq_len": SEQ, "piece": PIECE, "dtype": "float32",
          **kw}
    return OuroBackend(**kw)


def f32_params(be):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  be._init_params())


def ids_of(n, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def reference(be, ids, threshold=1.0):
    """(logits of every position, the pass each left at)."""
    logits, chosen = fam.backend_forward(f32_params(be), be, ids, len(ids),
                                         threshold=threshold)
    return np.asarray(logits), chosen


class Served:
    """A backend's jitted piece and wave, an arena of three slots and the
    junk one, and the teacher-forced walk of a prompt through them."""

    def __init__(self, be, params=None):
        self.be = be
        self.params = params or be.place_params(be._init_params())
        self.arena = be.init_arena(3)
        self.piece = jax.jit(be.piece_hidden_fn())
        self.hidden = jax.jit(be._decode_hidden_fn())

    def pieces(self, prompts, slots):
        """The prompts' pieces, all lanes to a program: -> logits of every
        prompt's positions."""
        be, out = self.be, [[] for _ in prompts]
        for st in range(0, max(map(len, prompts)), be.piece):
            lanes = [i for i, p in enumerate(prompts) if len(p) > st]
            buf = np.zeros((len(lanes), be.piece), np.int32)
            lens = []
            for row, i in enumerate(lanes):
                n = min(be.piece, len(prompts[i]) - st)
                buf[row, :n] = prompts[i][st:st + n]
                lens.append(n)
            self.arena, x, _ = self.piece(
                self.params, self.arena,
                np.asarray([slots[i] for i in lanes], np.int32), buf,
                np.asarray(lens, np.int32),
                np.full(len(lanes), st, np.int32))
            logits = np.asarray(be._logits(self.params, x))
            for row, (i, n) in enumerate(zip(lanes, lens)):
                out[i].append(logits[row * be.piece:row * be.piece + n])
        return [np.concatenate(o) for o in out]

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

    def walk(self, streams, n_prompts, slots):
        """Every stream's ids: its prompt by pieces (all of them to a
        program), the rest wave by wave, the streams a wave together.  ->
        logits of every position, a stream each."""
        out = [[lg] for lg in self.pieces(
            [s[:n] for s, n in zip(streams, n_prompts)], slots)]
        at = list(n_prompts)
        while any(a < len(s) for a, s in zip(at, streams)):
            live = [i for i, s in enumerate(streams) if at[i] < len(s)]
            rows = self.wave([streams[i][at[i]] for i in live],
                             [at[i] for i in live], [slots[i] for i in live])
            for row, i in zip(rows, live):
                out[i].append(row[None])
                at[i] += 1
        return [np.concatenate(o) for o in out]


# -- pieces and waves against the plain reference -----------------------------------

@functools.lru_cache(maxsize=None)
def served_of(attn_impl):
    """One backend and its compiled programs an ``attn_impl`` (a program a
    lane count: the tests share them)."""
    return Served(backend(attn_impl=attn_impl))


# A prompt that ends inside a piece, on a piece's edge and past two pieces.
PROMPTS = [9, 16, 37]


@pytest.mark.parametrize("n_prompt", PROMPTS)
def test_float32_pieces_then_waves_match_the_plain_reference(n_prompt):
    """A stream alone (the oracle's arena), then five waves: the logits of
    every position are the full forward's, through four passes' caches."""
    served = served_of("reference")
    ids = ids_of(n_prompt + 5, seed=n_prompt)
    want, chosen = reference(served.be, ids)
    got, = served.walk([ids], [n_prompt], [1])
    assert np.abs(got - want).max() < TOL_F32
    assert (chosen == PASSES - 1).all()


def test_several_streams_to_a_wave_and_several_prompts_to_a_piece():
    """The three streams go through the piece program together (three lanes,
    then the longest alone) and through every wave together, on the kernels'
    arena: each is the reference's alone."""
    served = served_of("fused")
    streams = [ids_of(n + 3, seed=20 + n) for n in PROMPTS]
    got = served.walk(streams, PROMPTS, [0, 1, 2])
    for ids, g in zip(streams, got):
        assert np.abs(g - reference(served.be, ids)[0]).max() < TOL_F32


def test_the_full_context_apply_is_the_reference_too():
    be = backend()
    apply, params = be.make_apply_params()
    ids = ids_of(40, seed=4)
    got = np.asarray(apply(params, {"INPUT_IDS": jnp.asarray(ids)})["logits"])
    assert np.abs(got - reference(be, ids)[0]).max() < TOL_F32


def test_one_layer_list_and_passes_times_layers_cache_leaves():
    be = backend()
    params = be._init_params()
    assert len(params["layers"]) == LAYERS
    assert {"exit_w", "exit_b"} <= set(params)
    arena = jax.eval_shape(lambda: be.init_arena(4))
    assert arena["k"].shape == arena["v"].shape == (PASSES * LAYERS, 5, SEQ,
                                                    64)
    assert be.passes == PASSES and be.cache_leaves == ("k", "v")
    # Pass t's entry of layer l lies at t x layers + l.
    assert [be._layer_kind(li) for li in (0, 2, 3, 11)] == [
        ("rows", 0), ("rows", 2), ("rows", 3), ("rows", 11)]
    assert be.cache_rows_by_kind(10) == (0, PASSES * LAYERS * 10, 0)
    assert be.prefill_piece == (PIECE, 2)


def test_a_pass_reads_its_own_rows_and_no_other_passes():
    """After a prompt, every (pass, layer) entry of the slot holds other
    rows: no pass wrote into another's cache or left its own empty."""
    served = served_of("reference")
    served.arena = served.be.init_arena(3)
    served.walk([ids_of(20, seed=6)], [20], [1])
    k = np.asarray(served.arena["k"][:, 1, :20])
    assert (np.abs(k).max(axis=(1, 2)) > 0).all()
    for a in range(PASSES * LAYERS):
        for b in range(a):
            assert np.abs(k[a] - k[b]).max() > 1e-3
    assert not np.asarray(served.arena["k"][:, 2]).any()


# -- the exit rule -----------------------------------------------------------------

def test_the_exit_rule_takes_the_last_pass_at_threshold_one_and_an_earlier_below():
    be = backend()
    ids = ids_of(24, seed=7)
    full, chosen = reference(be, ids, threshold=1.0)
    assert (chosen == PASSES - 1).all()
    early, sooner = reference(be, ids, threshold=0.3)
    assert (sooner <= chosen).all() and (sooner < PASSES - 1).any()
    moved = sooner < PASSES - 1
    assert np.abs(early - full)[moved].max() > 1e-3
    assert np.abs(early - full)[~moved].max(initial=0.0) == 0.0
    # By hand: gates of 0.2, 0.5, 0.9 (and the last, which takes the rest)
    # cumulate 0.2, 0.6, 0.96, 1.
    gates = np.tile(np.array([[0.2], [0.5], [0.9], [0.1]]), (1, 5))
    for threshold, want in ((0.1, 0), (0.2, 0), (0.5, 1), (0.9, 2),
                            (0.97, 3), (1.0, 3)):
        assert (fam.exit_pass(gates, threshold) == want).all(), threshold


def test_a_threshold_under_one_is_refused_at_load():
    with pytest.raises(ValueError, match="different passes"):
        backend(early_exit_threshold=0.9)
    assert backend(early_exit_threshold=1).passes == PASSES


# -- the frames' pass axis ---------------------------------------------------------

class _Plain(OuroBackend):
    """Nothing between passes: two passes are then the layers listed twice."""

    def _between_passes(self, p, x):
        return x


def test_two_passes_are_the_same_layers_listed_twice_with_a_cache_each():
    """The frames' own test: a backend with ``passes = 2`` over two layers is,
    bit for bit, the one-pass backend over those layers listed twice: the
    same logits of every position through pieces and waves, the same arena
    (leaf entry ``t x layers + l`` is the listed-twice model's layer ``t x 2 +
    l``)."""
    kw = dict(attn_impl="fused", dtype="bfloat16")
    looped = _Plain(seed=5, max_seq_len=SEQ, piece=PIECE, n_layers=2,
                    passes=2, **kw)
    listed = _Plain(seed=5, max_seq_len=SEQ, piece=PIECE, n_layers=4,
                    passes=1, **kw)
    params = looped.place_params(looped._init_params())
    twice = {**params, "layers": params["layers"] * 2}
    a, b = Served(looped, params), Served(listed, twice)
    n_prompts = [21, 32]
    streams = [ids_of(n + 3, seed=30 + n) for n in n_prompts]
    got_a = a.walk(streams, n_prompts, [0, 2])
    got_b = b.walk(streams, n_prompts, [0, 2])
    for x, y in zip(got_a, got_b):
        np.testing.assert_array_equal(x, y)
    for leaf in ("k", "v", "tok"):
        np.testing.assert_array_equal(np.asarray(a.arena[leaf]),
                                      np.asarray(b.arena[leaf]))


def test_one_pass_is_the_frame_every_other_backend_runs():
    """``passes`` 1 and nothing between passes are the contract's defaults,
    and a body's place in the walk is then its layer's number."""
    from client_tpu.models.decoder import DecoderBackend

    assert DecoderBackend.passes == 1
    assert DecoderBackend._between_passes(None, None, "x") == "x"
    be = backend(passes=1)
    seen = []
    be._walk_layers({"layers": ["a", "b", "c"]},
                    lambda c, lp, li: seen.append((lp, li)) or c, ("x",))
    assert seen == [("a", 0), ("b", 1), ("c", 2)]
    be = backend(passes=2)
    seen.clear()
    be._walk_layers({"layers": ["a", "b", "c"], "lnf": jnp.ones(1)},
                    lambda c, lp, li: seen.append((lp, li)) or c,
                    (jnp.ones(1),))
    assert seen == [("a", 0), ("b", 1), ("c", 2), ("a", 3), ("b", 4),
                    ("c", 5)]


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


# (prompt length, tokens): inside a piece, on its edge, past two pieces.
PLAN = [(9, 4), (16, 3), (37, 4)]


@pytest.fixture(scope="module")
def served():
    name = "ouro_served"
    be = backend(name=name, dtype="bfloat16", max_streams=2, record=True)
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
    yield be, prompts, together, alone, before, after
    engine.shutdown()


class TestScheduler:
    def test_together_equals_alone_token_for_token(self, served):
        _, _, together, alone, *_ = served
        assert [t for t, _ in together] == [t for t, _ in alone]
        assert [len(t) for t, _ in together] == [m for _, m in PLAN]

    def test_the_record_is_the_logits_and_the_reference_accepts_them(
            self, served):
        be, prompts, together, alone, *_ = served
        params = f32_params(be)

        def rows_fn(prompt, emitted, _words):
            seq = np.asarray(prompt + emitted, np.int32)
            logits, _ = fam.backend_forward(params, be, seq[:-1],
                                            len(emitted))
            return logits, np.zeros(len(seq) - 1)

        for i, (p, (_, m)) in enumerate(zip(prompts, PLAN)):
            assert together[i][1].shape == (len(p) + m - 1, be.stream_record)
            one = {"prompts": [p], "max_tokens": m,
                   "concurrent": [together[i][0]], "solo": [alone[i][0]],
                   "concurrent_record": [together[i][1]],
                   "solo_record": [alone[i][1]]}
            verdict = kimi.judge(one, rows_fn, 0, margin=TOL_BF16,
                                 logit_rms_alone=TOL_BF16 / 3,
                                 logit_rms_together=TOL_BF16 / 3,
                                 logit_max=TOL_BF16, tie=0.0)
            assert verdict["ok"], verdict
            assert verdict["tokens_checked"] == 2 * m

    def test_passes_rows_and_pieces_reach_the_counters(self, served):
        be, _, _, _, before, after = served
        c = {k: after[k] - before[k] for k in after}
        assert c["fetched_waves"] > 0
        assert c["fetched_passes"] == PASSES * c["fetched_waves"]
        # Every layer of every pass reads every live position's row.
        assert c["fetched_rows_global"] == (PASSES * LAYERS
                                            * c["fetched_positions_valid"])
        assert c["fetched_rows_window"] == 0
        assert c["prefill_pieces"] == 2 * sum(-(-n // PIECE)
                                              for n, _ in PLAN)

    def test_a_backend_of_one_pass_counts_one_a_wave(self):
        from client_tpu.models.generate import TinyGptBackend

        repo = ModelRepository()
        repo.register_backend(TinyGptBackend(name="gpt_one_pass"))
        engine = TpuEngine(repo)
        try:
            stream(engine, [1, 2, 3], 4, "gpt_one_pass")()
            c = counters(engine, "gpt_one_pass")
            assert c["fetched_passes"] == c["fetched_waves"] > 0
        finally:
            engine.shutdown()


# -- the benchmark family ----------------------------------------------------------

def _config():
    from traffic import load_json

    return load_json(os.path.join(BENCH, "configs", "ouro_2b6.json"))


def test_the_configuration_file_is_the_catalog_row_but_for_its_cut():
    cfg = _config()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["num_hidden_layers"] == 12 and cfg["total_ut_steps"] == 4
    for key in ("assumed", "departures", "deployment", "memory"):
        assert cfg[key]


def test_the_backend_built_from_the_file_is_the_issues_arena():
    import serve

    cfg = _config()
    kw = serve.backend_kwargs(cfg, 7, 1536)
    be = OuroBackend(name="o", **kw)
    arena = jax.eval_shape(lambda: be.init_arena(be.max_streams))
    assert arena["k"].shape == (48, 19, 1536, 2048)
    assert arena["k"].dtype == jnp.bfloat16
    cache = 2 * np.prod(arena["k"].shape) * 2
    assert 11.47e9 < cache < 11.49e9
    weights = sum(int(np.prod(leaf.shape)) for leaf in
                  jax.tree_util.tree_leaves(
                      be._init_params(),
                      is_leaf=lambda x: hasattr(x, "shape")))
    assert 817e6 < weights < 819e6                    # one set of layers
    assert be.prefill_piece == (512, 2) and be.stream_record == 9
    with pytest.raises(ValueError, match="early_exit_threshold"):
        OuroBackend(name="o", **{**kw, "early_exit_threshold": 0.5})


def test_the_readers_and_the_familys_arithmetic_check_out():
    import check_ouro

    assert check_ouro.main() == 0


def _judged(**fault):
    """A probe of one stream whose record is the reference's own logits, with
    one fault: the verdict."""
    be = backend()
    prompt, toks = ids_of(9, seed=1).tolist(), []
    params = f32_params(be)
    seq = list(prompt)
    rows = []
    for _ in range(4):
        logits, _ = fam.backend_forward(params, be, np.asarray(seq), 1)
        row = np.asarray(logits[0], np.float32)
        tok = int(row.argmax())
        rows.append(np.concatenate([[row[tok]], row[:8]]))
        toks.append(tok)
        seq.append(tok)
    record = np.zeros((len(prompt) + 3, 9), np.float32)
    record[len(prompt) - 1:] = np.stack(rows)
    record[-1, 3] += fault.get("one_logit", 0.0)
    record[len(prompt) - 1:] += fault.get("every_logit", 0.0)
    if fault.get("short"):
        record = record[:-1]
    rec = record.view(np.int32).tolist()
    if fault.get("token"):
        toks[-1] = (toks[-1] + 1) % be.vocab
    probe = {"prompts": [prompt], "max_tokens": 4, "concurrent": [toks],
             "solo": [toks], "concurrent_record": [rec], "solo_record": [rec]}
    return fam.check(params, probe, be)


@pytest.mark.parametrize("fault,ok", [
    ({}, True), ({"one_logit": 2 * fam.LOGIT_MAX}, False),
    ({"every_logit": 1.5 * fam.LOGIT_RMS_ALONE}, False),
    ({"token": True}, False), ({"short": True}, False)])
def test_the_comparison_fails_by_each_of_its_limits(fault, ok):
    verdict = _judged(**fault)
    assert verdict["ok"] is ok, verdict
    assert "tie" not in verdict


_CONTROL_KW = dict(seed=5, max_seq_len=32, piece=PIECE, n_layers=2, passes=3)
_CONTROL_IDS = ids_of(21, seed=9)


@functools.lru_cache(maxsize=None)
def _as_published(dtype):
    return Served(OuroBackend(**_CONTROL_KW, dtype=dtype)).walk(
        [_CONTROL_IDS], [18], [1])[0]


@pytest.mark.parametrize("which", ["one_pass", "shared_cache", "norm_at_end",
                                   "pre_norm", "unrotated", "e4m3"])
def test_a_control_is_the_served_backend_with_one_thing_wrong(which):
    """Each control serves other logits than the backend it derives from, on
    the same weights, by far more than the float32 tolerance; the reference it
    is judged by stays the published model (four passes)."""
    import ouro_controls as controls

    dtype = "bfloat16" if which == "e4m3" else "float32"
    wrong = controls.CONTROLS[which](**_CONTROL_KW, dtype=dtype)
    assert isinstance(wrong, OuroBackend)
    a = _as_published(dtype)
    b, = Served(wrong).walk([_CONTROL_IDS], [18], [1])
    assert np.abs(a - b).max() > 1e-2
    want, _ = fam.backend_forward(f32_params(wrong), wrong, _CONTROL_IDS,
                                  len(_CONTROL_IDS))
    if which != "e4m3":
        assert np.abs(a - np.asarray(want)).max() < TOL_F32


def test_a_launch_of_another_model_imports_none_of_it():
    code = ("import sys; from client_tpu.models import build_repository; "
            "build_repository(['simple']); "
            "print('client_tpu.models.ouro' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().endswith("False"), out.stdout + out.stderr
    from client_tpu.models import model_names

    assert "ouro" in model_names()
