"""The contract between the generative scheduler and a served decoder
(``client_tpu/models/decoder.py``): every registered decoder derives from the
base, the scheduler reads each member plainly (no probe, no fall-back), the
base's chunked step is K single steps, and the two variables that selected
what the code now decides are gone from the registry."""

import copy

import numpy as np
import pytest

from client_tpu import config as envcfg
from client_tpu.engine import TpuEngine
from client_tpu.engine.generative import GenerativeScheduler
from client_tpu.models import build_repository
from client_tpu.models.decoder import (DECODE_ARGS, DECODE_CHUNK_ARGS,
                                       PREFILL_ARGS, DecoderBackend)

SERVED = ["tiny_gpt", "tiny_gpt_long", "tiny_gpt_oracle", "evabyte",
          "tiny_gpt_mc", "moe_gpt_mc", "pangu_moe", "kimi_linear"]
# What `GenerativeScheduler.__init__` reads of a backend before it starts its
# worker; a backend that hides one cannot be scheduled.
READ_AT_CONSTRUCTION = ["max_streams", "max_seq_len", "arena_rows",
                        "init_arena", "prefill_fn", "decode_fn",
                        "donate_argnums", "prefill_static_argnums",
                        "decode_static_argnums", "prefill_piece",
                        "cache_rows", "transition_due", "wave_stats",
                        "stream_record"]


@pytest.fixture(scope="module")
def engines():
    built = {}

    def engine(name):
        # (Built once a name: ``setdefault`` would build, and leak, an engine
        # a call, and a live ``tiny_gpt`` arena is what tests/test_costs.py
        # reconciles the census against in the same worker.)
        if name not in built:
            built[name] = TpuEngine(build_repository([name]))
        return built[name]

    yield engine
    for eng in built.values():
        eng.shutdown()


@pytest.mark.parametrize("name", ["tiny_gpt", "kimi_linear"])
def test_warming_a_served_decoder_compiles_no_full_context_apply(engines,
                                                                 name):
    """The launcher's ``--warmup`` warms a generative model's programs
    through its scheduler; the model-level ``apply`` no request reaches is
    left alone (8.5 s of a published-size launch: PERF.md section 6, PR
    34)."""
    model = engines(name)._schedulers[name].model
    model.warmup()
    assert model._apply is not None and not model._compiled


@pytest.mark.parametrize("name", SERVED)
def test_a_served_decoder_declares_the_whole_contract(engines, name):
    sched = engines(name)._schedulers[name]
    assert isinstance(sched, GenerativeScheduler)
    backend = sched.model.backend
    assert isinstance(backend, DecoderBackend)
    for member in READ_AT_CONSTRUCTION + ["vocab", "default_max_tokens",
                                          "kv_shards", "transition_fn",
                                          "decode_chunk_fn"]:
        assert hasattr(backend, member), member
    # What the scheduler holds is what the backend declared.
    free, dummy = backend.arena_rows(backend.max_streams)
    assert (sched._rows_init, sched._dummy) == (list(free), dummy)
    assert (sched._piece_len, sched._piece_lanes) == (
        backend.prefill_piece or (0, 0))
    assert sched._cache_rows == backend.cache_rows
    assert sched._transition_due == backend.transition_due
    assert len(sched._wave_stats) == len(backend.wave_stats)
    # A stream's record: off unless declared, and then kept piece by piece.
    assert sched._record == backend.stream_record
    assert not backend.stream_record or backend.prefill_piece
    assert set(backend.cache_leaves) < set(sched._arena)
    # Layer kinds: none declared means every layer reads rows and is its own
    # index; declared, each kind's leaves are as deep as its layers.
    assert set(backend.state_leaves) < set(sched._arena)
    if backend.layer_kinds is None:
        assert backend.state_leaves == ()
        assert backend._layer_kind(3) == ("rows", 3)
    else:
        kinds = backend.layer_kinds
        assert set(kinds) == {"rows", "state"}
        assert [backend._layer_kind(li) for li in range(len(kinds))] == [
            (kind, kinds[:li].count(kind)) for li, kind in enumerate(kinds)]
        for leaves, kind in ((backend.cache_leaves, "rows"),
                             (backend.state_leaves, "state")):
            assert leaves
            for leaf in leaves:
                assert sched._arena[leaf].shape[0] == kinds.count(kind)
                assert sched._arena[leaf].shape[1] == backend.max_streams + 1
    assert (sched._transition is None) == (backend.transition_fn is None)
    assert sched.arena_shards() == backend.kv_shards
    # `sample`, `k` and the arena stand where the argument lists say.
    assert backend.donate_argnums == (1,) == (DECODE_ARGS.index("arena"),)
    assert PREFILL_ARGS[backend.prefill_static_argnums[0]] == "sample"
    assert DECODE_ARGS[backend.decode_static_argnums[0]] == "sample"
    assert [DECODE_CHUNK_ARGS[i] for i in
            backend.decode_chunk_static_argnums] == ["sample", "k"]


class _Hiding:
    """A backend seen through a wall with one member missing."""

    def __init__(self, backend, hidden):
        self._backend, self._hidden = backend, hidden

    def __getattr__(self, name):
        if name == self._hidden:
            raise AttributeError(name)
        return getattr(self._backend, name)


@pytest.mark.parametrize("hidden", READ_AT_CONSTRUCTION)
def test_the_scheduler_has_no_fallback_for_a_missing_member(engines, hidden):
    sched = engines("tiny_gpt")._schedulers["tiny_gpt"]
    model = copy.copy(sched.model)
    model.backend = _Hiding(sched.model.backend, hidden)
    with pytest.raises(AttributeError, match=hidden):
        GenerativeScheduler(model, sched.stats)


@pytest.mark.parametrize("name", ["ATTN_IMPL", "GEN_PIPELINE"])
def test_a_deleted_variable_is_not_registered(name, monkeypatch):
    full = "CLIENT_TPU_" + name
    monkeypatch.setenv(full, "1")
    assert full not in envcfg.registered()
    for read in (envcfg.env_str, envcfg.env_int):
        with pytest.raises(KeyError):
            read(full)


def test_chunked_decode_is_refused_where_a_transition_may_fall():
    from client_tpu.models.evabyte import EvaByteBackend

    with pytest.raises(NotImplementedError, match="one wave a dispatch"):
        EvaByteBackend().decode_chunk_fn()


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
@pytest.mark.parametrize("sample", [False, True])
def test_the_chunked_step_is_k_single_steps(attn_impl, sample):
    import jax

    from client_tpu.models.generate import TinyGptBackend

    backend = TinyGptBackend(name="c", n_layers=2, d_model=64, n_heads=4,
                             d_ff=128, vocab=64, max_seq_len=32,
                             max_streams=4, attn_impl=attn_impl)
    params = backend.place_params(backend._init_params())
    rows = np.asarray([2, 0, 4], np.int32)          # two streams, one padded
    lens = np.asarray([3, 5, 0], np.int32)
    seeds = np.asarray([7, 11, 0], np.int32)
    temps = np.asarray([0.8, 0.0, 0.0], np.float32)
    top_ks = np.asarray([8, 0, 0], np.int32)
    top_ps = np.asarray([0.9, 1.0, 1.0], np.float32)

    def arena():
        fresh = backend.init_arena(4)
        return {**fresh, "tok": fresh["tok"].at[rows].set(rows + 1)}

    step = jax.jit(backend.decode_fn(),
                   static_argnums=backend.decode_static_argnums)
    chunk = jax.jit(backend.decode_chunk_fn(),
                    static_argnums=backend.decode_chunk_static_argnums)
    want_arena, want = arena(), []
    for i in range(3):
        want_arena, nxt = step(params, want_arena, rows, lens + i, seeds,
                               temps, top_ks, top_ps, sample)
        want.append(np.asarray(nxt))
    got_arena, got = chunk(params, arena(), rows, lens, seeds, temps, top_ks,
                           top_ps, sample, 3)
    np.testing.assert_array_equal(np.asarray(got), np.stack(want))
    for leaf in ("k", "v", "tok"):
        np.testing.assert_allclose(np.asarray(got_arena[leaf]),
                                   np.asarray(want_arena[leaf]), atol=1e-6)


# -- the piece's head runs only where a prompt ends (PR 51) ---------------------

# The backends that run the decoder's piece frame, at their tiny presets with
# a stream's record where they keep one, and what they declare for lanes.
FRAME_BACKENDS = {
    "pangu_moe": ("pangu_moe", "PanguMoeBackend", {}, 1),
    "kimi_linear": ("kimi_linear", "KimiLinearBackend", {}, 2),
    "smallthinker": ("smallthinker", "SmallThinkerBackend",
                     {"record": True}, 2),
    "nemotron_h": ("nemotron_h", "NemotronHBackend", {"record": True}, 2),
    "ouro": ("ouro", "OuroBackend", {"record": True}, 2),
}


def _frame_backend(name):
    import importlib

    module, cls, options, lanes = FRAME_BACKENDS[name]
    backend = getattr(importlib.import_module(f"client_tpu.models.{module}"),
                      cls)(**options)
    assert backend.prefill_piece[1] == lanes
    assert type(backend).prefill_fn is DecoderBackend.prefill_fn
    assert backend.piece_ends
    return backend


@pytest.mark.parametrize("sample", [False, True])
@pytest.mark.parametrize("name", sorted(FRAME_BACKENDS))
def test_a_piece_computes_its_head_only_where_a_lane_ends(name, sample):
    """One piece program, run on the same operands with no lane's ``ends``
    set, with every lane's and with one lane's: the cache, ring and state
    leaves and the record's words (the routing of **every** piece) are
    bit-equal whatever ``ends`` says; where a lane ends the tokens, the
    slots' tokens and the logit bits are the head's as it stood in the open
    (``_logits`` of each lane's last valid row, ``_served``, the token
    choice, ``logit_bits``), for the lane that goes on beside it too; where
    none does they are zeros.  Lane 0 holds a full piece (what the program
    cannot tell from a prompt that goes on)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models.decoder import (RECORD_LOGITS, choose_tokens,
                                           logit_bits)

    be = _frame_backend(name)
    piece, lanes = be.prefill_piece
    width, logits_at = be.stream_record, be.stream_record - 1 - RECORD_LOGITS
    params = be.place_params(be._init_params())
    rng = np.random.default_rng(5)
    rows = np.asarray([2, 0][:lanes], np.int32)
    lens = np.asarray([piece, 5][:lanes], np.int32)
    starts = np.zeros(lanes, np.int32)
    ids = rng.integers(1, be.vocab, (lanes, piece)).astype(np.int32)
    ids *= np.arange(piece) < lens[:, None]
    sampling = (np.asarray([7, 11][:lanes], np.int32),
                np.full(lanes, 0.8 if sample else 0.0, np.float32),
                np.full(lanes, 8, np.int32), np.full(lanes, 0.9, np.float32))
    step = jax.jit(be.prefill_fn(), static_argnums=be.prefill_static_argnums)

    def run(ends):
        arena, out = step(params, be.init_arena(3), rows, ids, lens,
                          *sampling, sample, starts,
                          np.asarray(ends[:lanes], np.int32))
        out = np.asarray(out)
        rec = out[lanes:].reshape(lanes, piece, width) if width else None
        return {k: np.asarray(v) for k, v in arena.items()}, out[:lanes], rec

    def open_head(p, arena, rows, ids, lens, starts):
        _, x, _ = be.piece_hidden_fn()(p, arena, rows, ids, lens, starts)
        at = lens - 1 + piece * jnp.arange(lanes)
        logits = be._served(be._logits(p, x[at]))
        tokens = choose_tokens(logits, *sampling[:1], starts + lens,
                               *sampling[1:], sample)
        return tokens, logit_bits(logits, tokens, RECORD_LOGITS)

    want_tokens, want_bits = (np.asarray(a) for a in jax.jit(open_head)(
        params, be.init_arena(3), rows, ids, lens, starts))
    none, every, one = run([0, 0]), run([1, 1]), run([0, 1][-lanes:])
    for (arena, tokens, rec), ended in ((none, False), (every, True),
                                        (one, True)):
        for leaf in arena:
            if leaf != "tok":
                assert np.array_equal(arena[leaf], every[0][leaf]), leaf
        assert np.array_equal(tokens, want_tokens * ended)
        assert np.array_equal(arena["tok"][rows], want_tokens * ended)
        if rec is None:
            continue
        assert np.array_equal(rec[..., :logits_at], every[2][..., :logits_at])
        bits = rec[..., logits_at:]
        for lane in range(lanes):
            last = np.arange(piece) == lens[lane] - 1
            assert np.array_equal(bits[lane][last][0],
                                  want_bits[lane] * ended)
            assert not bits[lane][~last].any()
    # (The routing's words say something in every row that held a token.)
    if every[2] is not None and logits_at:
        assert none[2][0, :, :logits_at].any()


def test_a_backend_with_its_own_piece_program_takes_no_ends(engines):
    """``piece_ends`` goes with the frame: the one backend that writes its own
    piece program (its head is 320 ids) says so, and the scheduler hands it
    ``starts`` alone."""
    import inspect

    from client_tpu.models.evabyte import EvaByteBackend

    sched = engines("evabyte")._schedulers["evabyte"]
    backend = sched.model.backend
    assert type(backend).prefill_fn is EvaByteBackend.prefill_fn \
        is not DecoderBackend.prefill_fn
    assert backend.prefill_piece and not backend.piece_ends
    assert not sched._piece_ends
    assert list(inspect.signature(backend.prefill_fn()).parameters)[-1] \
        == PREFILL_ARGS[-3] == "starts"
    frame = engines("kimi_linear")._schedulers["kimi_linear"]
    assert frame._piece_ends and PREFILL_ARGS[-2:] == ("ends", "wave")
    # (Behind ``ends``, the wave that a backend's piece programs carry,
    # ``piece_wave``: this one's carry the top bucket's, since PR 60.)
    assert list(inspect.signature(
        frame.model.backend.prefill_fn()).parameters)[-2:] == ["ends", "wave"]
    assert frame.model.backend.piece_wave
    assert frame._piece_wave == frame.model.backend.max_streams
    # A one-shot backend's program takes neither.
    shot = engines("tiny_gpt")._schedulers["tiny_gpt"]
    assert not shot._piece_len and not shot._piece_ends
