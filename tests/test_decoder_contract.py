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
    yield lambda name: built.setdefault(
        name, TpuEngine(build_repository([name])))
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
