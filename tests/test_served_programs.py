"""The programs of the served decoders the benchmark measures are the ones
they were.

**The rule.**  For each family's decode wave and prefill (or piece) program at
a tiny preset, lowered for the TPU with the kernels in, two hashes stand
recorded: of the StableHLO with the kernels' bodies taken out (a body carries
the source path of the checkout) and, apart, of the Pallas kernels' own jaxprs
with their grids.  A PR that means to change one of these programs records the
new hash here, says so in CHANGES.md, and shows on the chip that the compiled
program is the one it was or better; one that does not mean to has a guard.
``prefill`` is the program of a piece backend's declared lanes, ``prefill_<n>``
another lane count of its ladder.  Beside each entry: the PR that last recorded
(StableHLO, kernels).

    python - <<'X'          # to record: run from the repo root
    import tests.test_served_programs as t; t.record()
    X
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

RECORDED = {
    # (a parallel block over a ring of two pieces and whole-context rows, a
    # group of four query heads a key head, half the experts held: PR 53)
    ("cohere_moe", "decode"): ("781b759572c6fa34", "a9b441acdfa65310"),  # 53, 53
    # (the piece programs of PR 56, ``cohere_moe``'s and ``smallthinker``'s:
    # each carries a wave behind the piece's rows, models/decoder.py
    # ``piece_wave``; their wave programs, and every other backend's
    # programs, as they were: with no ``wave`` operand the frame traces to
    # the programs PR 53 and PR 52 recorded, ``PLAIN_PIECES`` below)
    ("cohere_moe", "prefill"): ("98ba8582adfbc1a9", "54ab1ee336ca57f1"),  # 56, 56
    ("evabyte", "decode"): ("4b633014727aa219", "54792ebb84a37cd7"),  # 44, 44
    ("evabyte", "prefill"): ("f73e0dc2333af8de", "e59f91f604bb6804"),  # 42, 42
    # (one period of nine state layers round an attention layer, one group of
    # B and C, heads of 64 in groups of two: PR 59)
    ("granite", "decode"): ("88e556bdc27a1f6b", "176c43380e722e34"),  # 59, 59
    ("granite", "prefill"): ("a237a4c9cfd525f5", "ced90e03e3dd34c2"),  # 59, 59
    ("granite", "prefill_1"): ("b3e8671185e51c68", "1e77ce44a37939cf"),  # 59, 59
    ("gpt", "decode"): ("c7d0eebb4f86770a", "2445b260a28de378"),      # 44, 44
    ("gpt", "prefill"): ("9f74b6f52137fcbf", "813762073b8c861a"),     # 31, 31
    ("kimi", "decode"): ("3c7634b1c3eb0637", "f12c2095739d8cfd"),     # 34, 40
    # (the piece programs of PR 60, ``kimi``'s and ``pangu``'s: each carries a
    # wave behind the piece's rows through the latent cache,
    # models/latent_moe.py ``_piece_rows_layer``, ``kimi``'s through its state
    # layers too; their wave programs as they were, and with no ``wave``
    # operand the frame traces to the programs PR 51 recorded, ``PLAIN_PIECES``
    # below)
    ("kimi", "prefill"): ("02355e9f9b032c8b", "76860703c0fa674b"),    # 60, 60
    ("kimi", "prefill_1"): ("2420b6afc1e0138e", "42a21884ef2c6f7d"),  # 60, 60
    ("nemotron", "decode"): ("1389f36bf7b1b00e", "a626f88ba8d2d94e"),  # 45, 45
    # (the piece programs of PR 58: each carries a wave behind the piece's
    # rows through the ``"state"`` kind too, models/state_layer.py
    # ``_step_slots``; the wave program as it was, and with no ``wave``
    # operand the frame traces to the programs PR 52 and PR 51 recorded,
    # ``PLAIN_PIECES`` below)
    ("nemotron", "prefill"): ("e092785a1c460693", "6e989d52d0c1992c"),  # 58, 58
    ("nemotron", "prefill_1"): ("ccedbbbfc4760089", "b9d9b336795b3b24"),  # 58, 58
    # (three passes over two layers: the pass axis of both frames)
    ("ouro", "decode"): ("a3cdb3b4609e3d6a", "0a13edafef621d9d"),     # 50, 50
    ("ouro", "prefill"): ("70bea758f74145ba", "472369f043e003f9"),    # 52, 50
    ("ouro", "prefill_1"): ("6418e3116c027a48", "54b16b6182cc8ce7"),  # 51, 50
    ("pangu", "decode"): ("b73c536a3102de37", "231955794429a2a6"),    # 33, 40
    # (the piece programs of PR 49: one frame for the four piece backends,
    # models/experts.py ``piece_hidden_fn``; ``pangu``'s and
    # ``smallthinker``'s kernels by the tile of the sorted layout alone,
    # which ``_piece_tile`` now chooses for them too: 32 rows for 64 at
    # these presets' shares, and the hashes PR 33 and PR 43 recorded with
    # the tile held at 64; the programs of PR 51: the frame's head under one
    # conditional on the trailing ``ends``, the kernels as they were)
    # (and PR 60's: it carries a wave, above)
    ("pangu", "prefill"): ("6780a70c2c70bfb4", "f68b86dd9a430b02"),   # 60, 60
    ("smallthinker", "decode"): ("44c3bd43dd161186", "93891480d1ba5ee1"),  # 44, 44
    # (``prefill_1`` is what ``prefill`` was until PR 52 declared two lanes)
    ("smallthinker", "prefill"): ("8a616ab553920ba9", "2b386c908a98390c"),  # 56, 56
    ("smallthinker", "prefill_1"): ("d16dcb5f1e1712d4", "0340085c96da5db9"),  # 56, 56
}

# What the piece frame traces to with no ``wave`` operand for the backends
# whose programs carry one: the programs they served until PR 56 (``nemotron``
# until PR 58, ``kimi`` and ``pangu`` until PR 60; the PR that last recorded
# them beside each; ``nemotron``'s
# two-lane program is PR 52's, a lane's rows behind a barrier where another
# lane follows, models/grouped_query.py ``_lane_by_lane``).  The guard that
# the frame, the lane walk, the state layer, the latent layer and the expert
# layer are what they were for the two families whose piece programs take
# none.
PLAIN_PIECES = {
    ("cohere_moe", "prefill"): ("03df8db14a8ae370", "e1195263e49cba3f"),  # 53, 53
    ("kimi", "prefill"): ("37d5b524b0b73f60", "083a795658c4ced4"),    # 51, 48
    ("kimi", "prefill_1"): ("c1a4f0a1a32b0105", "11c370955249a916"),  # 51, 48
    ("nemotron", "prefill"): ("d02cab991c063cc5", "036da1223ab7372f"),  # 52, 47
    ("nemotron", "prefill_1"): ("50e1142bcff1a77f", "72f715ae5cf8c153"),  # 51, 47
    ("pangu", "prefill"): ("9f81a0e83b3aaa34", "f9c5bf8655c5c096"),   # 51, 49
    ("smallthinker", "prefill"): ("1fe2b7a99c70a34e", "65c5487282cc111d"),  # 52, 52
    ("smallthinker", "prefill_1"): ("19097d0c8dec2ec6", "1cfe8973c0312174"),  # 51, 49
}


def _backend(family):
    if family == "evabyte":
        from client_tpu.models.evabyte import EvaByteBackend

        return EvaByteBackend(seed=3, max_seq_len=128, window=32, chunk=4,
                              attention_impl="flash", attn_impl="fused",
                              prefill_lanes=2)
    if family == "pangu":
        from client_tpu.models.pangu_moe import PanguMoeBackend

        # Heads of whole 128-lane tiles, as the flash pieces need them.
        return PanguMoeBackend(seed=3, n_heads=2, nope_dim=192, rope_dim=64,
                               v_dim=128, kv_rank=128, max_seq_len=32,
                               piece=16, attention_impl="flash",
                               attn_impl="fused")
    if family == "kimi":
        from client_tpu.models.kimi_linear import KimiLinearBackend

        lin = {"kda_layers": [1, 2, 3], "full_attn_layers": [4],
               "num_heads": 2, "head_dim": 128, "short_conv_kernel_size": 4}
        return KimiLinearBackend(seed=3, n_heads=2, nope_dim=192,
                                 rope_dim=64, v_dim=128, kv_rank=128,
                                 linear_attn=lin, max_seq_len=32, piece=16,
                                 attention_impl="flash",
                                 attn_impl="fused")
    if family == "smallthinker":
        from client_tpu.models.smallthinker import SmallThinkerBackend

        # Heads of whole 128-lane tiles, as the flash pieces need them; two
        # periods, a ring of two pieces.
        return SmallThinkerBackend(seed=3, n_layers=8, n_heads=4,
                                   n_kv_heads=2, head_dim=128,
                                   max_seq_len=64, window=32, piece=16,
                                   attention_impl="flash", attn_impl="fused",
                                   record=True)
    if family == "cohere_moe":
        from client_tpu.models.cohere_moe import CohereMoeBackend

        # Heads of whole 128-lane tiles, as the flash pieces need them; a
        # group of four query heads a key head, a ring of two pieces, a share
        # of half the experts.
        return CohereMoeBackend(seed=3, n_heads=8, n_kv_heads=2,
                                head_dim=128, experts_held=4,
                                max_seq_len=64, window=32, piece=16,
                                attention_impl="flash", attn_impl="fused",
                                record=True)
    if family == "nemotron":
        from client_tpu.models.nemotron_h import NemotronHBackend

        # Heads of whole 128-lane tiles, as the flash pieces need them; two
        # state heads of 64 side by side in a row of the state's leaf.
        return NemotronHBackend(seed=3, pattern="MEM*E", n_heads=4,
                                n_kv_heads=2, head_dim=128, mamba_heads=4,
                                mamba_head_dim=64, n_groups=2, state_size=128,
                                max_seq_len=32, piece=16, chunk=8,
                                attention_impl="flash", attn_impl="fused",
                                record=True)
    if family == "granite":
        from client_tpu.models.granite_hybrid import GraniteHybridBackend

        # Heads of 64, two a 128-lane tile (the flash pieces repeat the key
        # heads to the query heads); two state heads of 64 side by side in a
        # row of the state's leaf, one group of B and C for both pairs.
        return GraniteHybridBackend(seed=3, d_model=256, n_heads=4,
                                    n_kv_heads=2, mamba_heads=4,
                                    mamba_head_dim=64, state_size=128,
                                    attention_multiplier=1 / 64,
                                    max_seq_len=32, piece=16, chunk=8,
                                    attention_impl="flash",
                                    attn_impl="fused", record=True)
    if family == "ouro":
        from client_tpu.models.ouro import OuroBackend

        # Heads of whole 128-lane tiles, as the flash pieces need them; two
        # layers, three passes.
        return OuroBackend(seed=3, n_layers=2, passes=3, n_heads=2,
                           n_kv_heads=2, head_dim=128, max_seq_len=32,
                           piece=16, attention_impl="flash",
                           attn_impl="fused", record=True)
    from client_tpu.models.generate import TinyGptBackend

    return TinyGptBackend(attention_impl="flash", attn_impl="fused")


def _program(family, which, plain=False):
    """(function, static and donated argument numbers, abstract arguments);
    ``which``: ``decode``, ``prefill`` (a piece backend's declared lanes) or
    ``prefill_<lanes>``; ``plain``: the piece program with no wave operand,
    of a backend whose programs carry one."""
    be = _backend(family)
    if plain:
        be.piece_wave = False
    if family in ("pangu", "kimi", "smallthinker", "nemotron", "ouro",
                  "cohere_moe", "granite"):
        # (made when asked for)
        params = jax.tree_util.tree_map(
            lambda w: jax.ShapeDtypeStruct(w.shape, jnp.dtype(w.dtype)),
            be._init_params())
    elif family == "evabyte":           # the host's tree, split as placed
        params = jax.eval_shape(lambda: be.split_layers(be._init_params()))
    else:
        params = jax.eval_shape(be._init_params)
    arena = jax.eval_shape(lambda: be.init_arena(4))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    def f32(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32)

    if which == "decode":
        return be.decode_fn(), (be.decode_static_argnums,
                                be.donate_argnums), (
            params, arena, i32(4), i32(4), i32(4), f32(4), i32(4), f32(4),
            False)
    piece = be.prefill_piece
    lanes, width = (piece[1], piece[0]) if piece else (2, 64)
    if which.startswith("prefill_"):    # another lane count of its ladder
        lanes = int(which.split("_")[1])
    args = (params, arena, i32(lanes), i32(lanes, width), i32(lanes),
            i32(lanes), f32(lanes), i32(lanes), f32(lanes), False)
    # A piece's ``starts`` and, for the decoder's own piece frame, ``ends``;
    # behind them the wave that a backend's piece programs carry, at the top
    # bucket (``piece_wave``).
    wave = ((i32(4), i32(4), i32(4), f32(4), i32(4), f32(4)),) if (
        piece and be.piece_wave) else ()
    return be.prefill_fn(), (be.prefill_static_argnums,
                             be.donate_argnums), args + (
        (i32(lanes),) * (1 + be.piece_ends) if piece else ()) + wave


def _kernel_bodies(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.params["jaxpr"])
                       + str(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _kernel_bodies(inner, out)
    return out


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def hashes(family, which, plain=False):
    fn, (static, donated), args = _program(family, which, plain)
    text = jax.jit(fn, static_argnums=static,
                   donate_argnums=donated).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    bodies = _kernel_bodies(
        jax.make_jaxpr(fn, static_argnums=static)(*args).jaxpr, [])
    assert bodies and text.count("tpu_custom_call") >= 1
    return (_digest(re.sub(r'backend_config = "[^"]*"',
                           'backend_config = ""', text)),
            _digest("".join(bodies)))


@pytest.fixture()
def on_the_chips_branches(monkeypatch):
    from client_tpu.engine import backend_init

    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)


@pytest.mark.parametrize("part", ["program", "kernels"])
@pytest.mark.parametrize("family,which", sorted(RECORDED))
def test_the_program_is_the_one_it_was(on_the_chips_branches, family, which,
                                       part):
    got = hashes(family, which)[part == "kernels"]
    assert got == RECORDED[family, which][part == "kernels"], (
        f"{family}'s {which} {part} changed: if that is meant, record {got}")


@pytest.mark.parametrize("part", ["program", "kernels"])
@pytest.mark.parametrize("family,which", sorted(PLAIN_PIECES))
def test_with_no_wave_the_frame_traces_to_the_program_it_was(
        on_the_chips_branches, family, which, part):
    got = hashes(family, which, plain=True)[part == "kernels"]
    assert got == PLAIN_PIECES[family, which][part == "kernels"], (
        f"{family}'s {which} {part} with no wave changed: {got}")


def test_the_piece_frame_is_the_decoders():
    """One piece frame, the decoder's: the expert backends and the dense one
    run it, and none writes a piece program of its own."""
    from client_tpu.models.decoder import DecoderBackend
    from client_tpu.models.experts import ExpertDecoder
    from client_tpu.models.granite_hybrid import GraniteHybridBackend
    from client_tpu.models.ouro import OuroBackend

    for cls in (ExpertDecoder, OuroBackend, GraniteHybridBackend):
        assert cls.piece_hidden_fn is DecoderBackend.piece_hidden_fn
        assert cls.prefill_fn is DecoderBackend.prefill_fn
        assert cls._walk_kinds is DecoderBackend._walk_kinds
        assert cls._walk_layers is DecoderBackend._walk_layers


# The four cells' routers, and what ``_piece_tile`` gives a piece call of
# ``lanes`` prompts of 512 positions there (the tiles PR 47, PR 48 and PR 52
# measured fastest: models/experts.py ``_piece_tile``).
PIECE_TILES = [
    ("pangu", dict(n_experts=256, top_k=8), 1, 32),
    ("kimi", dict(n_experts=256, top_k=8), 1, 32),
    ("kimi", dict(n_experts=256, top_k=8), 2, 32),
    ("smallthinker", dict(n_experts=64, top_k=6), 1, 64),
    ("smallthinker", dict(n_experts=64, top_k=6), 2, 128),
    ("nemotron", dict(n_experts=128, top_k=6), 1, 32),
    ("nemotron", dict(n_experts=128, top_k=6), 2, 64),
]


@pytest.mark.parametrize("family,router,lanes,tile", PIECE_TILES)
def test_a_piece_backend_runs_the_one_frame(family, router, lanes, tile):
    """No piece backend writes its own piece program, and the sorted layout's
    tile is one rule's for all four."""
    import importlib

    from client_tpu.models.experts import ExpertDecoder

    module, name = {"pangu": ("pangu_moe", "PanguMoeBackend"),
                    "kimi": ("kimi_linear", "KimiLinearBackend"),
                    "smallthinker": ("smallthinker", "SmallThinkerBackend"),
                    "nemotron": ("nemotron_h", "NemotronHBackend")}[family]
    cls = getattr(importlib.import_module(f"client_tpu.models.{module}"),
                  name)
    assert cls.piece_hidden_fn is ExpertDecoder.piece_hidden_fn
    assert cls.prefill_fn is ExpertDecoder.prefill_fn
    assert cls._piece_tile is ExpertDecoder._piece_tile
    backend = cls(**router)
    assert lanes <= backend.prefill_piece[1]
    assert backend._piece_tile(lanes * 512) == tile


def record():
    from client_tpu.engine import backend_init

    backend_init.pallas_interpret = lambda: False
    for key in sorted(RECORDED):
        print(key, hashes(*key))
