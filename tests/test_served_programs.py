"""The programs of the served decoders the benchmark already measures are the
ones they were: what ``models/decoder.py``, ``ops/decode_kernel.py`` and
``ops/flash_attention.py`` gain for a new decoder (cache leaves a backend
names, a latent kernel, values of another width than the keys, a wave's
counts behind its tokens) changes nothing that ``gpt2_small`` or
``evabyte_6b5`` runs.

For each family's decode wave and prefill (or piece) program at a tiny preset,
lowered for the TPU with the kernels in: the StableHLO with the kernels' bodies
taken out (a body carries the source path of the checkout) and, apart, the
Pallas kernels' own jaxprs, each against the hash recorded at commit 6b8c7c9
(PR 31); ``pangu``'s (models/pangu_moe.py: the latent kernel, the grouped
matmuls, prefill by flash pieces) at the tree PR 33 left; ``kimi``'s
(models/kimi_linear.py: the state kernel beside the latent one, the chunked
piece) at the tree PR 34 left, which moved what ``pangu`` and ``kimi`` share
into models/latent_moe.py and left ``pangu``'s two as they were; the kernels
of ``pangu``'s and ``kimi``'s decode waves at the tree PR 40 left (the latent
kernel walks a lane's live blocks: its body and its grid changed, the
programs around it did not); ``evabyte``'s four at the tree PR 42 left (its
layers walked in a loop over leaves of their own, ``wq``, ``wk`` and ``wv``
served ``[out, in]``, a piece fenced: the other three families' eight, which
walk the same loop of ``models/decoder.py`` now, did not move);
``smallthinker``'s two (models/smallthinker.py: the decode kernel with
grouped-query rows, over whole-context leaves and over a ring; prefill by
pieces through the flash kernel's band) at the tree PR 43 left, which gave
``models/decoder.py`` the ``"ring"`` layer kind and both kernels their static
switches, and moved the expert layer from models/latent_moe.py to
models/experts.py (activation and score function its parameters): the other
four families' sixteen hashes did not move; ``gpt``'s, ``evabyte``'s and
``smallthinker``'s decode waves at the tree PR 44 left (the decode-wave
kernel walks a lane's live blocks, the lanes its grid, a lane's last block
copied by quanta, and takes the layer as an operand, so that a program's
calls of one shape are one function: both hashes of the three moved, the
three prefill programs and ``pangu``'s and ``kimi``'s four did not);
``nemotron``'s two (models/nemotron_h.py: the state-space kernel of
ops/ssd.py, the decode kernel with grouped-query rows, the transposed grouped
matmul of an un-gated expert; the chunked piece) at the tree PR 45 left, which
gave ``models/decoder.py`` the ``"none"`` layer kind and ``models/experts.py``
the expert's form, and lifted a wave's tails (``slot_tails``), a piece's
grouped-query attention (models/grouped_query.py) and a stream's record out
of ``kimi_linear.py`` and ``smallthinker.py``: the other five families'
twenty hashes did not move; ``nemotron``'s piece programs at the tree PR 47
left, whose piece holds as many prompts as its backend declares lanes (two:
``prefill``, the program of both lanes; ``prefill_1``, the one-lane program
of its ladder): the projections
and the expert block over all lanes' positions at once, the mixers a lane at
a time.  ``models/experts.py`` ``prefill_fn`` takes any lane count for it and
lowers to the recorded programs for the three other families that prefill
through it with one lane: the other eleven pairs of hashes did not move;
``kimi``'s piece programs at the tree PR 48 left, whose backend declares two
lanes as ``nemotron``'s does (``prefill``, ``prefill_1``: ``wqkv``, the latent
projections and the feed-forward over both lanes' positions, the chunked
form, the switch and the flash call a lane at a time), with
models/latent_moe.py's own ``prefill_fn`` gone for models/experts.py's (the
record's one word a layer through ``_piece_words``): ``pangu``'s two, ``kimi``'s
decode wave and the ten other pairs did not move.  A
PR that means to change one of these programs records the new hash here and
says so; one that does not has a guard.

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
    ("evabyte", "decode"): ("4b633014727aa219", "54792ebb84a37cd7"),
    ("evabyte", "prefill"): ("f73e0dc2333af8de", "e59f91f604bb6804"),
    ("gpt", "decode"): ("c7d0eebb4f86770a", "2445b260a28de378"),
    ("gpt", "prefill"): ("9f74b6f52137fcbf", "813762073b8c861a"),
    ("kimi", "decode"): ("3c7634b1c3eb0637", "f12c2095739d8cfd"),
    ("kimi", "prefill"): ("0719e01ccc5ce5e0", "083a795658c4ced4"),
    ("kimi", "prefill_1"): ("8e944d9b2ab178e0", "11c370955249a916"),
    ("nemotron", "decode"): ("1389f36bf7b1b00e", "a626f88ba8d2d94e"),
    ("nemotron", "prefill"): ("412e3a406d01a832", "036da1223ab7372f"),
    ("nemotron", "prefill_1"): ("2e42a7ed9a4722d7", "72f715ae5cf8c153"),
    ("pangu", "decode"): ("b73c536a3102de37", "231955794429a2a6"),
    ("pangu", "prefill"): ("2947c2e62c0d448d", "fa56a8062981bfe9"),
    ("smallthinker", "decode"): ("44c3bd43dd161186", "93891480d1ba5ee1"),
    ("smallthinker", "prefill"): ("312ebd7279ae9bc1", "ffec3fbbb48d7b3e"),
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
    from client_tpu.models.generate import TinyGptBackend

    return TinyGptBackend(attention_impl="flash", attn_impl="fused")


def _program(family, which):
    """(function, static and donated argument numbers, abstract arguments);
    ``which``: ``decode``, ``prefill`` (a piece backend's declared lanes) or
    ``prefill_<lanes>``."""
    be = _backend(family)
    if family in ("pangu", "kimi", "smallthinker", "nemotron"):
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
    return be.prefill_fn(), (be.prefill_static_argnums,
                             be.donate_argnums), args + (
        (i32(lanes),) if piece else ())


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


def hashes(family, which):
    fn, (static, donated), args = _program(family, which)
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


def record():
    from client_tpu.engine import backend_init

    backend_init.pallas_interpret = lambda: False
    for key in sorted(RECORDED):
        print(key, hashes(*key))
