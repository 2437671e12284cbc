"""Compile the decode step and the prefill program for the chip, without
the chip.

The TPU's compiler is installed beside JAX and compiles for a device that is
described, not attached (``jax.experimental.topologies``).  Nothing runs, so
these tests say nothing about results or times; they say what the compiled
``jit_decode`` does to the donated key/value arena, which is what made a
decode wave take 900 ms where 4 ms of memory traffic were needed (PERF.md
section 6, PR 25: the compiler stored the ``[.., 12, 64]`` leaves with the
sequence axis minor and re-laid out a whole leaf around every scatter and
gather, 10.6 GB of temporaries that ``memory_stats`` never showed).

``jit_prefill`` had the same fault in another place (PERF.md section 6, PR
29): q, k and v forced through ``[.., 12, 64]`` shapes around the flash
kernel, every layer's K and V stacked and scattered at the end, 41% of a 21.9
ms prefill spent moving bytes.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU's library, and pytest-xdist's
workers each import every test file.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# GPT-2's widths (12 heads x 64, ffn 3072) at a depth and vocabulary cut to
# keep the compile to seconds; the cell's arena of 49 x 1024 rows.
GEOMETRY = dict(n_layers=2, d_model=768, n_heads=12, d_ff=3072, vocab=1024,
                max_seq_len=1024, max_streams=48)


def _backend_shapes(monkeypatch, place, **overrides):
    """A ``TinyGptBackend`` that takes the chip's branches, with the shapes
    of its parameters and arena placed by ``place(shape, dtype, leaf)``."""
    from client_tpu.engine import backend_init
    from client_tpu.models.generate import TinyGptBackend

    # The process sees the CPU; the program under test is the chip's.
    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)
    backend = TinyGptBackend(name="g", **{**GEOMETRY, **overrides})
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, a.dtype, False), jax.eval_shape(
            lambda: jax.tree_util.tree_map(jnp.asarray,
                                           backend._init_params())))
    total = len(backend.arena_rows()[0]) + backend.kv_shards
    leaf = (backend.n_layers, total, backend.max_seq_len, backend.d_model)
    arena = {"k": place(leaf, jnp.float32, True),
             "v": place(leaf, jnp.float32, True),
             "tok": place((total,), jnp.int32, False)}
    return backend, params, arena


def _on(sharding):
    return lambda shape, dtype, _leaf=False: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _compile_decode(one_chip, monkeypatch, bucket, **overrides):
    """The scheduler's ``jit_decode`` (arena donated, greedy) compiled for
    one v5e chip from shapes alone.  Returns (compiled, arena shapes)."""
    from client_tpu.observability import spans

    place = _on(one_chip)
    backend, params, arena = _backend_shapes(monkeypatch, place, **overrides)
    lanes_i, lanes_f = place((bucket,), jnp.int32), place((bucket,),
                                                          jnp.float32)
    step = jax.jit(spans.named_step(backend.decode_fn(), spans.STEP_DECODE),
                   donate_argnums=(1,), static_argnums=(8,))
    compiled = step.lower(params, arena, lanes_i, lanes_i, lanes_i, lanes_f,
                          lanes_i, lanes_f, False).compile()
    return compiled, arena


def _compile_prefill(place, monkeypatch, n, lanes=8, **overrides):
    """The scheduler's ``jit_prefill`` (arena donated, greedy) for ``lanes``
    prompts of ``n`` positions.  Returns (compiled, arena shapes)."""
    from client_tpu.observability import spans

    backend, params, arena = _backend_shapes(monkeypatch, place, **overrides)
    lanes_i, lanes_f = place((lanes,), jnp.int32), place((lanes,),
                                                         jnp.float32)
    step = jax.jit(spans.named_step(backend.prefill_fn(), spans.STEP_PREFILL),
                   donate_argnums=(1,), static_argnums=(9,))
    compiled = step.lower(params, arena, lanes_i, place((lanes, n), jnp.int32),
                          lanes_i, lanes_i, lanes_f, lanes_i, lanes_f,
                          False).compile()
    return compiled, arena


def _leaf_bytes(arena):
    k = arena["k"]
    return math.prod(k.shape) * k.dtype.itemsize


def _entry_ops_shaped_like(text, shape):
    """Instructions of the ENTRY computation whose result has ``shape`` (or
    is a tuple that starts with it), as (name, opcode) pairs."""
    entry = text[text.index("ENTRY"):]
    dims = ",".join(str(d) for d in shape)
    pat = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?f32\[" + dims
                     + r"\][^=]*? ([\w\-]+)\(", re.M)
    return pat.findall(entry)


# What may carry an arena leaf's shape: the program's arguments and results,
# and Mosaic's calls, which update the leaf they are given in place.
_PASSES_A_LEAF_ON = ("parameter", "get-tuple-element", "custom-call",
                     "bitcast", "tuple")


@pytest.mark.parametrize("bucket", [1, 48])
def test_decode_step_updates_the_donated_arena_in_place(
        one_chip, monkeypatch, bucket):
    compiled, arena = _compile_decode(one_chip, monkeypatch, bucket)
    memory = compiled.memory_analysis()
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    leaf = _leaf_bytes(arena)
    # Both leaves' buffers are the outputs' buffers.
    assert memory.alias_size_in_bytes >= 2 * leaf
    # No copy of, or pass over, a leaf: all temporaries together stay well
    # under one leaf (the 5-D layout needed 2.9 arenas of them).
    assert memory.temp_size_in_bytes < leaf // 4, memory
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # Mosaic's kernel, compiled
    moved = [(name, op) for name, op in _entry_ops_shaped_like(
        text, arena["k"].shape) if op not in _PASSES_A_LEAF_ON]
    assert not moved, f"arena-shaped work in jit_decode: {moved}"


def test_arena_leaf_is_stored_row_major_unpadded(one_chip, monkeypatch):
    """The device keeps a leaf as the program indexes it: positions on the
    second-minor axis, 768 features (6 x 128 lanes) on the minor one."""
    compiled, arena = _compile_decode(one_chip, monkeypatch, 8)
    text = compiled.as_text()
    dims = ",".join(str(d) for d in arena["k"].shape)
    layouts = set(re.findall(r"f32\[" + dims + r"\](\{[^}]*\})", text))
    # (The kernel's operand constraints name the order without the tile.)
    assert "{3,2,1,0:T(8,128)}" in layouts, layouts
    assert all(lay.startswith("{3,2,1,0") for lay in layouts), layouts


def test_kernel_compiles_at_tiny_gpt_geometry(one_chip, monkeypatch):
    """The zoo's default decoder (4 heads x 64 on 256 lanes, 128 positions,
    64 streams) takes the same kernel: blocks follow from the shapes."""
    compiled, arena = _compile_decode(
        one_chip, monkeypatch, 16, n_layers=2, d_model=256, n_heads=4,
        d_ff=1024, vocab=512, max_seq_len=128, max_streams=64)
    memory = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    if memory is not None:
        assert memory.alias_size_in_bytes >= 2 * _leaf_bytes(arena)


@pytest.mark.parametrize("combine", ["ring", "psum"])
def test_row_sharded_arena_compiles_for_four_chips(topo, combine):
    """``kv_shards=4``: the same kernel per shard under ``shard_map`` over
    the 2x2 host, each shard's rows aliased in place."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from client_tpu.parallel.kv_shard import (
        arena_row_layout,
        sharded_decode_attention,
    )

    mesh = Mesh(np.asarray(topo.devices[:4]), ("kv",))
    total, _free, _dummy = arena_row_layout(8, 4)
    layers, seq, h, d, bsz = 2, 1024, 12, 64, 8
    rows_sh = NamedSharding(mesh, P(None, "kv"))
    rep = NamedSharding(mesh, P())
    leaf = jax.ShapeDtypeStruct((layers, total, seq, h * d), jnp.float32,
                                sharding=rows_sh)
    vec = jax.ShapeDtypeStruct((bsz, h, d), jnp.float32, sharding=rep)
    ix = jax.ShapeDtypeStruct((bsz,), jnp.int32, sharding=rep)

    def step(k, v, q, kn, vn, rows, lens):
        return sharded_decode_attention(mesh, k, v, q, kn, vn, rows, lens,
                                        layer=1, combine=combine)

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        leaf, leaf, vec, vec, vec, ix, ix).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    if memory is not None:
        per_chip_leaf = layers * (total // 4) * seq * h * d * 4
        assert memory.alias_size_in_bytes >= 2 * per_chip_leaf
        assert memory.temp_size_in_bytes < per_chip_leaf // 4, memory


def test_prefill_moves_each_key_value_and_query_once(one_chip, monkeypatch):
    """``jit_prefill`` at GPT-2's geometry, 8 lanes x 1024 positions x 12
    layers: each layer's K and V go from the projection into the donated
    arena's rows (one DMA a lane and leaf), and the flash kernel reads q, k
    and v as ``[8, 1024, 768]``, the layout the projections write."""
    compiled, arena = _compile_prefill(
        _on(one_chip), monkeypatch, 1024, n_layers=12,
        attention_impl="flash")
    text = compiled.as_text()
    layers = arena["k"].shape[0]
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 2 * layers     # a write and a flash each
    # Nothing has an arena leaf's shape but what passes a leaf on, and
    # nothing the shape of every layer's K or V side by side.
    moved = [(name, op) for name, op in _entry_ops_shaped_like(
        text, arena["k"].shape) if op not in _PASSES_A_LEAF_ON]
    assert not moved, f"arena-shaped work in jit_prefill: {moved}"
    assert not re.search(r"f32\[(8,12|12,8|1,12),1024,768\]", text)
    # No relayout around the kernel: nothing at all is shaped by heads.
    assert not re.search(r"\[[\d,]*1024,12,64\]|\[[\d,]*12,1024,64\]", text)
    memory = compiled.memory_analysis()
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    assert memory.alias_size_in_bytes >= 2 * _leaf_bytes(arena)
    # A layer's q, k, v and attention output are 25 MB each, its
    # feed-forward activation 100 MB; one layer's worth is alive at a time
    # (the staged formulation kept 24 slabs and their transposed copy).
    slab = 8 * 1024 * 768 * 4
    assert memory.temp_size_in_bytes < 10 * slab, memory


@pytest.mark.parametrize("lanes", [1, 2, 4])
def test_prefill_compiles_at_every_lane_count_of_the_ladder(
        one_chip, monkeypatch, lanes):
    """The scheduler picks a prefill's lane count from a ladder (PR 31: 4
    lanes beside 8 at the 1024 bucket, 2 and 1 at longer ones): at GPT-2's
    widths the program compiles for the chip at each, with the same two
    kernels a layer and nothing arena-shaped moved."""
    compiled, arena = _compile_prefill(
        _on(one_chip), monkeypatch, 1024, lanes=lanes,
        attention_impl="flash")
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 2 * arena["k"].shape[0]
    moved = [(name, op) for name, op in _entry_ops_shaped_like(
        text, arena["k"].shape) if op not in _PASSES_A_LEAF_ON]
    assert not moved, f"arena-shaped work in jit_prefill: {moved}"


def test_prefill_of_a_short_bucket_and_of_tiny_gpt_compile(one_chip,
                                                           monkeypatch):
    """A prompt bucket under a row group takes XLA's scatter, in place; the
    zoo's default decoder (einsum attention, 4 heads x 64) takes the DMA."""
    compiled, arena = _compile_prefill(_on(one_chip), monkeypatch, 4)
    assert "tpu_custom_call" not in compiled.as_text()
    memory = compiled.memory_analysis()
    if memory is not None:
        assert memory.alias_size_in_bytes >= 2 * _leaf_bytes(arena)
        assert memory.temp_size_in_bytes < _leaf_bytes(arena) // 4, memory
    compiled, arena = _compile_prefill(
        _on(one_chip), monkeypatch, 128, n_layers=2, d_model=256, n_heads=4,
        d_ff=1024, vocab=512, max_seq_len=128, max_streams=64)
    assert "tpu_custom_call" in compiled.as_text()


def test_prefill_compiles_over_the_row_sharded_arena(topo, monkeypatch):
    """``kv_shards=4`` on the 2x2 host: each shard's rows take their lanes'
    slabs in place, under ``shard_map``."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from client_tpu.models.generate import TinyGptBackend

    mesh = Mesh(np.asarray(topo.devices[:4]), ("kv",))
    monkeypatch.setattr(TinyGptBackend, "_mesh", lambda self: mesh)
    rows_sh, rep = NamedSharding(mesh, P(None, "kv")), NamedSharding(mesh,
                                                                    P())

    def place(shape, dtype, leaf=False):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=rows_sh if leaf else rep)

    # (Einsum attention, as every served sharded arena has: a Mosaic call
    # outside ``shard_map`` has no partitioning rule; 128 positions keep
    # its scores small beside the slabs.)
    compiled, arena = _compile_prefill(place, monkeypatch, 128, kv_shards=4)
    assert arena["k"].shape[1] == 48 + 4
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    if memory is not None:
        per_chip_leaf = _leaf_bytes(arena) // 4
        assert memory.alias_size_in_bytes >= 2 * per_chip_leaf
        assert memory.temp_size_in_bytes < per_chip_leaf // 2, memory


# -- the latent, sparse-expert decoder at its published widths (PR 32) -----------

PANGU = dict(n_layers=5, n_dense=1, d_model=7680, n_heads=128, q_rank=1536,
             kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, d_ff=18432,
             d_expert=2048, n_experts=256, experts_held=16, top_k=8,
             vocab=19200, max_seq_len=4096, piece=512, max_streams=128,
             attention_impl="flash")


def _pangu_decode(one_chip, monkeypatch, bucket):
    """``pangu_ultra_moe``'s ``jit_decode`` for one v5e chip from shapes
    alone (13.2 GB of weights and cache that nothing allocates)."""
    from client_tpu.engine import backend_init
    from client_tpu.models.pangu_moe import PanguMoeBackend
    from client_tpu.observability import spans

    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)
    place = _on(one_chip)
    backend = PanguMoeBackend(name="p", **PANGU)
    params = jax.tree_util.tree_map(
        lambda leaf: place(leaf.shape, jnp.dtype(leaf.dtype)),
        backend._init_params())
    arena = jax.tree_util.tree_map(
        lambda a: place(a.shape, a.dtype),
        jax.eval_shape(lambda: backend.init_arena(backend.max_streams)))
    lanes_i, lanes_f = place((bucket,), jnp.int32), place((bucket,),
                                                          jnp.float32)
    step = jax.jit(spans.named_step(backend.decode_fn(), spans.STEP_DECODE),
                   donate_argnums=backend.donate_argnums,
                   static_argnums=backend.decode_static_argnums)
    compiled = step.lower(params, arena, lanes_i, lanes_i, lanes_i, lanes_f,
                          lanes_i, lanes_f, False).compile()
    return compiled, arena, params


def test_latent_expert_decode_step_compiles_at_published_widths(
        one_chip, monkeypatch):
    """A full wave of 128 lanes: the latent kernel (128 heads on a 640-lane
    row) and the grouped matmuls (16 experts of 7680 x 4096 and 2048 x 7680)
    compile under Mosaic's memory limits, the donated cache is updated in
    place, no expert matrix is copied in front of a kernel, and what is
    returned is the tokens and the wave's three counts."""
    compiled, arena, params = _pangu_decode(one_chip, monkeypatch, 128)
    text = compiled.as_text()
    calls = re.findall(r"%(\w+)\.\d+ = [^=]*? custom-call\(", text)
    assert calls.count("latent_wave_attention") == 5
    assert calls.count("grouped_matmul") == 8
    memory = compiled.memory_analysis()
    if memory is not None:
        cache = math.prod(arena["c"].shape) * 2
        assert memory.alias_size_in_bytes >= cache
        # Compiled temporaries: activations and the sorted layout's rows,
        # far under one expert layer's 1.5 GB of matrices.
        assert memory.temp_size_in_bytes < 0.5e9
    experts = r"bf16\[16,7680,4096\]|bf16\[16,2048,7680\]"
    assert not re.findall(r"= (?:" + experts + r")[^=]*? copy\(", text)
    assert "s32[131]" in text          # 128 tokens and three counts
    # The absorbed query goes from the fusion that scales and rounds it into
    # the kernel as it lies (``[lanes, 640, heads]``), and nothing re-lays the
    # kernel's float32 result.  What XLA does re-lay, once a layer each, is
    # the batched products' side of it (a batch dimension cannot be the minor
    # one of a dot's result or operand): ``q_nope W_kb`` as it leaves the
    # dot, in float32 as at the parent, and the result rounded to bfloat16 on
    # its way into ``W_vb``.
    moved = r" (?:copy|transpose)\((%[\w.-]+)"
    assert not re.findall(
        r"= \w+\[128,(?:640,128|128,640)\][^=]*?" + moved, text)
    relaid = re.findall(r"= (\w+)\[128,512,128\][^=]*?" + moved, text)
    dtypes = [d for d, _ in relaid]
    assert dtypes.count("f32") <= 5 and dtypes.count("bf16") <= 5, relaid
    assert not [src for d, src in relaid
                if d == "f32" and "latent_wave_attention" in src]


def test_latent_expert_piece_program_carries_a_wave_at_published_widths(
        one_chip, monkeypatch):
    """``pangu_ultra_moe``'s one piece program (a piece of 512 of one prompt)
    with **the wave it carries** (PR 60, ``piece_wave`` through the latent
    cache: models/latent_moe.py ``_piece_rows_layer``): the full wave's 128
    rows behind the piece's through every product, five
    ``latent_wave_attention`` (a Mosaic body a static layer) behind the five
    layers' flash calls (a branch a count of rows before the piece), eight
    grouped matmuls over one sorted layout of 5632 rows (4608 for the piece
    alone; tiles of 32 either way) and the wave's part (128 tokens, three
    counts) behind the piece's token.  The 3.4 GB latent leaf is updated in
    place, a lane's rows and then the wave's through the kernel's aliased
    operand, and no weight is written out again."""
    from client_tpu.models.pangu_moe import PanguMoeBackend

    backend = PanguMoeBackend(name="p", **PANGU)
    assert backend.prefill_piece == (512, 1) and backend.piece_wave
    text, arena, memory, _ = _piece_backend_program(
        one_chip, monkeypatch, backend, "prefill", 1)
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert calls.count("latent_wave_attention") == 5
    assert calls.count("grouped_matmul") == 8
    assert calls.count("flash_attention") == 5 * (4096 // 512)
    assert "bf16[5632,7680]" in text
    for width in (4096, 7680):
        assert len(set(re.findall(
            rf"%([\w.\-]+) = f32\[5632,{width}\][^=]*? custom-call\(",
            text))) == 4
    assert f"s32[{1 + 128 + 3}]" in text
    # (``wqn``'s ``[1536, 16384]`` is left out: the values of a branch of
    # 1536 rows have its shape.)
    weights = (r"16,7680,4096|16,2048,7680|7680,1536|1536,8192|7680,576"
               r"|16384,7680|7680,36864|18432,7680|7680,4096|2048,7680"
               r"|7680,19200|19200,7680")
    leaf = r"5,129,4096,640"
    moved = _written_out_again(text, weights + "|" + leaf)
    assert not moved, moved
    assert not re.findall(r"= \w+\[" + leaf + r"\][^=\n]*? copy\(", text)
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    assert memory.alias_size_in_bytes >= math.prod(arena["c"].shape) * 2
    print(f"pangu prefill x1: temporaries {memory.temp_size_in_bytes}")
    # A piece's temporaries are a lane's keys and values of up to 4096 rows
    # in 128 heads and the sorted layout's rows (671 MB; 750 MB before a
    # wave rode): under one expert layer's 1.5 GB of matrices, a fifth of
    # the latent leaf.
    assert memory.temp_size_in_bytes < 0.8e9, memory
    assert 13.1e9 < memory.argument_size_in_bytes < 13.4e9
    # The program's peak with the cell's arena (13.91 GB of the chip's
    # 16.9; ``hbm_peak_bytes.itl`` counts live buffers, not temporaries).
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 14.1e9, memory


# -- the hybrid decoder at its published widths (PR 34) ----------------------------

KIMI = dict(n_layers=8, n_dense=1, d_model=2304, n_heads=32,
            linear_attn={"kda_layers": [1, 2, 3, 5, 6, 7],
                         "full_attn_layers": [4, 8], "num_heads": 32,
                         "head_dim": 128, "short_conv_kernel_size": 4},
            kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, d_ff=9216,
            d_expert=1024, n_experts=256, experts_held=32, top_k=8,
            vocab=20480, max_seq_len=8192, piece=512,
            max_streams=256, attention_impl="flash")


def _kimi_shapes(one_chip, monkeypatch):
    """``kimi_linear`` at the cell's widths with the chip's branches taken:
    (place, backend, the shapes of its parameters, of its arena)."""
    from client_tpu.engine import backend_init
    from client_tpu.models.kimi_linear import KimiLinearBackend

    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)
    place = _on(one_chip)
    backend = KimiLinearBackend(name="k", **KIMI)
    params = jax.tree_util.tree_map(
        lambda leaf: place(leaf.shape, jnp.dtype(leaf.dtype)),
        backend._init_params())
    arena = jax.tree_util.tree_map(
        lambda a: place(a.shape, a.dtype),
        jax.eval_shape(lambda: backend.init_arena(backend.max_streams)))
    return place, backend, params, arena


def test_state_and_latent_decode_step_compiles_at_published_widths(
        one_chip, monkeypatch):
    """A full wave of 256 lanes over both caches: the state kernel (a block
    of heads of 128 x 128 float32 a grid step), the latent kernel at 32 heads
    and the grouped matmuls of 32 held experts compile under Mosaic's limits;
    the three donated leaves (8.7 GB) are updated in place, and what is
    returned is the tokens and the wave's three counts."""
    from client_tpu.observability import spans

    place, backend, params, arena = _kimi_shapes(one_chip, monkeypatch)
    lanes_i, lanes_f = place((256,), jnp.int32), place((256,), jnp.float32)
    step = jax.jit(spans.named_step(backend.decode_fn(), spans.STEP_DECODE),
                   donate_argnums=backend.donate_argnums,
                   static_argnums=backend.decode_static_argnums)
    compiled = step.lower(params, arena, lanes_i, lanes_i, lanes_i, lanes_f,
                          lanes_i, lanes_f, False).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\w+)\.\d+ = [^=]*? custom-call\(", text)
    assert calls.count("kda_wave_update") == 6
    assert calls.count("latent_wave_attention") == 2
    assert calls.count("grouped_matmul") == 14
    # 256 tokens, the lanes' rows of their streams' records, three counts.
    assert f"s32[{256 + 256 * backend.stream_record + 3}]" in text
    memory = compiled.memory_analysis()
    if memory is not None:
        leaves = sum(math.prod(arena[k].shape) * arena[k].dtype.itemsize
                     for k in ("c", "s", "conv"))
        assert memory.alias_size_in_bytes >= leaves
        # No copy of a state leaf (3.2 GB) or of the rows (5.4 GB) beside it.
        assert memory.temp_size_in_bytes < 1.0e9, memory


@pytest.mark.parametrize("lanes", [1, 2])
def test_state_and_latent_piece_programs_compile_at_published_widths(
        one_chip, monkeypatch, lanes):
    """Both of the hybrid decoder's piece programs (a piece of 512 of each of
    ``lanes`` prompts; the backend declares two) for one v5e chip from shapes
    alone: **fourteen grouped matmuls whatever the lanes** (one plan and one
    pair of products an expert layer for every lane's positions: the 32 held
    experts of a layer are read once a program), a lane's own flash calls (a
    branch a count of rows before it, in both latent layers) and the chunked
    form in plain XLA.  **A piece program carries a wave** (PR 60,
    ``piece_wave`` through the latent cache, models/latent_moe.py
    ``_piece_rows_layer``, beside the ``"state"`` kind): the full wave's 256
    rows behind the piece's, so the wave's six state calls and its two latent
    calls beside the flash calls and the chunked form, still fourteen grouped
    matmuls (one sorted layout for the rows of both: 7136 rows where the
    piece alone had 5088, 12288 in tiles of 64 for 9184 in tiles of 32) and
    the wave's part of the result behind the piece's.  No program copies a
    weight or writes a state, tail or latent leaf out again: the donated
    arena's three leaves (8.7 GB) are updated in place, each lane's slot
    among them and then the wave's slots through the kernels' aliased
    operands."""
    from client_tpu.models.kimi_linear import KimiLinearBackend

    backend = KimiLinearBackend(name="k", **KIMI)
    assert backend.prefill_piece == (512, 2) and backend.piece_wave
    text, arena, memory, _ = _piece_backend_program(
        one_chip, monkeypatch, backend, "prefill", lanes)
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert calls.count("grouped_matmul") == 14
    assert calls.count("flash_attention") == lanes * 2 * (8192 // 512)
    # The wave that rides in the program.
    assert calls.count("kda_wave_update") == 6
    assert calls.count("latent_wave_attention") == 2
    # The sorted layout in tiles of 32 rows for one lane's positions and the
    # wave's 256 rows (a held expert's mean share of the call's pairs is 24
    # rows), of 64 for two lanes' (a share of 40).
    assert f"bf16[{7136 if lanes == 1 else 12288},2304]" in text
    # A token and 512 record rows a lane, and the wave's part behind them
    # (256 tokens, a record row a lane, three counts): one result.
    wave = 256 + 256 * backend.stream_record + 3
    piece = lanes * (1 + 512 * backend.stream_record)
    assert f"s32[{piece + wave}]" in text
    weights = (r"32,2304,2048|32,1024,2304|2304,12288|4096,2304|2304,18432"
               r"|9216,2304|2304,2048|1024,2304|20480,2304|2304,20480")
    leaves = r"2,257,8192,640|6,257,32,128,128|6,257,36864"
    moved = _written_out_again(text, weights + "|" + leaves)
    assert not moved, moved
    assert not re.findall(
        r"= \w+\[(?:" + leaves + r")\][^=\n]*? copy\(", text)
    # The head of the piece's lanes and of the wave under the one
    # conditional of two branches (the flash calls' switches hold more).
    assert len([b for b in re.findall(
        r" conditional\([^\n]*?branch_computations=\{([^}]*)\}", text)
        if b.count("%") == 2]) == 1
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    cache = sum(math.prod(arena[k].shape) * arena[k].dtype.itemsize
                for k in ("c", "s", "conv"))
    assert memory.alias_size_in_bytes >= cache
    print(f"kimi prefill x{lanes}: temporaries {memory.temp_size_in_bytes}")
    # A piece's temporaries are the sorted layout, the chunks' pairwise
    # decays, a lane's keys and values of up to 8192 rows and the carried
    # wave's 256 rows of logits (404 | 461 MB; 320 | 397 MB before a wave
    # rode): far under the 3.2 GB state leaf or the 5.4 GB of latent rows
    # (the arena and weights are 13.1 GB of the chip's 16.9).
    assert memory.temp_size_in_bytes < (0.52e9 if lanes == 2 else 0.45e9), \
        memory
    assert 12.9e9 < memory.argument_size_in_bytes < 13.2e9
    # The program's peak with the cell's arena (13.34 | 13.39 GB).
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 13.6e9, memory


# -- the byte-level decoder at its published widths (PR 42) ------------------

EVABYTE = dict(n_layers=2, d_model=4096, n_heads=32, d_ff=11008, vocab=320,
               n_pred_heads=8, max_seq_len=32768, window=2048, chunk=16,
               max_streams=16, attention_impl="flash")


def _evabyte_program(one_chip, monkeypatch, which):
    """``evabyte_6b5``'s ``jit_decode`` (a full wave of 16) or ``jit_prefill``
    (one piece of 2048) for one v5e chip from shapes alone, two of the
    cell's eight layers.  Returns (optimised text, arena shapes, memory)."""
    from client_tpu.engine import backend_init
    from client_tpu.models.evabyte import EvaByteBackend
    from client_tpu.observability import spans

    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)
    place = _on(one_chip)
    backend = EvaByteBackend(name="e", **EVABYTE)
    n, d, f = backend.n_layers, backend.d_model, backend.d_ff
    heads = (n, backend.n_heads, backend.head_dim)
    # The host's stacked tree by its shapes (the seeded one is 0.8 GB of
    # draws at these widths), split as ``place_params`` splits it.
    stacked = jax.tree_util.tree_map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        {"embed": (backend.vocab, d), "lnf": (d,),
         "head": (d, backend.n_pred_heads * backend.vocab),
         "layers": {"ln1": (n, d), "ln2": (n, d), "wq": (n, d, d),
                    "wk": (n, d, d), "wv": (n, d, d), "wo": (n, d, d),
                    "wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d),
                    "phi": heads, "mu": heads}},
        is_leaf=lambda x: isinstance(x, tuple))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, a.dtype),
        jax.eval_shape(backend.split_layers, stacked))
    arena = jax.tree_util.tree_map(
        lambda a: place(a.shape, a.dtype),
        jax.eval_shape(lambda: backend.init_arena(backend.max_streams)))
    if which == "decode":
        lanes_i, lanes_f = place((16,), jnp.int32), place((16,), jnp.float32)
        step = jax.jit(
            spans.named_step(backend.decode_fn(), spans.STEP_DECODE),
            donate_argnums=backend.donate_argnums,
            static_argnums=backend.decode_static_argnums)
        lowered = step.lower(params, arena, lanes_i, lanes_i, lanes_i,
                             lanes_f, lanes_i, lanes_f, False)
    else:
        lane_i, lane_f = place((1,), jnp.int32), place((1,), jnp.float32)
        step = jax.jit(
            spans.named_step(backend.prefill_fn(), spans.STEP_PREFILL),
            donate_argnums=backend.donate_argnums,
            static_argnums=backend.prefill_static_argnums)
        lowered = step.lower(params, arena, lane_i,
                             place((1, backend.window), jnp.int32), lane_i,
                             lane_i, lane_f, lane_i, lane_f, False, lane_i)
    compiled = lowered.compile()
    return compiled.as_text(), arena, compiled.memory_analysis()


def _weights_moved(text):
    """Instructions that write a weight matrix out again: outside every
    fused computation (what a fusion calls is part of its one pass), a
    ``copy`` or a ``dynamic-slice`` fusion whose result is a ``bf16`` matrix
    of a layer's shapes, leading 1s allowed.  (An asynchronous ``slice`` or
    ``copy-start`` of one is the compiler's prefetch, in the layout the
    matrix has.)"""
    fused = set(re.findall(r" fusion\([^\n]*?calls=%([\w.\-]+)", text))
    shapes = r"(?:1,)*(?:4096,4096|4096,11008|11008,4096)"
    pat = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = bf16\[" + shapes
                     + r"\][^=]*? ([\w\-]+)\(", re.M)
    moved = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", comp)
        if head and head.group(1) not in fused:
            moved += [(name, op) for name, op in pat.findall(comp)
                      if op == "copy"
                      or (op == "fusion" and "dynamic-slice" in name)]
    return moved


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_byte_decoder_reads_every_weight_where_it_lies(one_chip, monkeypatch,
                                                       which):
    """At the cell's widths (4096, 32 x 128, ffn 11008, 16 + 1 slots of 4096
    rows) neither program writes a layer's matrix out again before its
    product: under a ``scan`` over stacked leaves the wave sliced ``wq`` and
    ``wk`` out of ``[L, 4096, 4096]`` and copied each to ``{1,2,0}`` every
    iteration, 1.30 of an 11.86 ms step (PERF.md section 6, PR 42); as
    ``[in, out]`` leaves of their own they were still transposed.  The
    donated arena is updated in place."""
    text, arena, memory = _evabyte_program(one_chip, monkeypatch, which)
    assert not _weights_moved(text), _weights_moved(text)
    calls = re.findall(r"%(\w+)\.\d+ = [^=]*? custom-call\(", text)
    kernel = {"decode": "decode_wave_attention",
              "prefill": "flash_attention"}[which]
    assert calls.count(kernel) == EVABYTE["n_layers"]
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    leaf = _leaf_bytes(arena)
    assert memory.alias_size_in_bytes >= 2 * leaf
    # A wave's temporaries are its activations; a piece's 2048 rows of
    # q, k, v and the feed-forward's 11008 columns in float32 (90 MB each):
    # 256 MB where the ``scan``'s body took 91, of a leaf's 1.14 GB.
    assert memory.temp_size_in_bytes < {"decode": leaf // 64,
                                        "prefill": leaf // 2}[which], memory


# -- window and global layers in one decoder at its published widths (PR 43) ----

SMALLTHINKER = dict(n_layers=8, d_model=2560, n_heads=28, n_kv_heads=4,
                    head_dim=128, d_expert=768, n_experts=64, top_k=6,
                    window=4096, vocab=151936, max_seq_len=16384, piece=512,
                    max_streams=48, attention_impl="flash", record=True)


# A program compiled once a module: (family, which, lanes) -> what its
# builder returned (two tests read the same piece programs).
_COMPILED = {}


def _once(key, build):
    if key not in _COMPILED:
        _COMPILED[key] = build()
    return _COMPILED[key]


def _smallthinker_program(one_chip, monkeypatch, which, lanes=1):
    return _once(("smallthinker", which, lanes),
                 lambda: _compile_smallthinker(one_chip, monkeypatch, which,
                                               lanes))


def _compile_smallthinker(one_chip, monkeypatch, which, lanes):
    """``smallthinker_21b``'s ``jit_decode`` (a full wave of 48) or
    ``jit_prefill`` (a piece of 512 of each of ``lanes`` prompts) for one v5e
    chip from shapes alone (13.7 GB of weights and cache that nothing
    allocates).  Returns (optimised text, arena shapes, memory, backend)."""
    from client_tpu.models.smallthinker import SmallThinkerBackend

    backend = SmallThinkerBackend(name="s", **SMALLTHINKER)
    assert backend.prefill_piece == (512, 2)
    text, arena, memory, _ = _piece_backend_program(
        one_chip, monkeypatch, backend, which, lanes)
    return text, arena, memory, backend


def _written_out_again(text, shapes):
    """Instructions outside every fused computation that write an array of
    one of ``shapes`` (a regular expression over the dimensions) out again:
    a ``copy`` or a ``transpose`` of it, or a ``dynamic-slice`` fusion that
    yields it (``_weights_moved``'s rule)."""
    fused = set(re.findall(r" fusion\([^\n]*?calls=%([\w.\-]+)", text))
    pat = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[(?:1,)*(" + shapes
                     + r")\][^=]*? ([\w\-]+)\(", re.M)
    moved = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", comp)
        if head and head.group(1) not in fused:
            moved += [(name, op, shape)
                      for name, shape, op in pat.findall(comp)
                      if op in ("copy", "transpose")
                      or (op == "fusion" and "dynamic-slice" in name)]
    return moved


@pytest.mark.parametrize("which,lanes", [("decode", 1), ("prefill", 1),
                                         ("prefill", 2)])
def test_window_and_global_decoder_compiles_at_published_widths(
        one_chip, monkeypatch, which, lanes):
    """At the cell's widths (2560, 28 query heads over 4 key heads of 128, 64
    experts of 768, 151936 ids; 48 + 1 slots of 6 x 4096 ring rows and 2 x
    16384 rows): the wave's two attention kinds are one kernel under two
    names, six ring calls and two whole-context calls, grouped-query rows of
    512 lanes; a piece holds, a lane, a flash call for every count of rows
    before it (9 a window layer, 32 a global one); sixteen grouped matmuls
    every way: **a piece of two prompts reads a layer's 64 experts once**
    (one plan, one gather and one pair of products for both lanes'
    positions).  **A piece program carries a wave** (PR 56,
    ``piece_wave``): the full wave's 48 rows behind the piece's, so
    the wave's eight attention calls beside the flash calls, one sorted
    layout for the rows of both (7424 rows where the piece alone had 7104,
    14592 for 14336) and the wave's part of the result behind the
    piece's.  No program writes a weight or a cache leaf out again: every
    matrix is read by its product where it lies, and the donated arena's
    four leaves are updated in place, the second lane's rows behind the
    first's and the wave's rows behind the piece's in the same leaf."""
    text, arena, memory, backend = _smallthinker_program(
        one_chip, monkeypatch, which, lanes)
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert calls.count("grouped_matmul") == 16
    # (A wave of its own, or the one that rides in the piece's program.)
    assert calls.count("window_wave_attention") == 6
    assert calls.count("decode_wave_attention") == 2
    # 48 tokens, a record row a lane and the wave's three counts.
    wave = 48 + 48 * backend.stream_record + 3
    if which == "decode":
        assert f"s32[{wave}]" in text
    else:
        assert calls.count("flash_attention") == lanes * (6 * 9 + 2 * 32)
        # The sorted layout in tiles of 64 rows for one lane's pairs, of 128
        # for two lanes' (an expert's mean share 52.5 and 100.5 rows with the
        # wave's 48 among them): the names of the trace's groups,
        # ``grouped_matmul_f32_7424_*`` and ``_14592_*``, once a layer each.
        layout = 7424 if lanes == 1 else 14592
        assert f"bf16[{layout},2560]" in text
        for width in (1536, 2560):
            assert len(set(re.findall(
                rf"%([\w.\-]+) = f32\[{layout},{width}\][^=]*? "
                r"custom-call\(", text))) == 8
        # A token and 512 record rows a lane, and the wave's part behind
        # them: one result.
        piece = lanes * (1 + 512 * backend.stream_record)
        assert f"s32[{piece + wave}]" in text
    weights = (r"2560,3584|3584,2560|2560,512|64,2560,1536|64,768,2560"
               r"|151936,2560|2560,151936")
    leaves = r"[26],49,(?:4096|16384),512"
    moved = _written_out_again(text, weights + "|" + leaves)
    if which == "prefill":
        # (The 2560 rows before a slot's sixth piece, sliced out of a leaf
        # for the flash call, have W_k's shape: one a layer, leaf and lane.)
        rows = [m for m in moved if m[1:] == ("fusion", "2560,512")]
        assert len(rows) == 16 * lanes, moved
        moved = [m for m in moved if m not in rows]
    assert not moved, moved
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    cache = sum(math.prod(arena[k].shape) * 2 for k in ("kg", "vg", "kw",
                                                         "vw"))
    assert memory.alias_size_in_bytes >= cache
    print(f"smallthinker {which} x{lanes}: temporaries "
          f"{memory.temp_size_in_bytes}")
    # A wave's temporaries are its activations (14 MB); a piece's the rows
    # before it (a global layer's 15872 x 512 of K and of V), its scores'
    # operands and the sorted layout's 7424 rows (100 MB with the wave it
    # holds; 203 MB with two lanes' 14592 rows, gathers and projections;
    # 57 and 215 MB before a wave rode): far under one ring
    # leaf's 1.2 GB or one layer's 0.8 GB of matrices, either of which a
    # lane walk the compiler may reorder costs (models/grouped_query.py
    # ``_lane_by_lane``).
    assert memory.temp_size_in_bytes < (0.3e9 if lanes == 2 else 0.2e9), \
        memory
    assert 13.6e9 < memory.argument_size_in_bytes < 13.8e9


# -- state-space, attention and expert layers as blocks of their own (PR 45) ----

NEMOTRON = dict(
    pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    n_layers=13, d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
    mamba_heads=64, mamba_head_dim=64, n_groups=8, state_size=128,
    conv_kernel=4, chunk=128, d_expert=1856, d_shared=3712, n_experts=128,
    experts_held=64, top_k=6, routed_scale=2.5, vocab=65536,
    max_seq_len=4096, piece=512, max_streams=256, attention_impl="flash",
    record=True)


def _piece_backend_program(one_chip, monkeypatch, backend, which, lanes=1):
    """A piece backend's ``jit_decode`` (a full wave of its ``max_streams``)
    or ``jit_prefill`` (a piece of each of ``lanes`` prompts) for one v5e chip
    from shapes alone (weights and cache that nothing allocates).  Returns
    (optimised text, arena shapes, memory, seconds the compiler took)."""
    import time

    from client_tpu.engine import backend_init
    from client_tpu.observability import spans

    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)
    place = _on(one_chip)
    params = jax.tree_util.tree_map(
        lambda leaf: place(leaf.shape, jnp.dtype(leaf.dtype)),
        backend._init_params())
    arena = jax.tree_util.tree_map(
        lambda a: place(a.shape, a.dtype),
        jax.eval_shape(lambda: backend.init_arena(backend.max_streams)))
    if which == "decode":
        wave = backend.max_streams
        lanes_i, lanes_f = place((wave,), jnp.int32), place((wave,),
                                                            jnp.float32)
        step = jax.jit(
            spans.named_step(backend.decode_fn(), spans.STEP_DECODE),
            donate_argnums=backend.donate_argnums,
            static_argnums=backend.decode_static_argnums)
        lowered = step.lower(params, arena, lanes_i, lanes_i, lanes_i,
                             lanes_f, lanes_i, lanes_f, False)
    else:
        piece = backend.prefill_piece[0]
        lane_i = place((lanes,), jnp.int32)
        lane_f = place((lanes,), jnp.float32)
        step = jax.jit(
            spans.named_step(backend.prefill_fn(), spans.STEP_PREFILL),
            donate_argnums=backend.donate_argnums,
            static_argnums=backend.prefill_static_argnums)
        wave = ()
        if backend.piece_wave:
            # The wave that rides in the program, at the top bucket.
            wave_i = place((backend.max_streams,), jnp.int32)
            wave_f = place((backend.max_streams,), jnp.float32)
            wave = ((wave_i, wave_i, wave_i, wave_f, wave_i, wave_f),)
        lowered = step.lower(params, arena, lane_i,
                             place((lanes, piece), jnp.int32), lane_i,
                             lane_i, lane_f, lane_i, lane_f, False, lane_i,
                             lane_i, *wave)
    t0 = time.monotonic()
    compiled = lowered.compile()
    return (compiled.as_text(), arena, compiled.memory_analysis(),
            time.monotonic() - t0)


def _nemotron_program(one_chip, monkeypatch, which, lanes=1):
    return _once(("nemotron", which, lanes), lambda: _compile_nemotron(
        one_chip, monkeypatch, which, lanes))


def _compile_nemotron(one_chip, monkeypatch, which, lanes):
    """``nemotron3_nano_30b``'s ``jit_decode`` (a full wave of 256) or
    ``jit_prefill`` (a piece of 512 of each of ``lanes`` prompts): 13.3 GB of
    weights and cache.  Returns (optimised text, arena shapes, memory,
    backend)."""
    from client_tpu.models.nemotron_h import NemotronHBackend

    backend = NemotronHBackend(name="n", **NEMOTRON)
    assert backend.prefill_piece == (512, 2)
    text, arena, memory, _ = _piece_backend_program(
        one_chip, monkeypatch, backend, which, lanes)
    return text, arena, memory, backend


@pytest.mark.parametrize("which,lanes", [("decode", 1), ("prefill", 1),
                                         ("prefill", 2)])
def test_state_attention_and_expert_blocks_compile_at_published_widths(
        one_chip, monkeypatch, which, lanes):
    """At the cell's widths (2688; 64 state heads of 64 x 128 in 8 groups,
    packed two a row; 32 query heads over 2 key heads of 128; 64 held
    un-gated experts of 1856 and a shared one of 3712; 65536 ids; 256 + 1
    slots of 4096): a wave is six state calls, two grouped-query decode calls
    and ten grouped matmuls (an expert is two matrices), a piece a flash call
    for every count of rows before it (8 an attention layer) and the chunked
    form in plain XLA; **a piece of two prompts is ten grouped matmuls too**
    (one plan, one gather and one pair of products a layer for both lanes'
    positions: the 64 held experts of a layer are read once a program) and a
    lane's own flash calls.  **A piece program carries a wave** (PR 58,
    ``piece_wave`` through the ``"state"`` kind): the full wave's 256 rows
    behind the piece's, so the wave's six state calls and two decode calls
    beside the flash calls and the chunked form, still ten grouped matmuls
    (one sorted layout for the rows of both: 8640 rows where the piece alone
    had 5056, 11712 for 10176) and the wave's part of the result behind the
    piece's.  No program copies a weight (the experts' ``[64, 1856, 2688]``
    leaves, ``W_in``'s three blocks, the head) or writes a state, tail or row
    leaf out again: the donated arena's four leaves are updated in place,
    each piece lane's slot of state and then the wave's slots, through the
    kernel's aliased operand, in the one 3.2 GB leaf."""
    text, arena, memory, backend = _nemotron_program(one_chip, monkeypatch,
                                                     which, lanes)
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert calls.count("grouped_matmul") == 10
    # (A wave of its own, or the one that rides in the piece's program.)
    assert calls.count("ssd_wave_update") == 6
    assert calls.count("decode_wave_attention") == 2
    # 256 tokens, a record row a lane and the wave's three counts.
    wave = 256 + 256 * backend.stream_record + 3
    if which == "decode":
        assert f"s32[{wave}]" in text
    else:
        assert calls.count("flash_attention") == lanes * 8 * 2
        # The sorted layout in tiles of 64 rows either way (an expert's mean
        # share 36 and 60 rows with the wave's 256 rows among the piece's:
        # 24 and 48, in tiles of 32 and 64, before a wave rode).
        assert f"bf16[{8640 if lanes == 1 else 11712},2688]" in text
        # A token and 512 record rows a lane, and the wave's part behind
        # them: one result.
        piece = lanes * (1 + 512 * backend.stream_record)
        assert f"s32[{piece + wave}]" in text
    weights = (r"64,1856,2688|2688,4096|2688,6144|4096,2688|2688,3712"
               r"|3712,2688|65536,2688|2688,65536")
    leaves = r"6,257,32,128,128|6,257,18432|2,257,4096,256"
    moved = _written_out_again(text, weights + "|" + leaves)
    assert not moved, moved
    # The state's leaf above all: 3.2 GB that fit the chip once.
    assert not re.findall(r"= f32\[6,257,32,128,128\][^=\n]*? copy\(", text)
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    cache = sum(math.prod(arena[k].shape) * arena[k].dtype.itemsize
                for k in ("s", "conv", "k", "v"))
    assert memory.alias_size_in_bytes >= cache
    print(f"nemotron {which} x{lanes}: temporaries "
          f"{memory.temp_size_in_bytes}")
    # A wave's temporaries are its activations (16 MB), a piece's the sorted
    # layout's rows, the chunks' pairwise decays and the carried wave's 256
    # rows of logits (147 MB; 34 MB before a wave rode): far under the 3.2 GB
    # state leaf or one expert leaf's 0.64 GB, which this program copied
    # whole before its leaves lay as they do (PERF.md section 6, PR 45).
    # Two lanes double a piece's own (211 MB; 168 MB before a wave rode).
    assert memory.temp_size_in_bytes < (0.3e9 if lanes == 2 else 0.2e9), \
        memory
    assert 13.2e9 < memory.argument_size_in_bytes < 13.4e9
    # The program's peak with the cell's arena: arguments (the arena among
    # them, aliased to the result), temporaries and what of the result is
    # not the arena, under the chip's 16 GB (13.45 and 13.51 GB).
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 13.7e9, memory


# -- the piece's head under one conditional (PR 51) -----------------------------

# A piece program's temporaries at the parent of PR 51 (bytes, this compiler),
# where the head stood in the open: (family, lanes) -> (temporaries, the
# vocabulary, the model's width).  (``smallthinker``'s two-lane program is PR
# 52's and never had its head in the open: its own temporaries less the row
# of logits a lane that the bound below allows.)
_PIECE_BEFORE_THE_CONDITIONAL = {
    ("smallthinker", 1): (56700416, 151936, 2560),
    ("smallthinker", 2): (215341568 - 2 * 151936 * 4, 151936, 2560),
    ("nemotron", 1): (33867776, 65536, 2688),
    ("nemotron", 2): (168177664, 65536, 2688),
}


def _computations(text):
    """name -> text of every computation of an optimised module."""
    out = {}
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", comp)
        if head:
            out[head.group(1)] = comp
    return out


def _reached_from(comps, name):
    """The computations ``name`` calls, itself among them."""
    seen, todo = set(), [name]
    while todo:
        at = todo.pop()
        if at in seen or at not in comps:
            continue
        seen.add(at)
        todo += re.findall(r"%([\w.\-]+)", " ".join(re.findall(
            r"(?:calls|to_apply|branch_computations|true_computation"
            r"|false_computation|body|condition)=\{?([^}\n]*?)[},\n]",
            comps[at])))
    return seen


@pytest.mark.parametrize("family,lanes", sorted(
    _PIECE_BEFORE_THE_CONDITIONAL))
def test_a_piece_computes_its_head_under_one_conditional(
        one_chip, monkeypatch, family, lanes):
    """The compiled piece program of ``smallthinker_21b``'s and
    ``nemotron3_nano_30b``'s widths holds **one** conditional of two branches
    beside its flash calls' switches (a branch a count of rows): the head's,
    which is the head of the wave that rides in the program too (PR 56, and
    PR 58 for ``nemotron3_nano_30b``: one product over the vocabulary's
    matrix for the lanes' last rows and the wave's, under "a lane ends or a
    wave lane is live").  Whatever has the vocabulary's dimension (the product with the
    head's matrix, the logits, the token choice) stands in the branch that
    takes, the other branch holds no product at all, and the donated arena
    passes through neither: the program's temporaries are the parent's and
    at most a row of logits a lane more."""
    if family == "smallthinker":
        text, arena, memory, _ = _smallthinker_program(one_chip, monkeypatch,
                                                       "prefill", lanes)
    else:
        text, arena, memory, _ = _nemotron_program(one_chip, monkeypatch,
                                                   "prefill", lanes)
    before, vocab, width = _PIECE_BEFORE_THE_CONDITIONAL[family, lanes]
    comps = _computations(text)
    two_way = [re.findall(r"%([\w.\-]+)", branches) for branches in
               re.findall(r" conditional\([^\n]*?branch_computations="
                          r"\{([^}]*)\}", text)
               if branches.count("%") == 2]
    assert len(two_way) == 1, two_way
    # (pred is false, pred is true) of each.
    under = set().union(*(_reached_from(comps, head) for _, head in two_way))
    fused = set(re.findall(r" fusion\([^\n]*?calls=%([\w.\-]+)", text))
    reads_head = {name for name, comp in comps.items()
                  if re.search(rf"\(param[^\n]*?bf16\[{width},{vocab}\]",
                               comp.split("\n", 1)[0])}
    assert reads_head and reads_head <= under, reads_head - under
    passes_on = ("parameter", "get-tuple-element", "tuple", "conditional")
    for name, comp in comps.items():
        if name in under or name in fused:
            continue
        shaped = re.findall(
            rf"^\s*(?:ROOT )?%[\w.\-]+ = [^=\n]*?\b{vocab}\b[^=\n]*? "
            rf"([\w\-]+)\(", comp, re.M)
        assert set(shaped) <= set(passes_on), (name, shaped)
    leaves = {math.prod(leaf.shape) for leaf in arena.values()
              if leaf.ndim > 1}
    plain = re.sub(r"/\*index=\d+\*/", "", text)   # (a long tuple's marks)
    for skip, _ in two_way:
        for name in _reached_from(comps, skip):
            assert not re.search(
                r" (?:dot|convolution|custom-call|reduce)\(",
                comps[name]), name
        # No leaf of the arena is an operand of the conditional.
        (operands,) = re.findall(
            r" conditional\(([^\n]*?)\), branch_computations=\{%"
            + re.escape(skip), text)
        for operand in re.findall(r"%([\w.\-]+)", operands):
            made = re.search(
                rf"%{re.escape(operand)} = (\(?[^=\n]*?) [\w\-]+\(",
                plain).group(1)
            for dims in re.findall(r"\w+\[([\d,]+)\]", made):
                assert math.prod(int(d) for d in dims.split(",")) \
                    not in leaves, (operand, made)
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    print(f"{family} x{lanes}: temporaries {memory.temp_size_in_bytes} "
          f"({before} before)")
    allowed = before + lanes * vocab * 4
    if family == "smallthinker":
        # The wave that rides (PR 56): a row of float32 logits a lane of its
        # 48 more under the one conditional (29.2 MB; the one-lane program
        # reads 6.0 MB over ``before``, the two-lane one under it).
        allowed += 48 * vocab * 4
    else:
        # The wave that rides (PR 58): a row of float32 logits a lane of its
        # 256 more under the one conditional (67.1 MB), and the sorted
        # layout's rows more with the wave's among them (8640 for 5056, 11712
        # for 10176), a row of it in bfloat16 before the first product and in
        # float32 behind each of the two.  The one-lane program reads 146.9
        # MB (113.0 MB over ``before``), the two-lane one 211.2 MB (43.1 MB
        # over).
        more = (8640 - 5056) if lanes == 1 else (11712 - 10176)
        allowed += 256 * vocab * 4 + more * (width * 2 + 1856 * 4 + width * 4)
    assert memory.temp_size_in_bytes <= allowed, memory


# -- a layer stack that runs four passes over one set of weights (PR 50) --------

OURO = dict(n_layers=12, passes=4, d_model=2048, n_heads=16, n_kv_heads=16,
            head_dim=128, d_ff=5632, vocab=49152, max_seq_len=1536, piece=512,
            max_streams=18, attention_impl="flash", record=True)


@pytest.mark.parametrize("which,lanes", [("decode", 1), ("prefill", 1),
                                         ("prefill", 2)])
def test_four_passes_over_one_set_of_weights_compile_at_published_widths(
        one_chip, monkeypatch, which, lanes):
    """At the cell's widths (2048; 16 query heads over 16 key heads of 128;
    SwiGLU 5632; 49152 ids; 4 passes over 12 layers; 18 + 1 slots of 1536): a
    wave is 48 grouped-query decode calls over **12** layers' weights, a piece
    a flash call for every count of rows before it (3) in each of its 48
    layer bodies and lanes.  The parameters are one set of layers (1.64 GB)
    and ``passes x layers`` cache leaves (11.5 GB); no program copies a
    weight or writes a cache leaf out again, whichever pass reads it."""
    from client_tpu.models.ouro import OuroBackend

    backend = OuroBackend(name="o", **OURO)
    assert backend.prefill_piece == (512, 2)
    text, arena, memory, seconds = _piece_backend_program(
        one_chip, monkeypatch, backend, which, lanes)
    print(f"ouro_2b6 {which} x{lanes}: compiled in {seconds:.1f} s; {memory}")
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert arena["k"].shape == (48, 19, 1536, 2048)
    if which == "decode":
        assert calls.count("decode_wave_attention") == 48
        # 18 tokens and a record row a lane.
        assert f"s32[{18 + 18 * backend.stream_record}]" in text
    else:
        assert calls.count("flash_attention") == lanes * 48 * 3
        # A token and 512 record rows a lane.
        assert f"s32[{lanes * (1 + 512 * backend.stream_record)}]" in text
    weights = r"2048,2048|2048,11264|5632,2048|49152,2048|2048,49152"
    moved = _written_out_again(text, weights + r"|48,19,1536,2048")
    assert not moved, moved
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    cache = 2 * math.prod(arena["k"].shape) * 2
    assert memory.alias_size_in_bytes >= cache
    # One set of weights: 12 x 51.39M + 201.3M parameters in bfloat16 beside
    # the cache, not four.
    assert 13.0e9 < memory.argument_size_in_bytes < 13.25e9
    assert memory.temp_size_in_bytes < 0.3e9, memory


# -- a dense decoder with a recurrent state, the model whole (PR 59) ------------

GRANITE = dict(
    layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
    d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192, mamba_heads=64,
    mamba_head_dim=64, n_groups=1, state_size=128, conv_kernel=4, chunk=256,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8, vocab=100352,
    max_seq_len=2048, piece=512, max_streams=80, attention_impl="flash",
    record=True)


@pytest.mark.parametrize("which,lanes", [("decode", 1), ("prefill", 1),
                                         ("prefill", 2)])
def test_forty_layers_of_state_and_rows_compile_at_published_widths(
        one_chip, monkeypatch, which, lanes):
    """``granite4_h_micro`` whole (2048; 36 layers of 64 state heads of 64 x
    128 in **one** group, packed two a row; 4 of 32 query heads over 8 key
    heads of **64**; a SwiGLU of 8192 in every layer; 100352 ids and a tied
    head; 80 + 1 slots of 2048): a wave is 36 state calls and 4 grouped-query
    decode calls, a piece a flash call for every count of rows before it (4
    an attention layer and lane) and the chunked form at the published chunk
    of 256 in plain XLA; no piece carries a wave.  No program copies a weight
    (the embedding that is the head above all: one leaf, contracted along its
    minor axis) or writes a state, tail or row leaf out again: the state's
    leaf is 6.1 GB and fits the chip once.  Arguments and temporaries stay
    under the chip's ``bytes_limit`` (16 909 336 064 B, as the v5e reports
    it: PERF.md section 4)."""
    from client_tpu.models.granite_hybrid import GraniteHybridBackend

    backend = GraniteHybridBackend(name="g", **GRANITE)
    assert backend.prefill_piece == (512, 2) and not backend.piece_wave
    assert (backend.pack, backend.chunk, backend.head_dim) == (2, 256, 64)
    text, arena, memory, seconds = _piece_backend_program(
        one_chip, monkeypatch, backend, which, lanes)
    print(f"granite4_h_micro {which} x{lanes}: compiled in {seconds:.1f} s; "
          f"{memory}")
    assert arena["s"].shape == (36, 81, 32, 128, 128)
    assert arena["conv"].shape == (36, 81, 3 * 4352)
    assert arena["k"].shape == arena["v"].shape == (4, 81, 2048, 512)
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    if which == "decode":
        assert calls.count("ssd_wave_update") == 36
        assert calls.count("decode_wave_attention") == 4
        # 80 tokens and a record row a lane.
        assert f"s32[{80 + 80 * backend.stream_record}]" in text
    else:
        assert calls.count("ssd_wave_update") == 0
        assert calls.count("flash_attention") == lanes * 4 * 4
        # A token and 512 record rows a lane.
        assert f"s32[{lanes * (1 + 512 * backend.stream_record)}]" in text
    weights = (r"2048,4096|2048,4352|4096,2048|2048,16384|8192,2048"
               r"|100352,2048|2048,100352")
    leaves = r"36,81,32,128,128|36,81,13056|4,81,2048,512"
    moved = _written_out_again(text, weights + "|" + leaves)
    assert not moved, moved
    assert not re.findall(r"= f32\[36,81,32,128,128\][^=\n]*? copy\(", text)
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    cache = sum(math.prod(arena[k].shape) * arena[k].dtype.itemsize
                for k in ("s", "conv", "k", "v"))
    assert 7.54e9 < cache < 7.56e9
    assert memory.alias_size_in_bytes >= cache
    # 6.38 GB of weights beside the arena.
    assert 13.9e9 < memory.argument_size_in_bytes < 13.97e9
    assert memory.temp_size_in_bytes < (0.1e9 if which == "decode"
                                        else 0.6e9), memory
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 14.6e9 < 16_909_336_064, memory


# -- a parallel block over window and full layers, 128 heads over 8 (PR 53) -----

COHERE = dict(n_layers=4, d_model=4096, n_heads=128, n_kv_heads=8,
              head_dim=128, d_expert=4096, n_experts=128, experts_held=16,
              top_k=8, n_shared=4, window=4096, vocab=32768,
              max_seq_len=25600, piece=512, max_streams=24,
              attention_impl="flash", record=True)


@pytest.mark.parametrize("which,lanes", [("decode", 1), ("prefill", 1),
                                         ("prefill", 2)])
def test_parallel_block_decoder_compiles_at_published_widths(
        one_chip, monkeypatch, which, lanes):
    """At the cell's widths (4096; 128 query heads over 8 key heads of 128;
    16 held of 128 experts of 4096, 8 a token, 4 shared; 32768 ids; 24 + 1
    slots of 3 x 4096 ring rows and 25600 rows): a wave is three ring calls
    and one whole-context call with grouped-query rows of 1024 lanes and
    eight grouped matmuls; a piece holds, a lane, a flash call for every
    count of rows before it (9 a window layer, 50 the full one) and, since
    PR 56 (``piece_wave``), the full wave's 24 rows behind its own:
    the wave's four attention calls and its result beside the piece's, one
    sorted layout for the rows of both.  The tied head is the embedding
    where it lies: no program writes a weight or a cache leaf out again,
    and the donated arena's four leaves are updated in place."""
    from client_tpu.models.cohere_moe import CohereMoeBackend

    backend = CohereMoeBackend(name="c", **COHERE)
    text, arena, memory, seconds = _piece_backend_program(
        one_chip, monkeypatch, backend, which, lanes)
    print(f"command_a_plus {which} x{lanes}: compiled in {seconds:.1f} s; "
          f"{memory}")
    calls = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert calls.count("grouped_matmul") == 8
    # (A wave of its own, or the one that rides in the piece's program.)
    assert calls.count("window_wave_attention") == 3
    assert calls.count("decode_wave_attention") == 1
    # 24 tokens, a record row a lane and the wave's three counts.
    wave = 24 + 24 * backend.stream_record + 3
    if which == "decode":
        assert f"s32[{wave}]" in text
        # The groups of ``expert_ffn_roofline.itl``: 432 rows of sorted
        # layout.
        for width in (8192, 4096):
            assert re.search(rf"f32\[432,{width}\][^=]*? custom-call\(", text)
    else:
        assert calls.count("flash_attention") == lanes * (3 * 9 + 50)
        # One result: the piece's part and the wave's behind it.
        piece = lanes * (1 + 512 * backend.stream_record)
        assert f"s32[{piece + wave}]" in text
    weights = (r"4096,16384|16384,4096|4096,1024|16,4096,8192|16,4096,4096"
               r"|4096,32768|32768,4096")
    leaves = r"[13],25,(?:4096|25600),1024"
    moved = _written_out_again(text, weights + "|" + leaves)
    if which == "prefill":
        # (Rows before a slot's piece, sliced out of a leaf for a flash call,
        # can have W_k's shape: 4096 rows of 1024 lanes.)
        moved = [m for m in moved if m[1:] != ("fusion", "4096,1024")]
    assert not moved, moved
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    cache = sum(math.prod(arena[k].shape) * 2 for k in ("kg", "vg", "kw",
                                                         "vw"))
    assert memory.alias_size_in_bytes >= cache
    assert 13.3e9 < memory.argument_size_in_bytes < 13.4e9
    assert memory.temp_size_in_bytes < (1.0e9 if lanes == 2 else 0.6e9), \
        memory


# The three served configurations' cache leaves: slots, rows a slot, lanes a
# row, dtype; a full wave's lanes, query heads, key heads, the head's width.
_SERVED_LEAVES = {
    "evabyte_6b5": ("decode", (8, 17, 4096, 4096), jnp.bfloat16, 16, 32, 32,
                    128),
    "gpt2_small": ("decode", (12, 49, 1024, 768), jnp.float32, 48, 12, 12,
                   64),
    "smallthinker_21b.global": ("decode", (2, 49, 16384, 512), jnp.bfloat16,
                                48, 28, 4, 128),
    "smallthinker_21b.ring": ("window", (6, 49, 4096, 512), jnp.bfloat16, 48,
                              28, 4, 128),
    "command_a_plus.global": ("decode", (1, 25, 25600, 1024), jnp.bfloat16,
                              24, 128, 8, 128),
    "command_a_plus.ring": ("window", (3, 25, 4096, 1024), jnp.bfloat16, 24,
                            128, 8, 128),
}


@pytest.mark.parametrize("leaf", sorted(_SERVED_LEAVES))
def test_wave_kernel_walks_live_blocks_at_the_served_shapes(one_chip, leaf):
    """The decode-wave kernel alone, at each served leaf's shape and a full
    wave: it compiles for the v5e (the ring of block places fits the fast
    memory, every copy's slice lies on row groups), its call keeps the name
    the benchmark's readers look for in a trace
    (``decode_attn_roofline.itl``, ``window_attn_roofline.itl``), both leaves
    are updated where they lie and nothing else of their size exists."""
    from client_tpu.ops import decode_kernel as dk

    kind, shape, dtype, lanes, h, hkv, d = _SERVED_LEAVES[leaf]
    fn = (dk.decode_wave_attention if kind == "decode"
          else dk.window_wave_attention)

    def sd(s, t):
        return jax.ShapeDtypeStruct(s, t, sharding=one_chip)

    compiled = jax.jit(
        lambda ka, va, q, kn, vn, rows, lens: fn(
            ka, va, q, kn, vn, rows, lens, layer=1),
        donate_argnums=(0, 1)).lower(
            sd(shape, dtype), sd(shape, dtype), sd((lanes, h, d), jnp.float32),
            sd((lanes, hkv, d), jnp.float32), sd((lanes, hkv, d), jnp.float32),
            sd((lanes,), jnp.int32), sd((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    (call,) = re.findall(r"%(\w+?)\.?\d* = [^=]*? custom-call\(", text)
    assert call == kind + "_wave_attention"
    # A block of at most 1 MiB a leaf: three places of K and of V.
    block = dk.wave_block_rows(shape[2], shape[3], dtype)
    assert block * shape[3] * jnp.dtype(dtype).itemsize <= 1 << 20
    assert block % dk.tail_quantum(block, dk.row_group(dtype)) == 0
    memory = compiled.memory_analysis()
    if memory is None:
        pytest.skip("this backend reports no memory analysis")
    leaf_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    assert memory.alias_size_in_bytes >= 2 * leaf_bytes
    assert memory.temp_size_in_bytes < leaf_bytes // 64, memory
