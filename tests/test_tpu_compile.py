"""Compile the decode step for the chip, without the chip.

The TPU's compiler is installed beside JAX and compiles for a device that is
described, not attached (``jax.experimental.topologies``).  Nothing runs, so
these tests say nothing about results or times; they say what the compiled
``jit_decode`` does to the donated key/value arena, which is what made a
decode wave take 900 ms where 4 ms of memory traffic were needed (PERF.md
section 6, PR 25: the compiler stored the ``[.., 12, 64]`` leaves with the
sequence axis minor and re-laid out a whole leaf around every scatter and
gather, 10.6 GB of temporaries that ``memory_stats`` never showed).

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


def _compile_decode(one_chip, monkeypatch, bucket, **overrides):
    """The scheduler's ``jit_decode`` (arena donated, greedy) compiled for
    one v5e chip from shapes alone.  Returns (compiled, arena shapes)."""
    from client_tpu.engine import backend_init
    from client_tpu.models.generate import TinyGptBackend
    from client_tpu.observability import spans

    # The process sees the CPU; the program under test is the chip's.
    monkeypatch.setattr(backend_init, "pallas_interpret", lambda: False)
    backend = TinyGptBackend(name="g", **{**GEOMETRY, **overrides})

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: jax.tree_util.tree_map(jnp.asarray, backend._init_params())))
    arena = on_chip(jax.eval_shape(
        lambda: backend.init_arena(backend.max_streams)))
    lanes_i = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    lanes_f = jax.ShapeDtypeStruct((bucket,), jnp.float32, sharding=one_chip)
    step = jax.jit(spans.named_step(backend.decode_fn(), spans.STEP_DECODE),
                   donate_argnums=(1,), static_argnums=(8,))
    compiled = step.lower(params, arena, lanes_i, lanes_i, lanes_i, lanes_f,
                          lanes_i, lanes_f, False).compile()
    return compiled, arena


def _leaf_bytes(arena):
    k = arena["k"]
    return math.prod(k.shape) * k.dtype.itemsize


def _entry_ops_shaped_like(text, shape):
    """Instructions of the ENTRY computation whose result has ``shape``,
    as (name, opcode) pairs."""
    entry = text[text.index("ENTRY"):]
    dims = ",".join(str(d) for d in shape)
    pat = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?f32\[" + dims
                     + r"\]\S* ([\w\-]+)\(", re.M)
    return pat.findall(entry)


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
        text, arena["k"].shape)
        if op not in ("parameter", "get-tuple-element", "custom-call",
                      "bitcast", "tuple")]
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
