"""The call every state-update kernel of a decode wave is made through: one
copy.

``ops/kda.py`` (a gated delta rule) and ``ops/ssd.py`` (a scalar decay) each
advance a wave's slots' states one position on the arena's state leaf ``[L, R,
heads, rows, lanes]`` **in place**.  What a recurrence does to a block of heads
is its ``_wave_kernel``; how a block comes to the body and goes back is written
here: one grid over lanes and head blocks, the lanes' slots and the layer
scalar-prefetched for the state's index map, the lanes' vector operands block
by block beside it, the state's output aliased to its input (a block is read
once and written once, to where it came from).  The pipeline is ``BlockSpec``'s
double buffer (a hand-written stream of copies in its place moved no cell:
PERF.md section 6, PR 46).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Heads of one grid step: 32 of 128 x 128 float32 are 2 MB of state in and as
# much out, double-buffered 8 MB of VMEM (on the v5e a call of 256 lanes of
# ops/kda.py's read 1.93 ms at 8 heads a step, 1.73 at 16, 1.72 at 32: PERF.md
# section 6, PR 34).  A leaf of fewer heads takes them all at once.
HEAD_BLOCK = 32


def state_wave_call(body, name, s_arena, blocks, rows, layer, heads, *,
                    interpret):
    """``body(rows_ref, layer_ref, *block refs, s_ref, s_out_ref, o_ref)`` on
    every (lane, head block) of a wave.

    s_arena ``[L, R, H, rows, lanes]`` (donated); ``blocks``: the lanes'
    vector operands, each ``[B, H / heads, ...]``, a block's part a grid
    step; rows ``[B]`` the lanes' slots; ``layer`` the leaf's index of this
    layer (a Python int or a traced scalar); ``heads`` the leaf's heads a
    block.  -> (s_arena, read-outs ``[B, H / heads, heads, lanes]``
    float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, nb = rows.shape[0], s_arena.shape[2] // heads
    state, width = s_arena.shape[3:], s_arena.shape[-1]
    prefetch = (rows.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1))

    def lane_map(b, ih, rows, layer):
        return (b, ih, 0, 0)

    def state_map(b, ih, rows, layer):
        return (layer[0], rows[b], ih, 0, 0)

    state_spec = pl.BlockSpec((None, None, heads, *state), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bsz, nb),
        in_specs=[*(pl.BlockSpec((None, None, *block.shape[2:]), lane_map)
                    for block in blocks), state_spec],
        out_specs=[state_spec,
                   pl.BlockSpec((None, None, heads, width), lane_map)],
    )
    block_bytes = heads * state[0] * width * s_arena.dtype.itemsize
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s_arena.shape, s_arena.dtype),
                   jax.ShapeDtypeStruct((bsz, nb, heads, width),
                                        jnp.float32)],
        input_output_aliases={len(prefetch) + len(blocks): 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, 6 * block_bytes + (16 << 20))),
        interpret=interpret,
        name=name,
    )(*prefetch, *blocks, s_arena)
