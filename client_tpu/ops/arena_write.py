"""Prefill's write into the key/value arena: one DMA per lane and leaf.

A layer's ``h @ wk`` for a prefill's lanes is ``[B, n, H*D]``: each lane's
slab is already the first ``n`` positions of an arena row (the arena's leaves
are ``[L, R, S, H*D]``, ops/decode_kernel.py).  This kernel copies slab ``b``
to ``arena[layer, rows[b], :n]`` and nothing else: the arena operands are
aliased to the outputs and stay in HBM (``memory_space=ANY``), the rows arrive
by scalar prefetch, and the copies run HBM to HBM side by side.  Every key and
value is written once, as it was produced.  (Until PR 29 the prefill program
stacked every layer's K and V, transposed the stack and scattered it at the
end: 604 MB of temporaries and a loop of whole-row copies, 4.5 ms of a 21.9 ms
GPT-2 prefill, PERF.md section 6.)

``write[b] == 0`` leaves lane ``b`` out: a row-sharded arena's shard writes
only the lanes whose rows it holds (parallel/kv_shard.py).  Padded lanes are
written like any other, into the dummy row their ``rows`` entry names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from client_tpu.ops.decode_kernel import row_group


def _write_kernel(rows_ref, write_ref, k_new, v_new, _k_in, _v_in,
                  k_out, v_out, sem, *, layer: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, n, _ = k_new.shape

    def copies(b):
        dst = (layer, rows_ref[b], pl.ds(0, n))
        return [pltpu.make_async_copy(new.at[b], arena.at[dst], sem.at[i, b])
                for i, (new, arena) in enumerate(((k_new, k_out),
                                                  (v_new, v_out)))]

    for phase in ("start", "wait"):
        for b in range(bsz):
            @pl.when(write_ref[b] != 0)
            def _(b=b, phase=phase):
                for copy in copies(b):
                    getattr(copy, phase)()


def kernel_writes(n: int, dtype) -> bool:
    """Whether ``n`` positions are a slice the chip's DMA accepts: HBM is
    tiled by 8 rows of float32 (16 of bfloat16), and a prompt bucket under
    that goes through ``reference_write_prompt_rows``."""
    return n % row_group(dtype) == 0


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def write_prompt_rows(k_arena, v_arena, k_new, v_new, rows, write=None, *,
                      layer: int, interpret: bool = False):
    """k_arena/v_arena ``[L, R, S, H*D]``; k_new/v_new ``[B, n, H*D]`` with
    ``n <= S`` whole row groups (``kernel_writes``); rows ``[B]`` int32;
    write ``[B]`` (nonzero = copy this lane; default all).  Returns the
    arenas with ``[layer, rows[b], :n]`` holding lane ``b``'s slab, in place
    (a donated arena is never copied)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, n, hd = k_new.shape
    if k_arena.shape[3] != hd or n > k_arena.shape[2]:
        raise ValueError(f"slabs {k_new.shape} do not fit arena rows "
                         f"{k_arena.shape[2:]}")
    if write is None:
        write = jnp.ones(bsz, jnp.int32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    k_out, v_out = pl.pallas_call(
        functools.partial(_write_kernel, layer=layer),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[hbm, hbm, hbm, hbm], out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2, bsz))]),
        out_shape=[jax.ShapeDtypeStruct(k_arena.shape, k_arena.dtype),
                   jax.ShapeDtypeStruct(v_arena.shape, v_arena.dtype)],
        # Operand indices count the scalar-prefetch args (rows, write), then
        # k_new, v_new, k_arena, v_arena.
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(rows.astype(jnp.int32), write.astype(jnp.int32),
      k_new.astype(k_arena.dtype), v_new.astype(v_arena.dtype),
      k_arena, v_arena)
    return k_out, v_out


def reference_write_prompt_rows(k_arena, v_arena, k_new, v_new, rows, *,
                                layer: int):
    """The same write as one XLA scatter per leaf, in place on a donated
    arena: the path of the CPU suite, of the tensor-parallel families (a
    Mosaic call has no partitioning rule) and of prompt buckets shorter than
    a row group."""
    n = k_new.shape[1]
    return (k_arena.at[layer, rows, :n].set(k_new.astype(k_arena.dtype)),
            v_arena.at[layer, rows, :n].set(v_new.astype(v_arena.dtype)))
