"""Cross-chip KV arena: row-sharded cache + shard_map'd fused decode.

One chip's HBM caps the generative engine at ``max_streams × max_seq_len``
KV rows; this module lifts that ceiling by sharding the arena's *row* axis
over a ``"kv"`` mesh axis with ``NamedSharding`` — each stream's whole
context lives on exactly one chip, so a decode wave needs no cross-chip
softmax (contrast ring_attention.py, which shards the *sequence* axis and
must rotate K/V): the owning shard computes the lane's full attention
locally with the fused kernel (ops/decode_kernel.py) and the per-lane
outputs are combined across the mesh, unowned shards contributing zeros.

Row layout (``arena_row_layout``): the global arena carries one junk row
*per shard* — the last local row of each shard — instead of the
single-chip layout's one trailing dummy row, so every shard has a local
row that absorbs scatters from lanes it does not own (the kernel always
scatters somewhere; pointing unowned lanes at their local junk row keeps
the grid shape static and the real rows untouched).  Shard 0's junk row
doubles as the engine-visible dummy row for padded lanes.

The combine is the cross-chip data plane and comes in two flavors:
``psum`` (XLA's collective) and the default ``ring`` — a Pallas kernel
moving the partial outputs neighbor-to-neighbor with
``make_async_remote_copy`` remote DMA (SNIPPETS.md [3] / pallas_guide.md),
double-buffered with per-slot DMA semaphores, a neighbor barrier and a
per-hop slot handshake.  Both run under ``interpret=True`` on CPU, which
is how the tier-1 suite exercises ≥2 shards on 8 virtual devices
(tests/conftest.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def kv_mesh(n_shards: int):
    """A 1-D ``("kv",)`` mesh over the first ``n_shards`` devices."""
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_shards > len(devices):
        raise ValueError(
            f"kv_shards={n_shards} but runtime has {len(devices)} "
            f"device(s)")
    return Mesh(np.asarray(devices[:n_shards]), ("kv",))


def arena_row_layout(capacity: int, n_shards: int):
    """(total_rows, free_rows, dummy_row) for a ``capacity``-stream arena
    over ``n_shards``.  Unsharded: ``capacity`` real rows plus the one
    trailing dummy.  Sharded: ``capacity`` real rows plus one junk row per
    shard (each shard's last local row), so ``capacity`` must divide
    evenly — every shard then holds ``capacity/n + 1`` rows."""
    if n_shards <= 1:
        return capacity + 1, list(range(capacity)), capacity
    if capacity % n_shards:
        raise ValueError(
            f"max_streams ({capacity}) must be divisible by kv_shards "
            f"({n_shards}) for an even row partition")
    total = capacity + n_shards
    r_loc = total // n_shards
    free = [r for r in range(total) if (r + 1) % r_loc != 0]
    return total, free, r_loc - 1


def shard_arena(arena: dict, mesh):
    """Place an arena pytree on the mesh: k/v rows sharded over ``kv``,
    token slots replicated (they are tiny and every shard gathers them)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    rows = NamedSharding(mesh, P(None, "kv"))
    rep = NamedSharding(mesh, P())
    return {"k": jax.device_put(arena["k"], rows),
            "v": jax.device_put(arena["v"], rows),
            "tok": jax.device_put(arena["tok"], rep)}


# -- ring all-reduce over remote DMA ------------------------------------------


def _ring_kernel(x_ref, o_ref, buf_ref, send_sem, recv_sem, free_sem,
                 *, n_dev: int, axis_name: str):
    """All-reduce-sum by rotating the chunk around the ring n-1 times:
    each step remote-copies the current buffer slot to the right
    neighbor's other slot and accumulates what arrived from the left.
    Double-buffered so a step never sends the slot it is receiving into.

    Two things keep the ring safe on real chips, where neighbors run at
    their own pace: a barrier with both neighbors before the first remote
    write (the target must have entered the kernel, or the write lands in
    VMEM that is not this kernel's yet), and a per-hop handshake — the
    slot written at step ``s+1`` is the one the right neighbor *sent from*
    at step ``s``, so it says when that send has drained (``free_sem``)
    before the slot is overwritten."""
    from jax.experimental.pallas import tpu as pltpu

    logical = pltpu.DeviceIdType.LOGICAL
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n_dev)
    left = jax.lax.rem(my + n_dev - 1, n_dev)

    barrier = pltpu.get_barrier_semaphore()
    for neighbor in (left, right):
        pltpu.semaphore_signal(barrier, inc=1, device_id=neighbor,
                               device_id_type=logical)
    pltpu.semaphore_wait(barrier, 2)

    o_ref[...] = x_ref[...]
    buf_ref[0] = x_ref[...]
    for step in range(n_dev - 1):
        src, dst = step % 2, (step + 1) % 2
        if step >= 1:
            pltpu.semaphore_wait(free_sem, 1)
        copy = pltpu.make_async_remote_copy(
            src_ref=buf_ref.at[src],
            dst_ref=buf_ref.at[dst],
            send_sem=send_sem.at[src],
            recv_sem=recv_sem.at[dst],
            device_id=right,
            device_id_type=logical)
        copy.start()
        copy.wait()
        o_ref[...] += buf_ref[dst]
        if step < n_dev - 2:
            # My send from slot `src` has drained: the left neighbor may
            # overwrite it on its next hop.  Signals and waits pair up
            # (steps 0..n-3 signal, steps 1..n-2 wait), so every
            # semaphore is back at zero when the kernel exits.
            pltpu.semaphore_signal(free_sem, inc=1, device_id=left,
                                   device_id_type=logical)


_LANES = 128


def ring_all_reduce(x, axis_name: str, n_dev: int, *, interpret=False):
    """Sum ``x`` across ``axis_name`` (size ``n_dev``, static) with a
    Pallas remote-DMA ring.  Call under ``shard_map``; the result is
    replicated.  ``n_dev`` must be passed statically — Pallas needs the
    hop count at trace time.

    The payload travels as a lane-dense ``[rows, 128]`` slab (flattened
    and zero-padded): Mosaic refuses to slice a VMEM buffer whose minor
    dim is narrower than the 128-lane tile, which is what a ``[B, H, 64]``
    attention output or a 16-wide embedding row would be.

    ``interpret=True`` runs under Pallas' TPU interpreter
    (``pltpu.InterpretParams``), the one that models remote DMA and
    cross-device semaphores; pass an ``InterpretParams`` to set its
    options (the tests turn on ``detect_races``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if n_dev == 1:
        return x
    if interpret is True:
        interpret = pltpu.InterpretParams()
    flat = x.reshape(-1)
    pad = -flat.size % _LANES
    slab = jnp.pad(flat, (0, pad)).reshape(-1, _LANES)
    kernel = functools.partial(_ring_kernel, n_dev=n_dev,
                               axis_name=axis_name)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(slab.shape, slab.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + slab.shape, slab.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        # collective_id names the barrier semaphore this kernel's devices
        # share; has_side_effects keeps the remote writes from being
        # elided.
        compiler_params=pltpu.CompilerParams(collective_id=0,
                                             has_side_effects=True),
        interpret=interpret,
    )(slab)
    return out.reshape(-1)[:flat.size].reshape(x.shape)


# -- sharded fused decode ------------------------------------------------------


def sharded_decode_attention(mesh, k_arena, v_arena, q, k_new, v_new,
                             rows, lens, *, layer: int,
                             block_s: int | None = None,
                             interpret: bool = False,
                             combine: str = "ring"):
    """The fused decode wave over a row-sharded arena: every shard runs
    ops/decode_kernel.py on its local rows (lanes it does not own scatter
    into its junk row with a zero-length prefix), masks unowned lanes'
    outputs to zero, and the combine sums the partials so each lane's
    answer — computed entirely on its owning shard — lands everywhere.
    Same signature/returns as ``decode_wave_attention`` plus the mesh."""
    from client_tpu.ops.decode_kernel import decode_wave_attention
    from jax.sharding import PartitionSpec as P

    if combine not in ("ring", "psum"):
        raise ValueError(f"combine must be 'ring' or 'psum', "
                         f"got {combine!r}")
    n = mesh.shape["kv"]
    r_loc = k_arena.shape[1] // n

    def body(k_sh, v_sh, q, kn, vn, rows, lens):
        idx = jax.lax.axis_index("kv")
        lo = idx * r_loc
        owned = (rows >= lo) & (rows < lo + r_loc)
        loc_rows = jnp.where(owned, rows - lo, r_loc - 1).astype(jnp.int32)
        loc_lens = jnp.where(owned, lens, 0).astype(jnp.int32)
        k_sh, v_sh, o = decode_wave_attention(
            k_sh, v_sh, q, kn, vn, loc_rows, loc_lens, layer=layer,
            block_s=block_s, interpret=interpret)
        o = jnp.where(owned[:, None, None], o, 0.0).astype(o.dtype)
        if combine == "ring":
            o = ring_all_reduce(o, "kv", n, interpret=interpret)
        else:
            o = jax.lax.psum(o, "kv")
        return k_sh, v_sh, o

    from jax import shard_map

    arena_spec = P(None, "kv")
    rep = P()
    fn = shard_map(body, mesh=mesh,
                   in_specs=(arena_spec, arena_spec, rep, rep, rep, rep,
                             rep),
                   out_specs=(arena_spec, arena_spec, rep),
                   check_vma=False)
    return fn(k_arena, v_arena, q, k_new, v_new, rows, lens)


def sharded_write_prompt_rows(mesh, k_arena, v_arena, k_new, v_new, rows, *,
                              layer: int, interpret: bool = False):
    """Prefill's per-layer write (ops/arena_write.py) over a row-sharded
    arena: every shard copies the lanes whose rows it holds into its local
    rows and leaves the others out.  Same signature/returns as
    ``write_prompt_rows`` plus the mesh."""
    from client_tpu.ops.arena_write import write_prompt_rows
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    r_loc = k_arena.shape[1] // mesh.shape["kv"]

    def body(k_sh, v_sh, kn, vn, rows):
        lo = jax.lax.axis_index("kv") * r_loc
        owned = (rows >= lo) & (rows < lo + r_loc)
        return write_prompt_rows(
            k_sh, v_sh, kn, vn, jnp.where(owned, rows - lo, r_loc - 1),
            owned, layer=layer, interpret=interpret)

    arena_spec = P(None, "kv")
    rep = P()
    fn = shard_map(body, mesh=mesh,
                   in_specs=(arena_spec, arena_spec, rep, rep, rep),
                   out_specs=(arena_spec, arena_spec), check_vma=False)
    return fn(k_arena, v_arena, k_new, v_new, rows)
