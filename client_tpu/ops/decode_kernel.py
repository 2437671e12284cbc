"""Decode-wave kernel: one in-place K/V row write + single-query attention.

The generative engine's hot loop is the decode wave
(engine/generative.py): for every live stream, write the new token's K/V
row into the KV arena at ``(row, len)`` and attend the stream's query over
its valid prefix.  The arena is **lane-dense**: each leaf is
``[L, R, S, H*D]`` — cache rows on the second-minor axis, the heads' features
side by side on the minor axis (GPT-2: 768 = 6 x 128 lanes; a 32 x 128
decoder: 4096), so the chip's tile (``(8, 128)`` float32, ``(16, 128)``
bfloat16) holds it without padding and a row of the arena is what ``h @ wk``
produced, untransposed.  The kernel is a **prefix** kernel and knows nothing
of what a row means: a lane reads rows ``[0, len)`` of its slot and writes row
``len``.  For a full-attention decoder a row is a position and ``len`` the
context length; a backend whose cache is the model's own (models/evabyte.py:
chunk summaries first, the current window's exact keys behind them) passes
its count of live rows.  Leaves may be float32 or bfloat16: blocks go to the
MXU in the leaf's dtype (float32 at ``Precision.HIGHEST``, bfloat16 in its one
native pass), the softmax carry and the accumulator are float32.  (The earlier ``[L, R, S, H, D]``
leaves had ``[12, 64]`` minor dimensions; the compiler stored them with S
minor-most and re-laid out the whole leaf around every scatter and gather:
PERF.md section 6, PR 25.)

One Pallas grid per layer, ``(B, S // block_s)`` with the key-block index
innermost; the lane's ``(row, len)`` pair arrives by scalar prefetch and the
BlockSpec index maps pick each lane's blocks straight out of the arena, so no
``[B, S, ...]`` gather exists anywhere.  What a wave does to the arena:

- **Reads each live row once.**  Blocks that hold no valid position are not
  fetched: their index map repeats the last valid block, which the pipeline
  sees as unchanged and does not copy again, and their compute is skipped.
- **Writes one row per lane and leaf.**  The arena operand is aliased to the
  output (``input_output_aliases``) and the output stays in HBM
  (``memory_space=ANY``): the kernel copies the aligned row group that
  holds row ``len`` into VMEM, inserts the new row with an iota mask and
  copies the group back (HBM is tiled by 8 rows of float32 and 16 of
  bfloat16, so one row alone is not a DMA the chip accepts).  Nothing else
  of the arena is written.
- **Scores on the MXU.**  The query becomes a block-diagonal ``[Hp, H*D]``
  matrix (row h holds head h's 64 features at their lanes, zero elsewhere),
  so ``scores[h, s] = Qbd @ K_blk^T`` and ``acc[h, :] += p @ V_blk`` are two
  plain matmuls over lane-dense blocks (the block-diagonal form costs H
  times the useful FLOPs: nothing beside a float32 arena's six passes at
  H = 12, and 0.6 ms of a 4.6 ms memory-bound wave at H = 32 in bfloat16's
  single pass); the output row is read off the block diagonal of ``acc``.

Attention follows ``_fa_kernel``'s online-softmax carry
(ops/flash_attention.py) with a *strict* ``pos < len`` mask over the old
arena content; the new token's term (position ``len``, whose value is the
k/v being written) is folded in at the finalize step from registers, so the
kernel never reads back its own write and the order of the group's
write-back against the pipeline's block reads cannot matter.

The **latent** kernel (``latent_wave_attention``: one row a position, shared
by every head) has the same contract and another walk.  Its grid is the lanes
alone and the arena stays in HBM: a lane's program loops over the lane's
``ceil(len / block_s)`` live blocks, a trip count read from the prefetched
``lens``, and the wave's live blocks, lane after lane, are one stream of
``make_async_copy`` through a ring of three VMEM buffers, two copies ahead of
the products.  No step exists without a block, a lane of length 0 copies
none, and a lane's first block is on its way before the lane before it has
finished.  (On the v5e, against the ``(B, S // block_s)`` grid it had: a live
block 1.36 -> 1.20 us, a lane without one 2.81 -> 1.29 us where a slot has
eight blocks, a call at ``pangu_ultra_moe.reasoning``'s lengths 0.83 -> 0.59
ms and at ``kimi_linear.longgen``'s 2.87 -> 2.16 ms: PERF.md section 6,
PR 40.)

``interpret=True`` runs the same kernel on CPU; the tier-1 suite and
ci_check drive it that way (tests/test_ops.py parity suite).  The sharded
cross-chip variant wraps this kernel per shard — see
client_tpu/parallel/kv_shard.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def row_group(dtype) -> int:
    """Rows of one HBM tile: arrays are tiled (8, 128) in 32-bit words, so a
    DMA's slice of the second-minor axis starts and ends on a multiple of 8
    rows of float32 and of 16 rows of bfloat16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def pick_block_s(seq_len: int, cap: int = 512) -> int:
    """Largest multiple-of-8 divisor of ``seq_len`` up to ``cap`` (falls
    back to ``seq_len`` itself when no aligned divisor exists).  A block is
    ``block_s x H*D`` floats of K and of V, double-buffered: 512 x 768 is
    1.5 MB each, long enough a DMA to run near HBM bandwidth and short
    enough that a row's tail beyond ``len`` is mostly skipped."""
    best = None
    for cand in range(8, min(cap, seq_len) + 1, 8):
        if seq_len % cand == 0:
            best = cand
    return best if best is not None else seq_len


def _decode_kernel(rows_ref, lens_ref, *refs, layer, block_s: int,
                   head_dim: int, sm_scale: float, ring: int = 0,
                   ring_rows: int = 0, q_group: int = 0):
    """One (lane, key-block) grid step; key blocks iterate innermost so the
    scratch carries the online-softmax state across one lane's row.
    ``layer`` is a Python int, or ``None`` when the layer index arrives as
    the last scalar-prefetch operand (a decoder that scans over its layers).
    ``ring`` > 0: the row a lane writes arrives apart from its count of live
    rows (a third scalar-prefetch operand), and a step sees ``ring`` keys, the
    new one among them: old content that many positions back or more is
    masked (with ``ring`` the slot's ``ring_rows``, the written row alone).
    ``q_group`` > 0: grouped-query rows, ``q_group`` query heads to a key
    head; q and o are ``[Hp, D]`` a lane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    write_ref = None
    if ring:
        write_ref, refs = refs[0], refs[1:]
    if layer is None:
        layer = refs[0][0]
        refs = refs[1:]
    (k_ref, v_ref, q_ref, kn_ref, vn_ref,               # inputs
     ko_ref, vo_ref, o_ref,                             # outputs
     m_ref, l_ref, acc_ref, kbuf, vbuf, sem) = refs     # scratch
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)
    row = rows_ref[b]
    length = lens_ref[b]                 # valid prefix length (strict)
    # The row the new token goes to: behind the live rows, or in a ring the
    # row of the position that leaves the window.
    write = length if write_ref is None else write_ref[b]
    hp, hd = acc_ref.shape
    group = kbuf.shape[0]
    g0 = pl.multiple_of((write // group) * group, group)

    def group_copies(read: bool):
        """The aligned row group around row ``write``, K and V: arena ->
        VMEM (``read``) or back."""
        out = []
        for i, (arena, buf) in enumerate(((ko_ref, kbuf), (vo_ref, vbuf))):
            hbm = arena.at[layer, row, pl.ds(g0, group)]
            out.append(pltpu.make_async_copy(hbm, buf, sem.at[i]) if read
                       else pltpu.make_async_copy(buf, hbm, sem.at[2 + i]))
        return out

    @pl.when(ik == 0)
    def _init():
        for copy in group_copies(read=True):
            copy.start()
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-diagonal query: row h keeps head h's lanes of the scaled q.
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
    if q_group:
        # Query head i reads key head i // q_group: its features stand at
        # that key head's lanes (a padded head's at none).
        head = head // q_group
    own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)
    q_row = q_ref[0] * sm_scale
    if q_group:
        q_row = jnp.concatenate([q_row] * (hd // head_dim), axis=1)
    qbd = jnp.where(own, q_row, 0.0)                         # [Hp, H*D]
    # The MXU takes the arena's dtype: float32 blocks in full precision,
    # bfloat16 blocks (products exact in the float32 accumulator) in one pass.
    cache_dtype = k_ref.dtype
    highest = (jax.lax.Precision.HIGHEST if cache_dtype == jnp.float32
               else None)

    @pl.when(ik * block_s < length)
    def _block():
        # Scores over the OLD prefix content: strictly pos < length
        # (position `length` is the new token, folded in below).
        s = jax.lax.dot_general(
            qbd.astype(cache_dtype), k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=highest)
        pos = ik * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length                                 # [Hp, block_s]
        if ring and ring == ring_rows:
            # A full ring's row ``write`` holds the position that has just
            # left the window (under a full ring, write == length: no row).
            valid = valid & (pos != write)
        elif ring:
            # A window of fewer keys than the ring has rows: row ``pos``
            # holds the position ``ago`` steps back.
            ago = jax.lax.rem(write - pos + (ring_rows - 1), ring_rows) + 1
            valid = valid & (ago < ring)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[...]                                  # [Hp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Block 0 always holds position 0 < length, so m_new is a real
        # score whenever this body runs.
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(cache_dtype), v_ref[...],
            preferred_element_type=jnp.float32,
            precision=highest)                               # [Hp, H*D]

    @pl.when(ik == nk - 1)
    def _finalize():
        # The new row as the arena will hold it (rounded to the leaf's
        # dtype), so this wave and every later one read the same values.
        kn_c, vn_c = kn_ref[0].astype(cache_dtype), vn_ref[0].astype(
            cache_dtype)                                     # [1, H*D]
        kn, vn = kn_c.astype(jnp.float32), vn_c.astype(jnp.float32)
        # Fold in the new token (position `length`, value kn/vn) from
        # registers — it is always valid, so the denominator is > 0 and
        # lanes with an empty prefix (length == 0, i.e. padded lanes on the
        # dummy row) come out as exactly vn instead of NaN.
        s_new = jnp.sum(qbd * kn, axis=1, keepdims=True)     # [Hp, 1]
        m_fin = jnp.maximum(m_ref[...], s_new)
        p_new = jnp.exp(s_new - m_fin)
        corr = jnp.exp(m_ref[...] - m_fin)
        l_fin = l_ref[...] * corr + p_new
        acc = (acc_ref[...] * corr + p_new * vn) / l_fin
        if q_group:
            # Row i's output stands at its key head's lanes.
            key_head = jax.lax.broadcasted_iota(
                jnp.int32, (hp, head_dim), 0) // q_group
            o_ref[0] = sum(
                jnp.where(key_head == j,
                          acc[:, j * head_dim:(j + 1) * head_dim], 0.0)
                for j in range(hd // head_dim)).astype(o_ref.dtype)
        else:
            o_ref[0] = jnp.sum(jnp.where(own, acc, 0.0), axis=0,
                               keepdims=True).astype(o_ref.dtype)
        # The one write into the arena: the new row, inside its row group.
        for copy in group_copies(read=True):
            copy.wait()
        ins = jax.lax.broadcasted_iota(
            jnp.int32, kbuf.shape, 0) == write - g0
        kbuf[...] = jnp.where(ins, kn_c, kbuf[...])
        vbuf[...] = jnp.where(ins, vn_c, vbuf[...])
        for copy in group_copies(read=False):
            copy.start()
        for copy in group_copies(read=False):
            copy.wait()


def _wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens, *,
                    layer, block_s, interpret, layer_index, ring: int):
    """``decode_wave_attention`` and, with ``ring``,
    ``window_wave_attention``: one kernel, static switches."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, s, hd = k_arena.shape
    bsz, h, d = q.shape
    # Grouped-query rows: a row holds fewer key heads than q has heads.
    q_group = 0 if h * d == hd else h * d // hd
    if h * d != hd and (hd % d or h % (hd // d)):
        raise ValueError(f"arena rows hold {hd} features, q has {h} x {d}")
    if block_s is None:
        block_s = pick_block_s(s)
    if s % block_s:
        raise ValueError(f"block_s ({block_s}) must divide max_seq_len "
                         f"({s})")
    if ring > s:
        raise ValueError(f"a window of {ring} keys in a ring of {s} rows")
    hp = -(-h // 8) * 8                  # heads padded to whole sublanes
    group = math.gcd(s, row_group(k_arena.dtype))
    dynamic = layer is None
    if ring:
        # Context length n: position n goes to row n mod S, and the live
        # rows are the min(n, S) the slot holds.
        prefetch = (rows, jnp.minimum(lens, s), jax.lax.rem(lens, s))
    else:
        prefetch = (rows, lens)
    if dynamic:
        prefetch += (jnp.asarray(layer_index, jnp.int32).reshape(1),)

    def arena_map(b, ik, rows, lens, *more):
        # Blocks beyond the last valid position repeat that block's index:
        # the pipeline does not fetch an unchanged block again.
        last = jnp.maximum(lens[b] - 1, 0) // block_s
        return (more[-1][0] if dynamic else layer, rows[b],
                jnp.minimum(ik, last), 0)

    def lane_map(b, ik, rows, lens, *more):
        return (b, 0, 0)

    block = pl.BlockSpec((None, None, block_s, hd), arena_map)
    vec = pl.BlockSpec((1, 1, hd), lane_map)
    # Grouped-query rows: a lane's q and o are its heads' rows, [Hp, D].
    q_vec = pl.BlockSpec((1, hp, d), lane_map) if q_group else vec
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bsz, s // block_s),
        in_specs=[block, block, q_vec, vec, vec],  # k, v arena; q, kn, vn
        out_specs=[in_hbm, in_hbm, q_vec],         # k, v arena; o
        scratch_shapes=[
            pltpu.VMEM((hp, 1), jnp.float32),      # running max
            pltpu.VMEM((hp, 1), jnp.float32),      # running denominator
            pltpu.VMEM((hp, hd), jnp.float32),     # weighted accumulator
            pltpu.VMEM((group, hd), k_arena.dtype),    # K row group
            pltpu.VMEM((group, hd), v_arena.dtype),    # V row group
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    kernel = functools.partial(_decode_kernel, layer=layer, block_s=block_s,
                               head_dim=d, sm_scale=1.0 / np.sqrt(d),
                               ring=ring, ring_rows=s, q_group=q_group)
    block_bytes = block_s * hd * k_arena.dtype.itemsize
    if q_group:
        q_in = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    else:
        q_in = q.reshape(bsz, 1, hd)
    k_out, v_out, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_arena.shape, k_arena.dtype),
            jax.ShapeDtypeStruct(v_arena.shape, v_arena.dtype),
            jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        ],
        # Operand indices count the scalar-prefetch args: rows=0, lens=1
        # (the written rows, the layer index), then k_arena, v_arena.
        input_output_aliases={len(prefetch): 0, len(prefetch) + 1: 1},
        # K and V blocks double-buffered, plus the matmuls' operand copies.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, 10 * block_bytes + (16 << 20))),
        interpret=interpret,
        # The window layers' calls are told from the global ones by name
        # in a trace.
        **({"name": "window_wave_attention"} if ring else {}),
    )(*prefetch, k_arena, v_arena, q_in,
      k_new.reshape(bsz, 1, hd), v_new.reshape(bsz, 1, hd))
    return k_out, v_out, o[:, :h] if q_group else o.reshape(bsz, h, d)


@functools.partial(jax.jit, static_argnames=("layer", "block_s",
                                             "interpret"))
def decode_wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens, *,
                          layer: int | None, block_s: int | None = None,
                          interpret: bool = False, layer_index=None):
    """One layer's decode wave over the KV arena.

    k_arena/v_arena: ``[L, R, S, H*D]``, float32 or bfloat16;
    q/k_new/v_new: ``[B, H, D]``; rows/lens: ``[B]`` int32 (lane → arena
    slot, live rows of the slot).  Returns ``(k_arena, v_arena, o)`` with
    the new K/V written at ``(layer, rows[b], lens[b])`` in place (the arena
    operands are aliased to the outputs, so a donated arena is never copied)
    and ``o: [B, H, D]`` the attention read over rows ``0 .. lens[b]``
    inclusive.  ``layer`` is static; a decoder that scans over its layers
    passes ``layer=None`` and the traced index as ``layer_index``.

    **Grouped-query rows.**  Where a row holds fewer key heads than q has
    heads (``[L, R, S, Hkv*D]``, k_new/v_new ``[B, Hkv, D]``, ``H`` a multiple
    of ``Hkv``), query head i reads key head ``i // (H / Hkv)``: the
    block-diagonal query is ``[Hp, Hkv*D]`` with head i's features on that key
    head's lanes, so the MXU pays ``Hkv`` times the useful products, not
    ``H``, and a row is read once for all the heads of its group.
    """
    return _wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens,
                           layer=layer, block_s=block_s, interpret=interpret,
                           layer_index=layer_index, ring=0)


@functools.partial(jax.jit, static_argnames=("layer", "block_s",
                                             "interpret", "window"))
def window_wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens, *,
                          layer: int | None, block_s: int | None = None,
                          interpret: bool = False, layer_index=None,
                          window: int | None = None):
    """``decode_wave_attention`` over a **ring**: a slot's ``S`` rows hold its
    last ``S`` positions, position n at row ``n mod S`` (a sliding-window
    layer's cache).  ``lens`` are context lengths: lane b's new K/V go to row
    ``lens[b] mod S`` and ``o`` is the attention over the new token and the
    ``min(lens[b], S)`` live rows **but the one it overwrites**, whose old
    content is the position that has just left the window (the kernel folds
    the new token in from registers and never reads back its own write).
    The order of a ring's rows does not matter to a softmax: what a position
    is has to be in its key already (rotated before it is written).
    ``window`` < ``S``: a step sees that many keys, the new one among them
    (a window that is no multiple of what fills a ring; rows that hold
    older positions are masked, not skipped).  The same kernel under another
    name, so that a trace tells the two apart."""
    return _wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens,
                           layer=layer, block_s=block_s, interpret=interpret,
                           layer_index=layer_index,
                           ring=k_arena.shape[2] if window is None
                           else window)


def reference_decode_attention(k_arena, v_arena, q, k_new, v_new, rows,
                               lens, *, layer: int, ring: bool = False,
                               window: int | None = None):
    """XLA oracle with the reference path's exact semantics (scatter the
    new K/V, gather the rows, dense masked softmax over ``pos <= len``) —
    the parity target for the kernel, kept next to it like
    ``reference_attention`` is for flash.  Same arena layout, same
    signature (``layer`` may be traced here); the scores are float32 over
    the values the arena holds.  Grouped-query rows (fewer key heads than
    q has heads) are repeated; with ``ring`` the new row goes to ``lens mod
    S`` (``window_wave_attention``: once the ring is full every row is
    live, the written one included; with ``window`` < ``S`` the rows that
    hold the ``window`` newest positions)."""
    bsz, h, d = q.shape
    s, hd = k_arena.shape[2:]
    dt = k_arena.dtype
    at = lens % s if ring else lens
    k_arena = k_arena.at[layer, rows, at].set(
        k_new.reshape(bsz, hd).astype(dt))
    v_arena = v_arena.at[layer, rows, at].set(
        v_new.reshape(bsz, hd).astype(dt))
    ck = k_arena[layer, rows].reshape(bsz, s, hd // d, d).astype(jnp.float32)
    cv = v_arena[layer, rows].reshape(bsz, s, hd // d, d).astype(jnp.float32)
    if h * d != hd:
        ck, cv = (jnp.repeat(c, h * d // hd, axis=2) for c in (ck, cv))
    scores = jnp.einsum("bhd,bshd->bhs", q, ck) / np.sqrt(d)
    mask = jnp.arange(s)[None, :] <= lens[:, None]
    if window is not None:
        # Row r holds the position ``(at - r) mod S`` steps back.
        mask = mask & ((at[:, None] - jnp.arange(s)[None, :]) % s < window)
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    o = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(scores), cv)
    return k_arena, v_arena, o


# -- latent rows: one shared key/value row a position -------------------------

def latent_row_width(rank: int, rope_dim: int) -> int:
    """Lanes of a latent cache row ``[c (rank) | k_r (rope_dim) | 0]``: the
    two parts side by side, padded to whole 128-lane tiles (512 + 64 -> 640).
    One leaf and not two (512 and 128): the bytes are the same, and one leaf
    is one block DMA a block of rows, one score matmul over the row and one row
    group written a lane; the zero lanes cost a ninth of the row's reads."""
    return -(-(rank + rope_dim) // 128) * 128


# Rows of a latent block, and the VMEM buffers the wave's blocks go round.
# Chosen on the v5e (PERF.md section 6, PR 40).  A shorter block wastes less
# of a lane's last one, but the products' cost hardly falls with it (1.15 us
# a block of 512 with no copy at all, 1.04 of 256), so a call at
# ``pangu_ultra_moe.reasoning``'s lengths reads 0.60 ms at 512 and 0.96 at
# 256 (0.68, 0.98 and 0.78 at 1024 through two buffers).  Two buffers leave
# the live block where the grid had it (1.33 us: a copy then starts into the
# buffer the block before was just read from); three take it to 1.20, four
# read the same.
_LATENT_BLOCK_S = 512
_LATENT_RING = 3


def _latent_kernel(rows_ref, lens_ref, *refs, layer, block_s: int,
                   value_dim: int):
    """One lane of ``latent_wave_attention``: a loop over the lane's live
    blocks, ``ceil(len / block_s)`` of them.  The wave's live blocks, lane
    after lane, are one stream through a ring of VMEM buffers: ``walk``
    (SMEM) holds the lane and block of the next one to fetch and how many
    were fetched and used, so the copies run ahead of the products across a
    lane's end, over lanes without a live row, and a lane never starts with
    an exposed copy.  As ``_decode_kernel`` but every head reads the same
    row, whose first ``value_dim`` lanes are also the value.  The heads run
    along the lanes: scores ``[block_s, H]``, the softmax carry ``[1, H]``,
    the accumulator ``[V, H]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if layer is None:
        layer = refs[0][0]
        refs = refs[1:]
    (c_ref, q_ref, new_ref,                             # inputs
     co_ref, o_ref,                                     # outputs
     m_ref, l_ref, acc_ref, blocks, buf, sem, walk) = refs      # scratch
    del c_ref                            # aliased: ``co_ref`` is the cache
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    length = lens_ref[b]                 # valid prefix length (strict)
    n_blocks = pl.cdiv(length, block_s)
    ring = blocks.shape[0]
    group = buf.shape[1]
    # ``lax.div`` and ``lax.rem`` on these counts, which are never negative:
    # ``//`` and ``%`` lower through a traced function each, 0.1-0.2 s of a
    # wave program's first use in the serving process (PERF.md section 6,
    # PR 40).
    g0 = pl.multiple_of(jax.lax.div(length, group) * group, group)
    mine = jax.lax.rem(b, 2)
    hbm = co_ref.at[layer, rows_ref[b], pl.ds(g0, group)]
    cache_dtype = co_ref.dtype
    highest = (jax.lax.Precision.HIGHEST if cache_dtype == jnp.float32
               else None)

    def block_copy(lane, i, n):
        """Block ``i`` of ``lane``'s slot, the wave's ``n``-th live one."""
        start = pl.multiple_of(i * block_s, block_s)
        slot = jax.lax.rem(n, ring)
        return pltpu.make_async_copy(
            co_ref.at[layer, rows_ref[lane], pl.ds(start, block_s)],
            blocks.at[slot], sem.at[2 + slot])

    def live_lane(lane):
        """The first lane from ``lane`` on that has a live row, or
        ``lanes``."""
        return jax.lax.while_loop(
            lambda j: (j < lanes) & (lens_ref[jax.lax.min(j, lanes - 1)]
                                     == 0),
            lambda j: j + 1, lane)

    def fetch():
        """Start the copy of the wave's next live block, if one is left."""
        at = walk[0]

        @pl.when(at < lanes)
        def _():
            i, n = walk[1], walk[2]
            block_copy(at, i, n).start()
            walk[2] = n + 1
            last = i + 1 == pl.cdiv(lens_ref[at], block_s)
            walk[1] = jax.lax.select(last, jnp.zeros_like(i), i + 1)

            @pl.when(last)
            def _():
                walk[0] = live_lane(at + 1)

    @pl.when(b == 0)
    def _first():
        walk[0] = live_lane(0)
        walk[1] = 0
        walk[2] = 0
        walk[3] = 0
        jax.lax.fori_loop(0, ring - 1, lambda _, c: fetch(), None)

    pltpu.make_async_copy(hbm, buf.at[mine], sem.at[0]).start()
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    used = walk[3]

    def block(i, carry):
        n = used + i
        fetch()              # into the buffer of the block used before this
        block_copy(b, i, n).wait()
        # Both products stream the block's rows past a stationary 128-wide
        # operand: Q^T, then p.
        blk = blocks[jax.lax.rem(n, ring)]               # [block_s, W]
        s = jnp.dot(blk, q_ref[0], preferred_element_type=jnp.float32,
                    precision=highest)                   # [block_s, Hp]
        pos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, 1), 0)
        valid = pos < length
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[...]                              # [1, Hp]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            blk[:, :value_dim], p.astype(cache_dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=highest)                           # [V, Hp]
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)
    walk[3] = used + n_blocks

    # The new row as the cache will hold it, folded in from registers
    # (position ``length``: always valid, so a padded lane with an empty
    # prefix reads exactly its own row and never divides by zero).
    new_c = new_ref[0].astype(cache_dtype)               # [1, W]
    s_new = jnp.dot(new_c, q_ref[0], preferred_element_type=jnp.float32,
                    precision=highest)                   # [1, Hp]
    m_fin = jnp.maximum(m_ref[...], s_new)
    p_new = jnp.exp(s_new - m_fin)
    corr = jnp.exp(m_ref[...] - m_fin)
    l_fin = l_ref[...] * corr + p_new
    value = new_c[:, :value_dim].astype(jnp.float32).T   # [V, 1]
    o_ref[0] = ((acc_ref[...] * corr + value * p_new)
                / l_fin).astype(o_ref.dtype)
    # The one write into the arena: the new row, inside its row group.  Two
    # group buffers take turns, so the write-back is waited for a lane on,
    # behind that lane's blocks.  Nothing reads what is in flight: a wave's
    # lanes hold slots of their own, but for the padded lanes on the dummy
    # slot, whose groups differ in the one row each of them replaces.
    pltpu.make_async_copy(hbm, buf.at[mine], sem.at[0]).wait()
    ins = jax.lax.broadcasted_iota(
        jnp.int32, buf.shape[1:], 0) == length - g0
    buf[mine] = jnp.where(ins, new_c, buf[mine])

    @pl.when(b > 0)
    def _():     # the lane before's (a wait reads the size, not the place)
        pltpu.make_async_copy(buf.at[1 - mine], hbm, sem.at[1]).wait()

    back = pltpu.make_async_copy(buf.at[mine], hbm, sem.at[1])
    back.start()

    @pl.when(b == lanes - 1)
    def _():
        back.wait()


@functools.partial(jax.jit, static_argnames=("layer", "value_dim", "block_s",
                                             "interpret"))
def latent_wave_attention(c_arena, q, new_row, rows, lens, *, layer,
                          value_dim: int, block_s: int | None = None,
                          interpret: bool = False, layer_index=None):
    """One layer's decode wave over a **latent** cache: one row a position,
    shared by every head (multi-head latent attention with the key/value
    up-projection absorbed into the query and the output).

    c_arena ``[L, R, S, W]`` (float32 or bfloat16), a row ``[c | k_r | 0]``;
    q ``[B, W, H]`` **in the cache's dtype**, column h head h's ``[q_nope
    W_kb | q_rope | 0]`` times the score scale; new_row ``[B, W]``; rows/lens
    ``[B]`` int32.  Returns ``(c_arena, o)``: the new row written at
    ``(layer, rows[b], lens[b])`` in place and ``o [B, value_dim, H]``
    float32, ``softmax(row . q)`` over rows ``0 .. lens[b]`` inclusive
    applied to the rows' first ``value_dim`` lanes.  Scalar prefetch and
    the row-group write are ``decode_wave_attention``'s; the walk and the
    products differ.

    **The walk.**  The grid is the lanes, in order; the arena stays in HBM.
    A lane loops over its ``ceil(len / block_s)`` live blocks and no others,
    and the wave's live blocks are one stream through a ring of
    ``_LATENT_RING`` VMEM buffers (``_latent_kernel``): each iteration starts
    the copy of the block two ahead, which may be the next live lane's, then
    waits for its own.  A lane of length 0 (a padded lane on the dummy row)
    copies no block.  The row group's write-back is waited for a lane on.
    (On the v5e, against a grid step a block place: 588 of a
    ``pangu_ultra_moe.reasoning`` call's 1024 steps and 2200 of a
    ``kimi_linear.longgen`` call's 3950 held no block at 0.29 us each, and a
    live step's 0.29 us ran behind its copy and products, not beside them;
    timings in the module docstring and PERF.md section 6, PR 40.)

    **The products.**  A lane has only its 128 heads to put beside a
    block of 512 rows, so the **block is the operand that streams through
    the MXU** and the 128-wide one stands still: ``scores [s, H] = C_blk [s,
    W] @ Q^T [W, H]`` and ``acc [V, H] += C_blk[:, :V]^T @ p [s, H]`` load 5
    + 4 tiles of 128 x 128 a block with 512 rows past each, where heads as
    rows loaded the block's 20 + 16 tiles with 128 rows past each.  The
    heads lie along the lanes, so the softmax reduces down the sublanes
    (adds of whole registers, no reduction across lanes) and its carry is a
    ``[1, H]`` vector; the query arrives scaled and rounded, so no block's
    work touches it.  Fewer heads than a lane tile are padded to one.  (On
    the v5e, under the grid of then, a live block fell 1.46 -> 1.33 us and a
    layer's 1024 grid steps without one 0.35 -> 0.30 ms; Mosaic transposes
    the block's value lanes for the second product at 0.05 us a block:
    PERF.md section 6, PR 33.)"""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, s, w = c_arena.shape
    bsz, wq, h = q.shape
    if wq != w or new_row.shape != (bsz, w) or q.dtype != c_arena.dtype:
        raise ValueError(
            f"cache rows hold {w} lanes of {c_arena.dtype}, q {wq} of "
            f"{q.dtype}, the new row {new_row.shape}")
    if block_s is None:
        block_s = pick_block_s(s, _LATENT_BLOCK_S)
    if s % block_s:
        raise ValueError(f"block_s ({block_s}) must divide the slot's rows "
                         f"({s})")
    hp = -(-h // 128) * 128
    if hp != h:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, hp - h)))
    group = math.gcd(s, row_group(c_arena.dtype))
    dynamic = layer is None
    prefetch = (rows, lens) + (
        (jnp.asarray(layer_index, jnp.int32).reshape(1),) if dynamic else ())

    def lane_map(b, rows, lens, *li):
        return (b, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bsz,),
        in_specs=[in_hbm,
                  pl.BlockSpec((1, w, hp), lane_map),
                  pl.BlockSpec((1, 1, w), lane_map)],
        out_specs=[in_hbm, pl.BlockSpec((1, value_dim, hp), lane_map)],
        scratch_shapes=[
            pltpu.VMEM((1, hp), jnp.float32),          # running max
            pltpu.VMEM((1, hp), jnp.float32),          # running denominator
            pltpu.VMEM((value_dim, hp), jnp.float32),  # weighted accumulator
            pltpu.VMEM((_LATENT_RING, block_s, w), c_arena.dtype),   # blocks
            pltpu.VMEM((2, group, w), c_arena.dtype),  # the new row's group
            # One semaphore the group's read, one its write-back, one a block.
            pltpu.SemaphoreType.DMA((2 + _LATENT_RING,)),
            pltpu.SMEM((4,), jnp.int32),               # the walk
        ],
    )
    kernel = functools.partial(_latent_kernel, layer=layer, block_s=block_s,
                               value_dim=value_dim)
    block_bytes = block_s * w * c_arena.dtype.itemsize
    c_out, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(c_arena.shape, c_arena.dtype),
                   jax.ShapeDtypeStruct((bsz, value_dim, hp), jnp.float32)],
        input_output_aliases={len(prefetch): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(100 << 20, 6 * block_bytes + (24 << 20))),
        interpret=interpret,
        name="latent_wave_attention",
    )(*prefetch, c_arena, q, new_row.reshape(bsz, 1, w))
    return c_out, o[:, :, :h]


def reference_latent_attention(c_arena, q, new_row, rows, lens, *, layer,
                               value_dim: int):
    """XLA oracle of ``latent_wave_attention`` (same operands, same
    result): scatter the new row, gather each lane's slot, dense masked
    softmax over ``pos <= len`` in float32 over the values the cache and the
    query hold."""
    s = c_arena.shape[2]
    c_arena = c_arena.at[layer, rows, lens].set(new_row.astype(c_arena.dtype))
    c = c_arena[layer, rows].astype(jnp.float32)             # [B, S, W]
    scores = jnp.einsum("bsw,bwh->bsh", c, q.astype(jnp.float32))
    mask = jnp.arange(s)[None, :] <= lens[:, None]
    scores = jnp.where(mask[:, :, None], scores, _NEG_INF)
    o = jnp.einsum("bsv,bsh->bvh", c[..., :value_dim],
                   jax.nn.softmax(scores, axis=1))
    return c_arena, o
