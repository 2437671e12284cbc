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

One Pallas call per layer whose grid is the lanes, in order; the lane's
``(row, len)`` pair arrives by scalar prefetch and the arena stays in HBM
(``memory_space=ANY``, aliased to the output), so no ``[B, S, ...]`` gather
exists anywhere.  What a wave does to the arena:

- **Copies a lane's live rows and nothing else.**  A lane's program loops
  over its ``ceil(len / block_s)`` live blocks, a trip count read from the
  prefetched ``lens``; the wave's live blocks, lane after lane, are one
  stream of ``make_async_copy`` through a ring of three VMEM places (a K and
  a V buffer each), two blocks ahead of the products and across a lane's
  end.  No step exists without a block, a lane of length 0 (a padded lane on
  the dummy row) copies none, and a lane's last block is copied only as far
  as its live rows go, in quanta of whole row groups
  (``wave_block_rows``, ``tail_quantum``, ``copied_rows``).  The products
  run over the whole block under the ``pos < len`` mask; the rows of a place
  that no copy filled hold an earlier block's (the value places are zeroed
  once a call, because ``0 x NaN`` is NaN and a scratch buffer's first
  content is anything).  (On the v5e, against the ``(B, S // 512)`` grid of
  BlockSpec blocks it had, which copied every last block whole and stepped
  over the places behind it: a call at ``evabyte_6b5.longdoc``'s lengths
  0.891 -> 0.737 ms, at ``smallthinker_21b.mixed``'s 1.202 -> 0.972 over
  whole contexts and 0.521 -> 0.489 over rings, at ``gpt2_small.chat``'s
  0.302 -> 0.260; the copies run at 680-745 GB/s, so the time is the copied
  bytes': PERF.md section 6, PR 44.)
- **Writes one row per lane and leaf.**  The kernel copies the aligned row
  group that holds the written row into VMEM (the read starts at the lane's
  head), inserts the new row with an iota mask and copies the group back
  (HBM is tiled by 8 rows of float32 and 16 of bfloat16, so one row alone is
  not a DMA the chip accepts); two group buffers take turns, so the
  write-back is waited for a lane on.  Nothing else of the arena is written.
- **Scores on the MXU.**  The query becomes a block-diagonal ``[Hp, H*D]``
  matrix (row h holds head h's 64 features at their lanes, zero elsewhere),
  so ``scores[h, s] = Qbd @ K_blk^T`` and ``acc[h, :] += p @ V_blk`` are two
  plain matmuls over lane-dense blocks (the block-diagonal form costs H
  times the useful FLOPs: nothing beside a float32 arena's six passes at
  H = 12, and hidden behind the copies at H = 32 in bfloat16's single
  pass); the output row is read off the block diagonal of ``acc``.

Attention follows ``_fa_kernel``'s online-softmax carry
(ops/flash_attention.py) with a *strict* ``pos < len`` mask over the old
arena content; the new token's term (position ``len``, whose value is the
k/v being written) is folded in at the lane's end from registers, so the
kernel never reads back its own write and the order of the group's
write-back against the block copies cannot matter.

The **latent** kernel (``latent_wave_attention``: one row a position, shared
by every head) has the same contract and the same walk over one leaf, its
blocks copied whole (it had the walk first, PR 40; one walker for the two
bodies is ROADMAP C13's).  (On the v5e, against the ``(B, S // block_s)`` grid it had: a live
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
import operator

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
    back to ``seq_len`` itself when no aligned divisor exists): the rows of
    a block of the latent walk and of the flash kernels' tiles; the wave
    kernel's come from ``wave_block_rows``."""
    best = None
    for cand in range(8, min(cap, seq_len) + 1, 8):
        if seq_len % cand == 0:
            best = cand
    return best if best is not None else seq_len


# The wave kernel's walk, chosen on the v5e at the three served shapes and
# their cells' lengths (PERF.md section 6, PR 44; a call in ms, block rows /
# quantum rows).  Under one stream of copies the block hardly matters and
# the quantum does, because the time is the copied bytes': bfloat16 rows of
# 8 KB (16 lanes, 2060 live rows each) 0.737 at 128 / 16, 0.739 at 256 / 16,
# 0.741 at 512 / 16, 0.745 at 512 / 32, 0.760 at 512 / 128, 0.796 at
# 1024 / 256; float32 rows of 3 KB (33 lanes of 860, 15 without a row) 0.260
# at 256 / 16, 0.261 at 512 / 16, 0.269 at 256 / 8 (a copy of 24 KB is too
# short) and 0.282-0.288 at 128; bfloat16 rows of 1 KB (46 lanes of 7660)
# 0.969 at 512 / 16, 0.970 at 512 / 32 and 1024 / 32, 0.972 at 1024 / 64,
# 0.984 at 256 / 64 and 1024 / 256, 1.001 at 2048 / 512.  So: a block of at
# most 1 MiB a leaf (1-4 MB of VMEM a place; 128, 256 and 1024 rows there),
# its last copied in sixteenths or, where those are no whole row groups, in
# row groups.  Two places read 1-2% slower than three, four the same.
_WAVE_BLOCK_BYTES = 1 << 20
_WAVE_RING = 3
_TAIL_PARTS = 16


def wave_block_rows(slot_rows: int, width: int, dtype) -> int:
    """Rows of a block of the wave kernel's walk over leaves ``[..,
    slot_rows, width]``: as many as ``_WAVE_BLOCK_BYTES`` of one leaf
    hold."""
    return pick_block_s(
        slot_rows, max(8, _WAVE_BLOCK_BYTES // (width * jnp.dtype(
            dtype).itemsize)))


def tail_quantum(block_s: int, group: int) -> int:
    """Rows of the pieces a lane's last block is copied in: the block's
    ``_TAIL_PARTS``-th, or the smallest part above it that is whole row
    groups (a DMA's slice starts and ends on one), else the whole block."""
    for parts in range(_TAIL_PARTS, 1, -1):
        if block_s % (parts * group) == 0:
            return block_s // parts
    return block_s


def _live_blocks(n, block_s: int, quantum: int):
    """(blocks, quanta of the last one) that a lane of ``n`` live rows makes
    the kernel copy: a Python int, or a traced count, which goes through
    ``lax``'s primitives (it is never negative, and ``//`` and even ``+`` on
    a tracer go through a traced function each: PERF.md section 6, PR 40 and
    44).  Without a live row there is no block, and the second count means
    nothing."""
    add, sub, mul, div = (
        (operator.add, operator.sub, operator.mul, operator.floordiv)
        if isinstance(n, int)
        else (jax.lax.add, jax.lax.sub, jax.lax.mul, jax.lax.div))
    blocks = div(add(n, block_s - 1), block_s)
    tail = sub(n, mul(sub(blocks, 1), block_s))
    return blocks, div(add(tail, quantum - 1), quantum)


def copied_rows(n: int, slot_rows: int, width: int, dtype,
                block_s: int | None = None) -> int:
    """Rows of a leaf ``[.., slot_rows, width]`` that a lane of ``n`` live
    rows makes the wave kernel copy out of the arena: every block before its
    last whole, the last as far as its live rows' quanta go (a ring's ``n``
    is ``min(context, slot_rows)``).  Times the row's bytes and two leaves
    this is what a call moves a lane, but for the row group it reads and
    writes (``row_group(dtype)`` rows a leaf each way); the kernel's own trip
    counts come from the same ``_live_blocks``."""
    if block_s is None:
        block_s = wave_block_rows(slot_rows, width, dtype)
    quantum = tail_quantum(block_s, math.gcd(slot_rows, row_group(dtype)))
    blocks, last = _live_blocks(int(min(n, slot_rows)), block_s, quantum)
    return (blocks - 1) * block_s + last * quantum if blocks else 0


def _decode_kernel(rows_ref, lens_ref, *refs, block_s: int, quantum: int,
                   head_dim: int, sm_scale: float, ring: int = 0,
                   ring_rows: int = 0, q_group: int = 0):
    """One lane of the wave: a loop over the lane's live blocks,
    ``ceil(len / block_s)`` of them, the online-softmax state in scratch.
    The wave's live blocks, lane after lane, are one stream of copies
    through a ring of VMEM places (a K and a V buffer each): ``walk`` (SMEM)
    holds the lane and block of the next one to fetch and how many were
    fetched and used, so the copies run ahead of the products across a
    lane's end and over lanes without a live row (``_latent_kernel``'s walk,
    with two leaves).  A lane's last block is copied only as far as its live
    rows' quanta go.
    The layer index is the last scalar-prefetch operand, so one body serves
    every layer of a wave program.
    ``ring`` > 0: the row a lane writes arrives apart from its count of live
    rows (a scalar-prefetch operand before it), and a step sees ``ring`` keys, the
    new one among them: old content that many positions back or more is
    masked (with ``ring`` the slot's ``ring_rows``, the written row alone).
    ``q_group`` > 0: grouped-query rows, ``q_group`` query heads to a key
    head; q and o are ``[Hp, D]`` a lane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    write_ref = None
    if ring:
        write_ref, refs = refs[0], refs[1:]
    layer, refs = refs[0][0], refs[1:]
    (k_ref, v_ref, q_ref, kn_ref, vn_ref,               # inputs
     ko_ref, vo_ref, o_ref,                             # outputs
     m_ref, l_ref, acc_ref, qbd_ref, kblocks, vblocks,  # scratch
     kbuf, vbuf, sem, walk) = refs
    del k_ref, v_ref                     # aliased: the outputs are the arena
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    row = rows_ref[b]
    length = lens_ref[b]                 # valid prefix length (strict)
    n_blocks, last_quanta = _live_blocks(length, block_s, quantum)
    # The row the new token goes to: behind the live rows, or in a ring the
    # row of the position that leaves the window.
    write = length if write_ref is None else write_ref[b]
    hp, hd = acc_ref.shape
    places = kblocks.shape[0]
    parts = block_s // quantum
    group = kbuf.shape[1]
    # Scalar arithmetic on traced counts goes through ``lax``'s primitives
    # here and below: an operator on a tracer is a traced function, a third
    # of a millisecond each where the program is built, and this body is
    # built once a layer and wave bucket (PERF.md section 6, PR 44).
    add, mul, select = jax.lax.add, jax.lax.mul, jax.lax.select
    g0 = pl.multiple_of(mul(jax.lax.div(write, group), group), group)
    mine = jax.lax.rem(b, 2)
    cache_dtype = ko_ref.dtype
    # The MXU takes the arena's dtype: float32 blocks in full precision,
    # bfloat16 blocks (products exact in the float32 accumulator) in one pass.
    highest = (jax.lax.Precision.HIGHEST if cache_dtype == jnp.float32
               else None)

    # The aligned row group around row ``write``, K and V: arena -> VMEM
    # and back.  Built once: every start and wait below is one of these.
    group_reads, group_writes = [], []
    for j, (arena, buf) in enumerate(((ko_ref, kbuf), (vo_ref, vbuf))):
        hbm = arena.at[layer, row, pl.ds(g0, group)]
        group_reads.append(
            pltpu.make_async_copy(hbm, buf.at[mine], sem.at[j]))
        group_writes.append(
            pltpu.make_async_copy(buf.at[mine], hbm, sem.at[2 + j]))

    def block_copies(go, n, blocks, quanta, i, slot=None):
        """``go`` (start or wait for) the copies of a lane's block ``i``,
        the wave's ``n``-th live one, K and V into place ``n mod places``:
        one copy a leaf of a whole block, and of the lane's last block (of
        ``blocks``) a copy a quantum as far as its live rows go
        (``quanta``).  ``slot`` is the lane's; a wait reads a copy's size,
        not its place, and gives none."""
        place = jax.lax.rem(n, places)
        sem0 = add(mul(place, 2), 4)
        lyr, slt, plc = (0, 0, 0) if slot is None else (layer, slot, place)

        def copies(size, at=0):
            src = dst = 0
            if slot is not None:
                src = pl.multiple_of(add(mul(i, block_s), at), size)
                dst = at if isinstance(at, int) else pl.multiple_of(at, size)
            for j, (arena, buf) in enumerate(((ko_ref, kblocks),
                                              (vo_ref, vblocks))):
                go(pltpu.make_async_copy(
                    arena.at[lyr, slt, pl.ds(src, size)],
                    buf.at[plc, pl.ds(dst, size)], sem.at[add(sem0, j)]))

        whole = jax.lax.bitwise_or(jax.lax.lt(add(i, 1), blocks),
                                   jax.lax.eq(quanta, parts))

        @pl.when(whole)
        def _():
            copies(block_s)

        @pl.when(jax.lax.bitwise_not(whole))
        def _():
            jax.lax.fori_loop(
                0, quanta, lambda j, c: copies(
                    quantum, 0 if slot is None else mul(j, quantum)), None)

    def fetch(_, carry):
        """Start the copies of the wave's next live block, if one is left:
        ``walk`` holds its lane (or one before it without a live row) and
        block, and how many blocks were fetched."""
        at = jax.lax.while_loop(
            lambda j: jax.lax.bitwise_and(
                jax.lax.lt(j, lanes),
                jax.lax.eq(lens_ref[jax.lax.min(j, lanes - 1)], 0)),
            lambda j: add(j, 1), walk[0])
        walk[0] = at

        @pl.when(jax.lax.lt(at, lanes))
        def _():
            i, n = walk[1], walk[2]
            blocks, quanta = _live_blocks(lens_ref[at], block_s, quantum)
            block_copies(lambda copy: copy.start(), n, blocks, quanta, i,
                         rows_ref[at])
            last = jax.lax.eq(add(i, 1), blocks)
            walk[0] = select(last, add(at, 1), at)
            walk[1] = select(last, jnp.zeros_like(i), add(i, 1))
            walk[2] = add(n, 1)
        return carry

    @pl.when(b == 0)
    def _first():
        # A place's rows behind a last block's quanta keep what an earlier
        # block left there, and the second product multiplies them by p = 0:
        # they have to be finite, which a scratch buffer at its first use is
        # not.  (Keys need nothing: a dead row's score is replaced, not
        # multiplied.)
        def zero(i, carry):
            at = pl.multiple_of(mul(i, quantum), quantum)
            for place in range(places):
                vblocks[place, pl.ds(at, quantum)] = jnp.zeros(
                    (quantum, hd), cache_dtype)
            return carry

        jax.lax.fori_loop(0, parts, zero, None)
        for i in range(4):
            walk[i] = 0

    for copy in group_reads:
        copy.start()
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-diagonal query: row h keeps head h's lanes of the scaled q.
    lane_ix = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
    if q_group:
        # Query head i reads key head i // q_group: its features stand at
        # that key head's lanes (a padded head's at none).
        head = jax.lax.div(head, q_group)
    own = (lane_ix >= head * head_dim) & (lane_ix < (head + 1) * head_dim)
    q_row = q_ref[0] * sm_scale
    if q_group:
        q_row = jnp.concatenate([q_row] * (hd // head_dim), axis=1)
    qbd = jnp.where(own, q_row, 0.0)                         # [Hp, H*D]
    qbd_ref[...] = qbd.astype(cache_dtype)
    used = walk[3]

    def block(i, carry):
        n = add(used, i)
        # One copy ahead into the place of the block used before this; the
        # wave's first block starts the ring's.
        jax.lax.fori_loop(
            0, select(jax.lax.eq(n, 0), jnp.full_like(n, places),
                      jnp.ones_like(n)), fetch, None)
        block_copies(lambda copy: copy.wait(), n, n_blocks, last_quanta, i)
        place = jax.lax.rem(n, places)
        # Scores over the OLD prefix content: strictly pos < length
        # (position `length` is the new token, folded in below).
        s = jax.lax.dot_general(
            qbd_ref[...], kblocks[place], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=highest)
        pos = i * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(cache_dtype), vblocks[place],
            preferred_element_type=jnp.float32,
            precision=highest)                               # [Hp, H*D]
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)
    walk[3] = add(used, n_blocks)

    # The new row as the arena will hold it (rounded to the leaf's dtype),
    # so this wave and every later one read the same values.
    kn_c, vn_c = kn_ref[0].astype(cache_dtype), vn_ref[0].astype(
        cache_dtype)                                         # [1, H*D]
    kn, vn = kn_c.astype(jnp.float32), vn_c.astype(jnp.float32)
    # Fold in the new token (position `length`, value kn/vn) from registers:
    # it is always valid, so the denominator is > 0 and lanes with an empty
    # prefix (length == 0, i.e. padded lanes on the dummy row) come out as
    # exactly vn instead of NaN.
    s_new = jnp.sum(qbd * kn, axis=1, keepdims=True)         # [Hp, 1]
    m_fin = jnp.maximum(m_ref[...], s_new)
    p_new = jnp.exp(s_new - m_fin)
    corr = jnp.exp(m_ref[...] - m_fin)
    l_fin = l_ref[...] * corr + p_new
    acc = (acc_ref[...] * corr + p_new * vn) / l_fin
    if q_group:
        # Row i's output stands at its key head's lanes.
        key_head = jax.lax.div(jax.lax.broadcasted_iota(
            jnp.int32, (hp, head_dim), 0), q_group)
        o_ref[0] = sum(
            jnp.where(key_head == j,
                      acc[:, j * head_dim:(j + 1) * head_dim], 0.0)
            for j in range(hd // head_dim)).astype(o_ref.dtype)
    else:
        o_ref[0] = jnp.sum(jnp.where(own, acc, 0.0), axis=0,
                           keepdims=True).astype(o_ref.dtype)
    # The one write into the arena: the new row, inside its row group, whose
    # read has been under way since the lane's head.  Two group buffers take
    # turns, so the write-back is waited for a lane on, behind that lane's
    # blocks.  Nothing reads what is in flight: a wave's lanes hold slots of
    # their own, but for the padded lanes on the dummy slot, which copy no
    # block and whose groups differ in the one row each of them replaces.
    for copy in group_reads:
        copy.wait()
    ins = jax.lax.broadcasted_iota(
        jnp.int32, kbuf.shape[1:], 0) == write - g0
    kbuf[mine] = jnp.where(ins, kn_c, kbuf[mine])
    vbuf[mine] = jnp.where(ins, vn_c, vbuf[mine])

    @pl.when(b > 0)
    def _():     # the lane before's (a wait reads the size, not the place)
        for copy in group_writes:
            copy.wait()

    for copy in group_writes:
        copy.start()

    @pl.when(b == lanes - 1)
    def _():
        for copy in group_writes:
            copy.wait()


def _layer_operand(layer, layer_index):
    """The layer as the kernel takes it: an operand, static or traced."""
    return jnp.asarray(layer_index if layer is None else layer, jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret", "ring",
                                             "sm_scale"))
def _wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens, layer, *,
                    block_s, interpret, ring: int,
                    sm_scale: float | None = None):
    """``decode_wave_attention`` and, with ``ring``,
    ``window_wave_attention``: one kernel, static switches.  ``layer`` is an
    operand (scalar prefetch) whether the caller's is a Python int or traced:
    a wave program's calls of one shape are then one traced function and one
    lowered kernel, where a static layer made each call its own (0.1 s a
    layer and wave bucket to build, 40-84 of them a served model: PERF.md
    section 6, PR 44)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, s, hd = k_arena.shape
    bsz, h, d = q.shape
    # Grouped-query rows: a row holds fewer key heads than q has heads.
    q_group = 0 if h * d == hd else h * d // hd
    if h * d != hd and (hd % d or h % (hd // d)):
        raise ValueError(f"arena rows hold {hd} features, q has {h} x {d}")
    if block_s is None:
        block_s = wave_block_rows(s, hd, k_arena.dtype)
    if s % block_s:
        raise ValueError(f"block_s ({block_s}) must divide max_seq_len "
                         f"({s})")
    if ring > s:
        raise ValueError(f"a window of {ring} keys in a ring of {s} rows")
    hp = -(-h // 8) * 8                  # heads padded to whole sublanes
    group = math.gcd(s, row_group(k_arena.dtype))
    quantum = tail_quantum(block_s, group)
    if ring:
        # Context length n: position n goes to row n mod S, and the live
        # rows are the min(n, S) the slot holds.
        prefetch = (rows, jnp.minimum(lens, s), jax.lax.rem(lens, s))
    else:
        prefetch = (rows, lens)
    prefetch += (layer.reshape(1),)

    def lane_map(b, *prefetched):
        return (b, 0, 0)

    vec = pl.BlockSpec((1, 1, hd), lane_map)
    # Grouped-query rows: a lane's q and o are its heads' rows, [Hp, D].
    q_vec = pl.BlockSpec((1, hp, d), lane_map) if q_group else vec
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    dtype = k_arena.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bsz,),
        in_specs=[in_hbm, in_hbm, q_vec, vec, vec],    # k, v arena; q, kn, vn
        out_specs=[in_hbm, in_hbm, q_vec],             # k, v arena; o
        scratch_shapes=[
            pltpu.VMEM((hp, 1), jnp.float32),      # running max
            pltpu.VMEM((hp, 1), jnp.float32),      # running denominator
            pltpu.VMEM((hp, hd), jnp.float32),     # weighted accumulator
            pltpu.VMEM((hp, hd), dtype),           # block-diagonal query
            pltpu.VMEM((_WAVE_RING, block_s, hd), dtype),      # K blocks
            pltpu.VMEM((_WAVE_RING, block_s, hd), dtype),      # V blocks
            pltpu.VMEM((2, group, hd), dtype),     # K row group, by turns
            pltpu.VMEM((2, group, hd), dtype),     # V row group
            # The groups' reads (K, V) and write-backs, then K and V a place.
            pltpu.SemaphoreType.DMA((4 + 2 * _WAVE_RING,)),
            pltpu.SMEM((4,), jnp.int32),           # the walk
        ],
    )
    kernel = functools.partial(_decode_kernel, block_s=block_s,
                               quantum=quantum, head_dim=d,
                               sm_scale=(1.0 / np.sqrt(d) if sm_scale is None
                                         else sm_scale), ring=ring,
                               ring_rows=s, q_group=q_group)
    block_bytes = block_s * hd * dtype.itemsize
    if q_group:
        q_in = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    else:
        q_in = q.reshape(bsz, 1, hd)
    k_out, v_out, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_arena.shape, dtype),
            jax.ShapeDtypeStruct(v_arena.shape, dtype),
            jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        ],
        # Operand indices count the scalar-prefetch args: rows=0, lens=1
        # (the written rows, the layer index), then k_arena, v_arena.
        input_output_aliases={len(prefetch): 0, len(prefetch) + 1: 1},
        # The ring's K and V places, plus the matmuls' operand copies.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(100 << 20, (2 * _WAVE_RING + 4)
                                 * block_bytes + (16 << 20))),
        interpret=interpret,
        # The names the calls have in a trace, where the window layers'
        # are told from the global ones by them.
        name="window_wave_attention" if ring else "decode_wave_attention",
    )(*prefetch, k_arena, v_arena, q_in,
      k_new.reshape(bsz, 1, hd), v_new.reshape(bsz, 1, hd))
    return k_out, v_out, o[:, :h] if q_group else o.reshape(bsz, h, d)


def decode_wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens, *,
                          layer: int | None, block_s: int | None = None,
                          interpret: bool = False, layer_index=None,
                          sm_scale: float | None = None):
    """One layer's decode wave over the KV arena.

    k_arena/v_arena: ``[L, R, S, H*D]``, float32 or bfloat16;
    q/k_new/v_new: ``[B, H, D]``; rows/lens: ``[B]`` int32 (lane → arena
    slot, live rows of the slot).  Returns ``(k_arena, v_arena, o)`` with
    the new K/V written at ``(layer, rows[b], lens[b])`` in place (the arena
    operands are aliased to the outputs, so a donated arena is never copied)
    and ``o: [B, H, D]`` the attention read over rows ``0 .. lens[b]``
    inclusive.  ``layer`` is a Python int or, with ``layer=None``, the
    traced ``layer_index``; the kernel takes either as an operand.
    ``sm_scale`` replaces ``1 / sqrt(D)`` where a model scales its scores by
    a number of its own.

    **Grouped-query rows.**  Where a row holds fewer key heads than q has
    heads (``[L, R, S, Hkv*D]``, k_new/v_new ``[B, Hkv, D]``, ``H`` a multiple
    of ``Hkv``), query head i reads key head ``i // (H / Hkv)``: the
    block-diagonal query is ``[Hp, Hkv*D]`` with head i's features on that key
    head's lanes, so the MXU pays ``Hkv`` times the useful products, not
    ``H``, and a row is read once for all the heads of its group.
    """
    return _wave_attention(k_arena, v_arena, q, k_new, v_new, rows, lens,
                           _layer_operand(layer, layer_index),
                           block_s=block_s, interpret=interpret, ring=0,
                           sm_scale=sm_scale)


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
                           _layer_operand(layer, layer_index),
                           block_s=block_s, interpret=interpret,
                           ring=k_arena.shape[2] if window is None
                           else window)


def reference_decode_attention(k_arena, v_arena, q, k_new, v_new, rows,
                               lens, *, layer: int, ring: bool = False,
                               window: int | None = None,
                               sm_scale: float | None = None):
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
    scores = jnp.einsum("bhd,bshd->bhs", q, ck)
    scores = scores / np.sqrt(d) if sm_scale is None else scores * sm_scale
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
