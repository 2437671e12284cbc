"""Flash attention as a Pallas TPU kernel.

Why a kernel at all: XLA's stock attention materializes the [B, H, S, S]
score tensor in HBM — at seq 2048, BERT-base batch 8 that is 1.5 GB of
fp32 traffic per layer, strictly memory-bound. The flash formulation keeps
one (block_q × block_k) score tile in VMEM and carries the online-softmax
running max / denominator / weighted accumulator across key blocks, so HBM
traffic drops from O(S²) to O(S·D) and the MXU stays fed
(pallas_guide.md: VMEM ~16 MB/core, MXU 128×128 tiles).

The kernel reads q, k and v **as the projections write them**: ``[B, S,
H*D]``, the heads' features side by side on the minor axis (the layout of a
key/value arena row, models/generate.py), and writes its output the same
way, which is what ``@ wo`` reads.  Its blocks are ``[block, tile]`` slabs of
that array (``head_tile``): one head where a head fills whole 128-lane tiles
(D = 128), the tile that ``128 // D`` heads share (D = 64: two), and the
whole row where neither holds (the tests' narrow models).  A tile's heads are
told apart as ops/decode_kernel.py tells them: head j's query keeps its own
lanes and zeros elsewhere, so ``q_j @ K^T`` over the tile's lanes is head j's
scores, and ``p_j @ V`` is head j's output on its own lanes (the MXU
contracts 128 deep and writes 128 wide either way).  No operand is transposed
or copied before the call.  (Until PR 29 the kernel ran on ``[B, H, S, D]``
and the wrapper transposed q, k, v in and the output back: 4.4 ms of a 21.9
ms GPT-2 prefill, PERF.md section 6.)  A caller whose arrays are ``[B, S, H,
D]`` passes them so: the reshape is the identity on a row-major array.

Masking is an additive [B, S_k] bias (0 keep / -inf drop, the encoder
padding-mask convention) plus an optional causal flag for decoder/
long-context LM use; a key block that the causal rule masks whole is neither
fetched (its index map repeats the last block that counts) nor computed.
Where one block holds every key (GPT-2's 1024 positions) there is nothing to
carry: each piece of 256 queries takes the keys up to its last one, one
softmax, and writes its rows (0.25 ms a layer on v5e where the carried form,
whose scratch updates serialize the heads, takes 0.36; PERF.md section 6).
``interpret=True`` runs the same kernel on CPU for the hermetic test suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30
_LANES = 128
# Rows of a piece where one block holds the whole causal problem (sub_q).
SUB_Q = 256


def head_tile(n_heads: int, head_dim: int) -> int:
    """Lanes of the unit in which heads are told apart, in a ``[.., H*D]``
    operand: a head where it fills whole 128-lane tiles, the tile that
    ``128 // D`` heads share, or the whole row where neither holds."""
    if head_dim % _LANES == 0:
        return head_dim
    if _LANES % head_dim == 0 and (n_heads * head_dim) % _LANES == 0:
        return _LANES
    return n_heads * head_dim


def _fa_kernel(*refs, block_q: int, block_k: int, sub_q: int,
               grid_qk: tuple, causal: bool, sm_scale: float, prefix: int,
               head_dim: int, has_bias: bool, window: int | None = None):
    """One (batch, lane tile, q-block, k-block) grid step.

    A block is what one DMA brings; the arithmetic takes its queries
    ``sub_q`` rows at a time, each piece against the keys of the block that
    the causal rule lets it see.  Where one block holds all the keys a
    piece's softmax is whole in one pass and its output is written as it
    comes.  Otherwise the grid iterates k innermost (TPU grids run
    sequentially) and the VMEM scratch (m/l/acc, one of each per head of the
    tile) carries the online-softmax state across the k blocks of one q
    block, re-initialized when the k index wraps to 0.
    """
    from jax.experimental import pallas as pl

    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    o_ref = refs[3 + has_bias]
    # Where the grid has one block along an axis its index is 0 at trace
    # time, and so is every causal test that follows from it.
    iq = pl.program_id(2) if grid_qk[0] > 1 else 0
    ik = pl.program_id(3) if grid_qk[1] > 1 else 0
    single = grid_qk[1] == 1
    if not single:
        m_ref, l_ref, acc_ref = refs[4 + has_bias:]
    tile = q_ref.shape[-1]
    heads = tile // head_dim

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)

    def own(j):
        """Head j's lanes of the tile."""
        return (lane >= j * head_dim) & (lane < (j + 1) * head_dim)

    def merge(out, j, o_j):
        """Head j's lanes of ``o_j`` into the tile's output."""
        if heads == 1:
            return o_j
        return jnp.where(own(j), o_j, 0.0 if out is None else out)

    def piece(a: int, width: int, masked: bool):
        """Queries ``a * sub_q ..`` of the block against its first ``width``
        keys."""
        rows = slice(a * sub_q, (a + 1) * sub_q)
        q = q_ref[0, rows]                     # [sq, tile]
        k = k_ref[0, :width]                   # [width, tile]
        v = v_ref[0, :width]                   # [width, tile]
        if masked:
            q_pos = prefix + iq * block_q + a * sub_q + (
                jax.lax.broadcasted_iota(jnp.int32, (sub_q, width), 0))
            k_pos = ik * block_k + (
                jax.lax.broadcasted_iota(jnp.int32, (sub_q, width), 1))
            # The first ``prefix`` keys stand before every query (a cache's
            # summaries); the causal rule holds among the rest.
            visible = q_pos >= k_pos
            if window is not None:
                # The band: a query sees its last ``window`` keys, itself
                # among them (positions count the prefix's keys too).
                visible = visible & (q_pos - k_pos < window)
        out = None
        for j in range(heads):
            # Head j's query keeps its own lanes: over the tile's lanes
            # q_j @ K^T is head j's scores.
            q_j = q if heads == 1 else jnp.where(own(j), q, 0)
            s = jax.lax.dot_general(
                q_j, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [sq, width]
            s = s * sm_scale
            if has_bias:
                s = s + bias_ref[0, 0, :width][None, :].astype(jnp.float32)
            if masked:
                s = jnp.where(visible, s, _NEG_INF)
            m_new = jnp.max(s, axis=-1, keepdims=True)  # [sq, 1]
            if not single:
                m_prev = m_ref[j, rows]
                m_new = jnp.maximum(m_prev, m_new)
            if has_bias:
                # A row whose keys so far are all masked: exp(-inf - -inf)
                # would be NaN.  (Without a bias the first block holds a
                # key every query sees, so m_new is a real score.)
                safe_m = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
                p = jnp.exp(jnp.where(s <= _NEG_INF, -jnp.inf, s) - safe_m)
            else:
                safe_m = m_new
                p = jnp.exp(s - m_new)
            l_new = jnp.sum(p, axis=-1, keepdims=True)
            # p_j @ V over the tile's lanes: head j's output on its own
            # lanes; the other lanes are dropped when the heads are merged.
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [sq, tile]
            if single:
                out = merge(out, j, pv / jnp.where(l_new == 0.0, 1.0, l_new))
                continue
            correction = jnp.exp(m_prev - safe_m)
            if has_bias:
                correction = jnp.where(m_prev <= _NEG_INF, 0.0, correction)
            l_ref[j, rows] = l_ref[j, rows] * correction + l_new
            acc_ref[j, rows] = acc_ref[j, rows] * correction + pv
            m_ref[j, rows] = m_new
        if single:
            o_ref[0, rows] = out.astype(o_ref.dtype)

    if not single:
        @pl.when(ik == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    for a in range(block_q // sub_q):
        if not causal:
            piece(a, block_k, False)
            continue
        q_lo = prefix + iq * block_q + a * sub_q     # the piece's first query
        k_lo = ik * block_k
        if isinstance(q_lo - k_lo, int):
            # The block's place is known: the piece takes the keys up to its
            # last query (in whole lane tiles) and no others.
            width = min(block_k,
                        -(-(q_lo + sub_q - k_lo) // _LANES) * _LANES)
            piece(a, width, k_lo + width - 1 > q_lo or (
                window is not None and q_lo + sub_q - 1 - k_lo >= window))
            continue
        # A block past the piece's last query is skipped (its index map
        # fetched nothing new); one wholly before its first needs no mask.
        needed = k_lo <= q_lo + sub_q - 1
        masked = k_lo + block_k - 1 > q_lo
        if window is not None:
            # So is a block wholly before the band of the piece's first
            # query; one that holds a key before its last query's band is
            # masked.
            needed = needed & (k_lo + block_k - 1 > q_lo - window)
            masked = masked | (q_lo + sub_q - 1 - k_lo >= window)
        pl.when(needed & ~masked)(functools.partial(piece, a, block_k, False))
        pl.when(needed & masked)(functools.partial(piece, a, block_k, True))

    if not single:
        @pl.when(ik == grid_qk[1] - 1)
        def _finalize():
            out = None
            for j in range(heads):
                l_j = l_ref[j]
                out = merge(out, j,
                            acc_ref[j] / jnp.where(l_j == 0.0, 1.0, l_j))
            o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "prefix", "n_heads",
    "sub_q", "sm_scale", "window", "n_kv_heads"))
def flash_attention(q, k, v, bias=None, *, causal: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, prefix: int = 0,
                    n_heads: int | None = None, sub_q: int | None = None,
                    sm_scale: float | None = None,
                    window: int | None = None,
                    n_kv_heads: int | None = None):
    """Memory-efficient attention.  q: ``[B, S, H*D]`` with ``n_heads=H``
    (as ``h @ wq`` leaves it), or ``[B, S, H, D]``; k/v likewise with ``S_k =
    prefix + S`` positions; bias: additive [B, S_k] key mask (0 = attend,
    -inf/-1e9 = masked) or None.  Returns q's shape.

    **Values of another width than the keys** (multi-head latent attention
    before its up-projection is absorbed: q and k ``H x 192`` padded to ``H x
    256``, v ``H x 128``): v is ``[B, S_k, H*Dv]`` and so is what comes back,
    where both widths fill whole 128-lane tiles (a head is then its own
    tile, of either operand).  ``sm_scale`` replaces ``1 / sqrt(D)`` where
    the scores' scale is not the operand's width (the padding above).

    ``prefix`` = 0 is self-attention (same S for q and k).  With ``prefix``
    > 0 the first ``prefix`` keys are a prefix that **every** query sees
    (subject to ``bias``, which masks the unused part of it) and the causal
    rule applies to the remaining ``S`` keys: a prefill piece attending to
    its cache's chunk summaries and, causally, to its own window
    (models/evabyte.py).

    **A band** (``causal`` with ``window``; a sliding-window layer): a query
    sees its last ``window`` keys, itself among them, the ``prefix`` keys
    counted as the positions before the queries (a piece attending to its
    ring's rows and to itself: models/smallthinker.py).  A key block the band
    masks whole, before it or behind it, is neither fetched nor computed.
    **Grouped-query heads** (``n_kv_heads`` < ``n_heads``; k and v ``[B, S_k,
    Hkv*D]``): query head i reads key head ``i // (H / Hkv)``; a head has to
    fill whole 128-lane tiles (it is then its own tile of either operand,
    and the key's tile is picked by the index map).

    ``block_q``/``block_k`` are what one DMA brings (capped at the sequence
    lengths); ``sub_q`` cuts a block's queries into pieces for the
    arithmetic (default: where one block holds the whole causal problem,
    pieces of ``SUB_Q`` rows, each against the keys up to its last query;
    else the block)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = q.shape[:-1] + v.shape[-1:]
    if q.ndim == 4:
        n_heads = shape[2]
        q, k, v = (x.reshape(x.shape[0], x.shape[1], -1) for x in (q, k, v))
    elif n_heads is None:
        raise ValueError("[B, S, H*D] operands need n_heads")
    b, s, hd = q.shape
    hd_v = v.shape[-1]
    q_group = 1
    if n_kv_heads is not None and n_kv_heads != n_heads:
        d = hd // n_heads
        if (window is not None and not causal) or n_heads % n_kv_heads or (
                d % _LANES or k.shape[-1] != n_kv_heads * d
                or hd_v != n_kv_heads * d):
            raise ValueError(
                f"grouped-query heads: {n_heads} x {d} queries over "
                f"{n_kv_heads} key heads need heads of whole {_LANES}-lane "
                f"tiles, k {k.shape} and v {v.shape} of the same width")
        q_group = n_heads // n_kv_heads
        shape, hd_v = q.shape, hd
    if window is not None and not causal:
        raise ValueError("a window is a band under the causal rule")
    if hd % n_heads or hd_v % n_heads:
        raise ValueError(f"{hd} (values {hd_v}) features do not hold "
                         f"{n_heads} heads")
    d, d_v = hd // n_heads, hd_v // n_heads
    if d_v != d and (d % _LANES or d_v % _LANES):
        raise ValueError(
            f"values of another width than the keys ({d_v} against {d}) "
            f"need both to fill whole {_LANES}-lane tiles")
    s_k = k.shape[1]
    if s_k != prefix + s:
        raise ValueError(
            f"keys ({s_k}) must be prefix ({prefix}) + queries ({s})")
    block_q = min(block_q, s)
    block_k = min(block_k, s_k)
    if s % block_q or s_k % block_k:
        raise ValueError(
            f"block sizes ({block_q}/{block_k}) must divide the sequence "
            f"lengths {s}/{s_k}")
    grid_qk = (s // block_q, s_k // block_k)
    if sub_q is None:
        whole = causal and grid_qk == (1, 1) and block_q % SUB_Q == 0
        sub_q = SUB_Q if whole else block_q
    if block_q % sub_q:
        raise ValueError(f"pieces of {sub_q} queries must divide the block "
                         f"({block_q})")
    tile = head_tile(n_heads, d)
    tile_v = tile if d_v == d else d_v

    def k_block(qi, ki):
        if not causal:
            return ki
        # The last key block a query block sees; later ones repeat it, which
        # the pipeline takes as unchanged and does not fetch.
        last = (prefix + (qi + 1) * block_q - 1) // block_k
        if window is None:
            return jnp.minimum(ki, last)
        # And the first: the block of the first key in the band of the
        # query block's first query.
        first = jnp.maximum(prefix + qi * block_q - window + 1, 0) // block_k
        return jnp.clip(ki, first, last)

    def spec(rows, lanes, row_of, heads_a_tile=1):
        return pl.BlockSpec((1, rows, lanes), lambda bi, gi, qi, ki: (
            bi, row_of(qi, ki),
            gi if heads_a_tile == 1 else gi // heads_a_tile))

    def q_block(qi, ki):
        return qi

    q_spec, o_spec = spec(block_q, tile, q_block), spec(block_q, tile_v,
                                                        q_block)
    kv_spec, v_spec = (spec(block_k, tile, k_block, q_group),
                       spec(block_k, tile_v, k_block, q_group))
    operands, in_specs = [q, k, v], [q_spec, kv_spec, v_spec]
    if bias is not None:
        # [B, 1, S_k]: the unit middle dim makes the (1, 1, block_k) bias
        # block a legal TPU tile (trailing dims equal-or-aligned to the
        # array's).
        operands.append(bias[:, None, :])
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k),
            lambda bi, gi, qi, ki: (bi, 0, k_block(qi, ki))))
    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k, sub_q=sub_q,
        grid_qk=grid_qk, causal=causal,
        sm_scale=1.0 / np.sqrt(d) if sm_scale is None else sm_scale,
        prefix=prefix, head_dim=d, has_bias=bias is not None,
        **({} if window is None else {"window": window}))
    heads = tile // d
    out = pl.pallas_call(
        kernel,
        grid=(b, hd // tile) + grid_qk,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, hd_v), q.dtype),
        # The carry across key blocks, where there is more than one.
        scratch_shapes=[] if grid_qk[1] == 1 else [
            pltpu.VMEM((heads, block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((heads, block_q, 1), jnp.float32),   # denominator
            pltpu.VMEM((heads, block_q, tile_v), jnp.float32),  # accumulator
        ],
        interpret=interpret,
    )(*operands)
    return out.reshape(shape)


def reference_attention(q, k, v, bias=None, *, causal: bool = False):
    """O(S²)-memory oracle for tests (same math, XLA-scheduled)."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(d)
    if bias is not None:
        scores = scores + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        mask = np.tril(np.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
