"""Flash attention as a Pallas TPU kernel.

Why a kernel at all: XLA's stock attention materializes the [B, H, S, S]
score tensor in HBM — at seq 2048, BERT-base batch 8 that is 1.5 GB of
fp32 traffic per layer, strictly memory-bound. The flash formulation keeps
one (block_q × block_k) score tile in VMEM and carries the online-softmax
running max / denominator / weighted accumulator across key blocks, so HBM
traffic drops from O(S²) to O(S·D) and the MXU stays fed
(pallas_guide.md: VMEM ~16 MB/core, MXU 128×128 tiles).

The public layout is the serving models' native [B, S, H, D]; internally
the kernel runs on [B, H, S, D] (TPU block shapes tile the last two dims —
pallas requires them (8,128)-aligned or full); masking is an additive
[B, S_k] bias (0 keep / -inf drop, the
encoder padding-mask convention) plus an optional causal flag for decoder/
long-context LM use. ``interpret=True`` runs the same kernel on CPU for the
hermetic test suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref,
               m_ref, l_ref, acc_ref,
               *, block_q: int, block_k: int, causal: bool,
               sm_scale: float, prefix: int = 0):
    """One (batch, head, q-block, k-block) grid step.

    Grid iterates k innermost (TPU grids run sequentially), so the VMEM
    scratch (m/l/acc) carries the online-softmax state across k blocks of
    one q block and is re-initialized when the k index wraps to 0.
    """
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Blocks arrive as [1, 1, block, d] / [1, 1, block] — drop unit axes.
    q = q_ref[0, 0]                            # [bq, d]
    k = k_ref[0, 0]                            # [bk, d]
    v = v_ref[0, 0]                            # [bk, d]
    bias = bias_ref[0, 0]                      # [bk]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [bq, bk]
    s = s * sm_scale + bias[None, :].astype(jnp.float32)
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if prefix:
            # The first ``prefix`` keys stand before every query (a cache's
            # summaries); the causal rule holds among the rest.
            q_pos = q_pos + prefix
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

    m_prev = m_ref[:]                          # [bq, 1]
    l_prev = l_ref[:]
    m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows: exp(-inf - -inf) would be NaN.
    safe_m = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    p = jnp.exp(jnp.where(s <= _NEG_INF, -jnp.inf, s) - safe_m)  # [bq, bk]
    correction = jnp.where(m_prev <= _NEG_INF, 0.0,
                           jnp.exp(m_prev - safe_m))
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_ref[:] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new
    l_ref[:] = l_new
    acc_ref[:] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "prefix"))
def flash_attention(q, k, v, bias=None, *, causal: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, prefix: int = 0):
    """Memory-efficient attention. q: [B, S, H, D]; k/v: [B, S_k, H, D] with
    ``S_k = prefix + S``; bias: additive [B, S_k] key mask (0 = attend,
    -inf/-1e9 = masked) or None. Returns [B, S, H, D].

    ``prefix`` = 0 is self-attention (same S for q and k).  With ``prefix``
    > 0 the first ``prefix`` keys are a prefix that **every** query sees
    (subject to ``bias``, which masks the unused part of it) and the causal
    rule applies to the remaining ``S`` keys: a prefill piece attending to
    its cache's chunk summaries and, causally, to its own window
    (models/evabyte.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
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
    if bias is None:
        bias = jnp.zeros((b, s_k), jnp.float32)
    sm_scale = 1.0 / np.sqrt(d)

    # Kernel-internal layout: [B, H, S, D] so blocks tile the (seq, head_dim)
    # trailing dims.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # [B, 1, S]: the unit middle dim makes the (1, 1, block_k) bias block a
    # legal TPU tile (trailing dims equal-or-aligned to the array's).
    bias3 = bias[:, None, :]

    grid = (b, h, s // block_q, s_k // block_k)
    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=sm_scale, prefix=prefix)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, qi, ki: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, d), jnp.float32),   # weighted accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt, bias3)
    return out.transpose(0, 2, 1, 3)


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
