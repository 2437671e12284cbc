"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context first-class design (no reference counterpart — the reference
is a client stack): each device holds a sequence shard of Q/K/V; K/V (and
the key mask) rotate around the ring with ``jax.lax.ppermute`` while every
device folds the visiting block into a flash-style online softmax
(running max / denominator / accumulator). Communication is N-1 ppermute
steps of the local K/V shard — pure neighbor exchange that XLA maps onto
ICI — and the full [S, S] score matrix never exists anywhere.

Composition: this is the sequence-parallel (context-parallel) axis. It
nests under data parallelism (batch over "dp") and tensor parallelism
(heads over "tp") — see ``dryrun_training_step`` and the long-context
serving backend in ``client_tpu.parallel.serving``.
"""

from __future__ import annotations

import functools

import numpy as np

_NEG_INF = -1e30


def _block_attend(q, k, v, bias, m, l, acc, scale):
    """Fold one visiting K/V block into the online-softmax state.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; bias: [B, Sk];
    m/l: [B, Sq, H, 1]; acc: [B, Sq, H, D] fp32.
    """
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bqhk", q, k).astype(jnp.float32) * scale
    s = s + bias[:, None, None, :].astype(jnp.float32)

    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    safe_m = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    p = jnp.exp(jnp.where(s <= _NEG_INF, -jnp.inf, s) - safe_m)
    corr = jnp.where(m <= _NEG_INF, 0.0, jnp.exp(m - safe_m))
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum(
        "bqhk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, bias, axis_name: str):
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Call under ``shard_map`` (or inside a ``pjit`` region via shard_map):
    q/k/v are the *local* shards [B, S_local, H, D], bias the local
    additive key mask [B, S_local]. Returns the local output shard.
    """
    import jax
    import jax.numpy as jnp

    axis_size = jax.lax.psum(1, axis_name)
    scale = 1.0 / np.sqrt(q.shape[-1])
    b, sq, h, d = q.shape

    m = jnp.full((b, sq, h, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, sq, h, 1), jnp.float32)
    acc = jnp.zeros((b, sq, h, d), jnp.float32)

    def body(i, carry):
        k_blk, v_blk, bias_blk, m, l, acc = carry
        m, l, acc = _block_attend(q, k_blk, v_blk, bias_blk, m, l, acc,
                                  scale)
        # Rotate K/V (+ mask) one hop around the ring; the last fold needs
        # no send, but a uniform loop keeps the collective schedule static.
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        bias_blk = jax.lax.ppermute(bias_blk, axis_name, perm)
        return k_blk, v_blk, bias_blk, m, l, acc

    carry = (k, v, bias, m, l, acc)
    # Python loop: axis_size is static and small (a mesh axis), and an
    # unrolled ring lets XLA overlap each ppermute with the next fold.
    for i in range(axis_size):
        carry = body(i, carry)
    _, _, _, m, l, acc = carry

    denom = jnp.where(l == 0.0, 1.0, l)
    return (acc / denom).astype(q.dtype)


def sequence_parallel_attention(mesh, q, k, v, bias, axis_name: str = "sp"):
    """Convenience wrapper: shard_map ``ring_attention`` over ``mesh``.

    q/k/v: global [B, S, H, D] with S sharded over ``axis_name``; bias:
    global [B, S]. Batch stays sharded over "dp" when the mesh carries it.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    batch = "dp" if "dp" in mesh.shape else None
    qkv_spec = P(batch, axis_name, None, None)
    bias_spec = P(batch, axis_name)
    fn = functools.partial(ring_attention, axis_name=axis_name)
    mapped = shard_map(fn, mesh=mesh,
                       in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec),
                       out_specs=qkv_spec, check_vma=False)
    return mapped(q, k, v, bias)
