"""Kimi Delta Attention (KDA): a gated delta rule over a per-head state.

A head keeps a state ``S [d_k, d_v]`` (float32) in place of key/value rows.
One position, with ``q, k`` (``k`` of unit length), ``v``, a log-decay ``g``
(a vector over ``d_k``, ``<= 0``) and a write strength ``beta`` (a scalar a
head)::

    S' = Diag(exp(g)) S        u = beta (v - S'^T k)
    S  = S' + k u^T            o = S^T q

- :func:`kda_wave_update`: one position of every lane of a decode wave, on the
  arena's state leaf ``[L, R, H, d_k, d_v]`` **in place**: a Pallas kernel
  (its grid, index maps and aliasing are ops/state_wave.py's, shared with
  ops/ssd.py); a slot's block of heads is read once, decayed, corrected and
  read out while it is in VMEM, and written once to where it came from.
  :func:`reference_kda_update`
  is its ``jax.numpy`` oracle (gather, the four lines above, scatter).
- :func:`kda_chunk_scan`: ``n`` positions of one sequence from a start state,
  in chunks of ``C`` (plain ``jax.numpy``; the state alone walks the chunks,
  under a ``lax.scan``).  :func:`kda_recurrence` is the four lines above under
  a ``lax.scan`` over positions, what the chunked form has to equal.

**The chunked form.**  In a chunk with start state ``S_0``, ``G_r = sum_{s <=
r} g_s`` (so ``Gamma_r = exp(G_r)``) and ``A_ri = beta_r sum_d k_r[d] k_i[d]
exp(G_r[d] - G_i[d])`` for ``i < r``: ``U = (I + A)^-1 beta (V - (Gamma K)
S_0)``, ``o_r = S_0^T (Gamma_r q_r) + sum_{i <= r} u_i sum_d q_r[d] k_i[d]
exp(G_r[d] - G_i[d])``, ``S_C = Diag(Gamma_C) S_0 + sum_i (k_i exp(G_C -
G_i)) u_i^T``.  ``A`` is strictly lower triangular, so ``(I + A)^-1 = prod_j
(I + (-A)^(2^j))``, ``log2 C`` factors, computed for all chunks at once.

**Which sub-blocking: none, C is bounded.**  ``1 / Gamma_i`` overflows float32
under strong decay (``g`` of -8 a step passes ``exp(88)`` in eleven steps), so
it is never formed: every ``exp`` here is of ``G_r - G_i`` with ``i <= r``,
which is ``<= 0``, taken pairwise over the chunk's ``[C, C, d_k]``.  That
tensor is what bounds ``C`` (``CHUNK`` = 16: 33 million elements a layer for a
piece of 512 positions and 32 heads); a chunk of 64 with the pairwise form kept
to 16-row sub-blocks and block-to-block decays between them would walk a
quarter of the steps, and is not built.

A padded position has ``g = 0`` and ``beta = 0``: ``u = 0`` and the state
stands as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from client_tpu.ops.state_wave import HEAD_BLOCK, state_wave_call

# Positions of a chunk of the chunked form (the pairwise tensor bounds it: see
# above; on the v5e a layer's piece of 512 read 1.07 ms at 8, 0.88 at 16, 0.93
# at 32, 1.56 at 64: PERF.md section 6, PR 34).
CHUNK = 16


def _step(s, q, k, v, a, beta):
    """The four lines of the module docstring for one position: ``s [...,
    d_k, d_v]``, ``q, k, a = exp(g) [..., d_k]``, ``v [..., d_v]``, ``beta
    [...]`` -> (s, o ``[..., d_v]``), float32."""
    # Sums on the vector unit, not dots: a dot's operands would be rounded
    # to bfloat16 on the chip.
    s = s * a[..., None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return s, jnp.sum(s * q[..., None], axis=-2)


def kda_recurrence(q, k, v, g, beta, s0):
    """Position by position: ``q, k, g [n, H, d_k]``, ``v [n, H, d_v]``,
    ``beta [n, H]``, ``s0 [H, d_k, d_v]`` -> (o ``[n, H, d_v]``, the last
    state), float32."""
    def body(s, x):
        qt, kt, vt, gt, bt = x
        return _step(s, qt, kt, vt, jnp.exp(gt), bt)

    f32 = [x.astype(jnp.float32) for x in (q, k, v, g, beta)]
    s, o = jax.lax.scan(body, s0.astype(jnp.float32), tuple(f32))
    return o, s


def reference_kda_update(s_arena, q, k, v, g, beta, rows, *, layer):
    """XLA oracle of :func:`kda_wave_update` (same operands, same result)."""
    s, o = _step(s_arena[layer, rows].astype(jnp.float32), q, k, v,
                 jnp.exp(g), beta)
    return s_arena.at[layer, rows].set(s.astype(s_arena.dtype)), o


def _wave_kernel(rows_ref, layer_ref, vec_ref, s_ref, s_out_ref, o_ref, *,
                 heads: int):
    """One lane's block of heads.  ``vec_ref [5 * heads, d_k]`` holds the
    block's ``exp(g) | beta k | k | q | beta v``, a row a head each.  The
    first four multiply the state's rows, so they are wanted as columns: one
    transpose of the ``[4 * heads, d_k]`` tile (dense in HBM: heads along the
    minor axis of an array would be padded to whole lanes there), then a
    head's vector is a lane of it.  ``u = beta v - S'^T (beta k)``."""
    del rows_ref, layer_ref
    cols = vec_ref[:4 * heads, :].T                       # [d_k, 4 * heads]
    for h in range(heads):
        def col(part):
            return cols[:, part * heads + h:part * heads + h + 1]

        s = s_ref[h].astype(jnp.float32) * col(0)
        u = vec_ref[4 * heads + h:4 * heads + h + 1, :] - jnp.sum(
            s * col(1), axis=0, keepdims=True)
        s = s + col(2) * u
        s_out_ref[h] = s.astype(s_out_ref.dtype)
        o_ref[h:h + 1, :] = jnp.sum(s * col(3), axis=0, keepdims=True)


def kda_wave_update(s_arena, q, k, v, g, beta, rows, *, layer,
                    interpret: bool = False):
    """One layer's state update of a decode wave, in place.

    s_arena ``[L, R, H, d_k, d_v]`` (the arena's leaf, donated; ``d_k =
    d_v``); ``q, k, g [B, H, d_k]``, ``v [B, H, d_v]``, ``beta [B, H]``
    float32; rows ``[B]`` int32, the lanes' slots; ``layer`` the leaf's index
    of this layer (a Python int or a traced scalar).  Returns ``(s_arena, o
    [B, H, d_v])`` float32: slot ``rows[b]``'s state advanced one position
    and read by ``q``.  Lanes that follow one another on one slot (padded
    lanes on the junk slot) move its block once and leave junk there."""
    _, _, n_heads, d_k, d_v = s_arena.shape
    bsz = q.shape[0]
    hb = min(HEAD_BLOCK, n_heads)
    if n_heads % hb or d_k != d_v:
        raise ValueError(f"{n_heads} heads of {d_k} x {d_v} do not divide "
                         f"into blocks of {hb} square ones")
    nb = n_heads // hb
    f32 = jnp.float32
    beta = beta.astype(f32)[..., None]
    # A block's vectors as rows of d_k lanes: [B, nb, 5 * hb, d_k].
    vec = jnp.stack([jnp.exp(g.astype(f32)), k.astype(f32) * beta,
                     k.astype(f32), q.astype(f32), v.astype(f32) * beta],
                    axis=1)
    vec = vec.reshape(bsz, 5, nb, hb, d_k).swapaxes(1, 2).reshape(
        bsz, nb, 5 * hb, d_k)
    s_out, o = state_wave_call(
        functools.partial(_wave_kernel, heads=hb), "kda_wave_update",
        s_arena, (vec,), rows, layer, hb, interpret=interpret)
    return s_out, o.reshape(bsz, n_heads, d_v)


def kda_chunk_scan(q, k, v, g, beta, s0, *, chunk: int = CHUNK):
    """``n`` positions of one sequence from state ``s0``, chunk by chunk:
    ``q, k, g [n, H, d_k]``, ``v [n, H, d_v]``, ``beta [n, H]``, ``s0 [H, d_k,
    d_v]`` -> (o ``[n, H, d_v]``, the state after position ``n - 1``),
    float32 at full precision.  ``n`` divides into chunks of ``chunk``, a
    power of two (the inverse's factors)."""
    n, n_heads, d_k = q.shape
    c = int(chunk)
    if n % c or c & (c - 1):
        raise ValueError(f"{n} positions in chunks of {c}: the chunk must "
                         "divide them and be a power of two")
    hi = jax.lax.Precision.HIGHEST
    mm = functools.partial(jnp.einsum, precision=hi)

    def chunks(x):          # [n, H, ...] -> [chunks, H, C, ...]
        x = x.astype(jnp.float32).reshape(n // c, c, *x.shape[1:])
        return jnp.swapaxes(x, 1, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    big_g = jnp.cumsum(g, axis=2)                              # [N, H, C, d]
    # exp(G_r - G_i) for i <= r, pairwise: never 1 / Gamma.
    seen = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(
        seen[..., None], big_g[:, :, :, None] - big_g[:, :, None, :],
        -jnp.inf))                                             # [N,H,C,C,d]
    k_seen = k[:, :, None, :] * decay
    kk = jnp.sum(k[:, :, :, None] * k_seen, axis=-1)           # [N, H, C, C]
    qk = jnp.sum(q[:, :, :, None] * k_seen, axis=-1)           # i <= r
    a = jnp.where(seen & ~jnp.eye(c, dtype=bool), kk, 0.0) * beta[..., None]
    # (I + A)^-1 = prod_j (I + (-A)^(2^j)): A^C = 0.
    eye = jnp.eye(c, dtype=jnp.float32)
    inv, power = eye - a, -a
    for _ in range(c.bit_length() - 2):
        power = mm("nhri,nhij->nhrj", power, power)
        inv = mm("nhri,nhij->nhrj", inv, eye + power)
    gamma = jnp.exp(big_g)                                     # [N, H, C, d]
    u0 = mm("nhri,nhiv->nhrv", inv, v * beta[..., None])
    w = mm("nhri,nhid->nhrd", inv, k * gamma * beta[..., None])
    q_in = q * gamma                                           # reads S_0
    k_out = k * jnp.exp(big_g[:, :, -1:, :] - big_g)           # to the end
    last = gamma[:, :, -1, :]                                  # [N, H, d]

    def body(s, x):
        u0_c, w_c, q_c, qk_c, k_c, last_c = x
        u = u0_c - mm("hrd,hdv->hrv", w_c, s)
        o = mm("hrd,hdv->hrv", q_c, s) + mm("hri,hiv->hrv", qk_c, u)
        s = s * last_c[..., None] + mm("hrd,hrv->hdv", k_c, u)
        return s, o

    s, o = jax.lax.scan(body, s0.astype(jnp.float32),
                        (u0, w, q_in, qk, k_out, last))
    return jnp.swapaxes(o, 1, 2).reshape(n, n_heads, -1), s
