"""Mamba-2's state-space update (SSD): a scalar decay a head over a per-head
state, no correction.

A head keeps a state ``S [P, N]`` (float32; ``P`` the head's channels, ``N``
the state size; held here as ``S^T [N, P]``) in place of key/value rows.  One position, with the head's
input ``x [P]``, a step ``dt > 0`` and a decay rate ``A < 0`` (both scalars a
head), and ``B, C [N]`` that the ``H / G`` heads of a group share::

    S = exp(dt A) S + (dt x) B^T          y = S C

(the skip term ``D x`` is the model's: it does not touch the state).  Beside
ops/kda.py's delta rule: the decay is one number a head and not a vector, and
nothing is corrected, so a chunk of positions is plain matrix products.

**The arena's leaf is packed**: ``[L, R, H / pack, N, pack * P]``, ``pack``
heads of one group side by side along the minor axis with the state's ``N``
rows above them (``pack_state``; 64 heads of ``[64, 128]`` lie as 32 of ``[128,
128]``).  A head's ``[P, N]`` as it stands would put the sum over ``N`` of ``y
= S C`` across lanes, 8 lane reductions a head and lane; ``[N, P]`` would
leave half of every 128-lane row of the leaf empty.  Packed, ``decay``, ``dt
x`` and ``y`` are whole rows of lanes, ``B`` and ``C`` columns (one transpose
a grid step, as ops/kda.py), and the sum runs down the sublanes.

- :func:`ssd_wave_update`: one position of every lane of a decode wave, on the
  packed leaf **in place**: a Pallas kernel (its grid over lanes and blocks
  of packed heads, index maps and aliasing are ops/state_wave.py's, shared
  with ops/kda.py); a slot's block is read once, advanced and read out while
  it is in VMEM, and written once to where it came from.
  :func:`reference_ssd_update` is its ``jax.numpy`` oracle.
- :func:`ssd_chunk_scan`: ``n`` positions of one sequence from a start state,
  in chunks of ``C`` positions (plain ``jax.numpy``; the state alone walks the
  chunks, under a ``lax.scan``).  :func:`ssd_recurrence` is the line above
  under a ``lax.scan`` over positions, what the chunked form has to equal.

**The chunked form.**  In a chunk with start state ``S_0`` and ``G_r = sum_{s
<= r} dt_s A``: ``y_r = exp(G_r) S_0 C_r + sum_{i <= r} exp(G_r - G_i) (C_r .
B_i) dt_i x_i`` and ``S_C = exp(G_C) S_0 + sum_i exp(G_C - G_i) dt_i x_i
B_i^T``: a ``[C, C]`` matrix a head (``C B^T`` a group, times the pairwise
decay), three products a chunk.  Every ``exp`` is of ``G_r - G_i`` with ``i <=
r``, which is ``<= 0``: ``1 / exp(G_i)`` is never formed, and nothing bounds
the chunk but its ``[C, C]`` (the published ``chunk_size`` is 128).

A padded position has ``dt = 0``: decay 1 and nothing added, the state stands
as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from client_tpu.ops.state_wave import HEAD_BLOCK, state_wave_call

# Positions of a chunk of the chunked form: the published ``chunk_size``.
CHUNK = 128


def pack_state(s, pack: int):
    """A state a head ``[..., H, N, P]`` (``S^T``: the state's ``N`` rows
    above the head's channels) -> the arena's ``[..., H / pack, N, pack *
    P]``."""
    *lead, h, n, p = s.shape
    s = s.reshape(*lead, h // pack, pack, n, p)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // pack, n, pack * p)


def unpack_state(s, pack: int):
    """The arena's ``[..., H / pack, N, pack * P]`` -> ``[..., H, N, P]``."""
    *lead, hp, n, width = s.shape
    s = s.reshape(*lead, hp, n, pack, width // pack)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, hp * pack, n, width // pack)


def _step(s, x, dt, a, b, c):
    """The module docstring's line for one position: ``s [..., H, N, P]``
    (``S^T``), ``x [..., H, P]``, ``dt [..., H]``, ``a [H]``, ``b, c [..., G,
    N]`` -> (s, y ``[..., H, P]``), float32.  Sums on the vector unit, not
    dots: a dot's operands would be rounded to bfloat16 on the chip."""
    group = x.shape[-2] // b.shape[-2]
    b, c = (jnp.repeat(t, group, axis=-2)[..., None] for t in (b, c))
    s = (s * jnp.exp(dt * a)[..., None, None]
         + b * (dt[..., None] * x)[..., None, :])
    return s, jnp.sum(s * c, axis=-2)


def ssd_recurrence(x, dt, a, b, c, s0):
    """Position by position: ``x [n, H, P]``, ``dt [n, H]``, ``a [H]``, ``b, c
    [n, G, N]``, ``s0 [H, N, P]`` (``S^T``, as every state here outside the
    arena) -> (y ``[n, H, P]``, the last state), float32."""
    a = a.astype(jnp.float32)

    def body(s, t):
        return _step(s, *t[:2], a, *t[2:])

    f32 = tuple(t.astype(jnp.float32) for t in (x, dt, b, c))
    s, y = jax.lax.scan(body, s0.astype(jnp.float32), f32)
    return y, s


def reference_ssd_update(s_arena, x, dt, a, b, c, rows, *, layer):
    """XLA oracle of :func:`ssd_wave_update` (same operands, same result)."""
    pack = x.shape[1] // s_arena.shape[2]
    s, y = _step(unpack_state(s_arena[layer, rows].astype(jnp.float32), pack),
                 x, dt, a, b, c)
    return s_arena.at[layer, rows].set(
        pack_state(s, pack).astype(s_arena.dtype)), y


def _wave_kernel(rows_ref, layer_ref, vec_ref, bc_ref, s_ref, s_out_ref,
                 y_ref, *, heads: int, per_group: int):
    """One lane's block of ``heads`` packed heads.  ``vec_ref [2 * heads,
    lanes]`` holds the block's ``decay | dt x``, a row a packed head each;
    ``bc_ref [2 * groups, N]`` its groups' ``B | C``, which multiply the
    state's rows, so they are wanted as columns: one transpose of the tile,
    then a group's vector is a lane of it."""
    del rows_ref, layer_ref
    groups = heads // per_group
    cols = bc_ref[...].T                                   # [N, 2 * groups]
    for h in range(heads):
        g = h // per_group
        s = (s_ref[h].astype(jnp.float32) * vec_ref[h:h + 1, :]
             + cols[:, g:g + 1] * vec_ref[heads + h:heads + h + 1, :])
        s_out_ref[h] = s.astype(s_out_ref.dtype)
        y_ref[h:h + 1, :] = jnp.sum(s * cols[:, groups + g:groups + g + 1],
                                    axis=0, keepdims=True)


def ssd_wave_update(s_arena, x, dt, a, b, c, rows, *, layer,
                    interpret: bool = False):
    """One layer's state update of a decode wave, in place.

    s_arena ``[L, R, H / pack, N, pack * P]`` (the arena's packed leaf,
    donated); ``x [B, H, P]``, ``dt [B, H]``, ``a [H]``, ``b, c [B, G, N]``
    float32; rows ``[B]`` int32, the lanes' slots; ``layer`` the leaf's index
    of this layer (a Python int or a traced scalar).  Returns ``(s_arena, y
    [B, H, P])`` float32: slot ``rows[b]``'s state advanced one position and
    read by ``c``.  Lanes that follow one another on one slot (padded lanes on
    the junk slot) move its block once and leave junk there."""
    _, _, packed, n_state, width = s_arena.shape
    bsz, n_heads, p = x.shape
    groups = b.shape[1]
    pack = n_heads // packed
    per_group = n_heads // groups // pack          # packed heads of a group
    hb = min(HEAD_BLOCK, packed)
    if (pack * packed != n_heads or pack * p != width or packed % hb
            or per_group * pack * groups != n_heads or hb % per_group):
        raise ValueError(
            f"{n_heads} heads of {p} in {groups} groups do not lie in a leaf "
            f"of {packed} x [{n_state}, {width}] by blocks of {hb}")
    nb, gb = packed // hb, hb // per_group
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))                        # [B, H]
    # A block's rows of lanes: [B, nb, 2 * hb, pack * P].
    vec = jnp.stack([jnp.broadcast_to(decay[..., None], x.shape),
                     dt[..., None] * x.astype(f32)], axis=1)
    vec = vec.reshape(bsz, 2, nb, hb, width).swapaxes(1, 2).reshape(
        bsz, nb, 2 * hb, width)
    bc = jnp.stack([b.astype(f32), c.astype(f32)], axis=1)     # [B, 2, G, N]
    bc = bc.reshape(bsz, 2, nb, gb, n_state).swapaxes(1, 2).reshape(
        bsz, nb, 2 * gb, n_state)
    s_out, y = state_wave_call(
        functools.partial(_wave_kernel, heads=hb, per_group=per_group),
        "ssd_wave_update", s_arena, (vec, bc), rows, layer, hb,
        interpret=interpret)
    return s_out, y.reshape(bsz, n_heads, p)


def ssd_chunk_scan(x, dt, a, b, c, s0, *, chunk: int = CHUNK):
    """``n`` positions of one sequence from state ``s0``, chunk by chunk: ``x
    [n, H, P]``, ``dt [n, H]``, ``a [H]``, ``b, c [n, G, N]``, ``s0 [H / pack,
    N, pack * P]`` **as the arena packs it** (``pack`` 1: a state a head) ->
    (y ``[n, H, P]``, the state after position ``n - 1``, packed as ``s0``),
    float32 at full precision.  ``n`` divides into chunks of ``chunk``.  The
    state walks the chunks in the arena's form: a slot's state is read from
    the leaf and written back as it lies (unpacked around the scan, the
    compiler turned the transposition into a layout of the whole leaf and
    copied the leaf to it)."""
    n, n_heads, p = x.shape
    groups, size = b.shape[1], int(chunk)
    packed, _, width = s0.shape
    if (n % size or n_heads % groups or n_heads % packed
            or packed % groups or width * packed != n_heads * p):
        raise ValueError(
            f"{n} positions in chunks of {size}; {n_heads} heads of {p} in "
            f"{groups} groups and a state of {s0.shape}: they do not divide")
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def chunks(t):                       # [n, ...] -> [chunks, C, ...]
        return t.astype(jnp.float32).reshape(n // size, size, *t.shape[1:])

    def lanes(t):                        # [K, C, H, (P)] -> [K, C, H/pack, W]
        if t.ndim == 3:
            t = jnp.broadcast_to(t[..., None], (*t.shape, p))
        return t.reshape(*t.shape[:2], packed, width)

    x, dt, b, c = (chunks(t) for t in (x, dt, b, c))
    big_g = jnp.cumsum(dt * a.astype(jnp.float32), axis=1)     # [K, C, H]
    # exp(G_r - G_i) for i <= r, pairwise: never 1 / exp(G_i).
    seen = jnp.arange(size)[:, None] >= jnp.arange(size)[None, :]
    decay = jnp.exp(jnp.where(
        seen[..., None], big_g[:, :, None] - big_g[:, None, :],
        -jnp.inf))                                             # [K, C, C, H]
    cb = mm("krgn,kign->krig", c, b)                           # [K, C, C, G]
    pair = jnp.repeat(cb, n_heads // groups, axis=-1) * decay
    dtx = dt[..., None] * x                                    # [K, C, H, P]
    within = lanes(mm("krih,kihp->krhp", pair, dtx))
    reads = lanes(jnp.exp(big_g))                              # of S_0
    x_out = lanes(dtx * jnp.exp(big_g[:, -1:] - big_g)[..., None])
    # A packed head's B and C are its group's.
    b_p, c_p = (jnp.repeat(t, packed // groups, axis=2) for t in (b, c))
    last = lanes(jnp.exp(big_g[:, -1:]))[:, 0]                 # [K, H/pack, W]

    def body(s, t):
        within_c, reads_c, c_c, x_c, b_c, last_c = t
        y = within_c + reads_c * mm("rgn,gnw->rgw", c_c, s)
        s = s * last_c[:, None, :] + mm("rgn,rgw->gnw", b_c, x_c)
        return s, y

    s, y = jax.lax.scan(body, s0.astype(jnp.float32),
                        (within, reads, c_p, x_out, b_p, last))
    return y.reshape(n, n_heads, p), s
