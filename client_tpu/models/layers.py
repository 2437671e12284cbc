"""The small functions the served decoders share whatever else differs: the
two norms and the rotary positions (both pairings).  One copy each."""

from __future__ import annotations


def rms_norm(x, g, eps, unit_offset: bool = False):
    """``x / rms(x) * g`` in float32; ``* (1 + g)`` with ``unit_offset`` (a
    published ``norm_add_unit_offset``)."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jnp.reciprocal(jnp.sqrt(var + eps))
    g = g.astype(jnp.float32)
    return x * (1.0 + g if unit_offset else g)


def layer_norm(x, g, eps):
    """``(x - mean(x)) / sqrt(var(x) + eps) * g`` in float32, no bias (the
    cohere family's norm)."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jnp.reciprocal(jnp.sqrt(var + eps)) * g.astype(jnp.float32)


def rope(x, pos, theta, interleaved: bool = False):
    """Rotary positions: x ``[..., n, H, D]`` float32, pos ``[..., n]``.
    The rotate-half pairing (lanes ``i`` and ``i + D/2`` a pair) or, with
    ``interleaved``, the ``rope_gptj`` one (lanes ``2i`` and ``2i + 1``):
    each lane times its pair's cosine plus its partner, signed, times the
    sine; the partner comes by a roll of the lanes, nothing is re-laid."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if interleaved:
        ang = pos.astype(jnp.float32)[..., None] * jnp.repeat(inv, 2)
        cos = jnp.cos(ang)[..., None, :]
        sin = jnp.sin(ang)[..., None, :]
        partner = jnp.where(jnp.arange(d) % 2 == 0, -jnp.roll(x, -1, -1),
                            jnp.roll(x, 1, -1))
        return x * cos + partner * sin
    ang = pos.astype(jnp.float32)[..., None] * inv          # [..., n, D/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
