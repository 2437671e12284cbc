"""The two small functions the served decoders share whatever else differs:
the RMS norm and the rotary positions.  One copy each."""

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


def rope(x, pos, theta):
    """Rotary positions, rotate-half pairing: x ``[..., n, H, D]`` float32,
    pos ``[..., n]``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv          # [..., n, D/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
