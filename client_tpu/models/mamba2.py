"""A served decoder's Mamba-2 (SSD) mixer as a ``"state"`` layer: one copy.

``models/nemotron_h.py`` (8 groups of B and C, the gated norm a group) and
``models/granite_hybrid.py`` (one group for all heads, the gated norm over
every channel) hold the same mixer at other sizes.  With ``h = N(x; ln)``
``[n, d]``:

``[z | xBC | dt] = h W_in`` (``d_inner | d_inner + 2 G N | H`` columns,
``d_inner = H P``; held by its three column blocks ``wz, wxbc, wdt``: leaves
of whole lanes, which no slice of a product has to cut); ``xBC =
silu(conv(xBC) + b_conv)``, a causal depthwise convolution of ``taps``
positions, zeros before position 0; ``xBC`` splits into ``x [n, H, P]`` and
``B, C [n, G, N]`` (head h reads group ``h // (H / G)``); ``dt = softplus(dt +
dt_bias)`` a head, unclamped; ``A = -exp(A_log)``; the state ``S [P, N]`` a
head, zero at position 0, advanced as ops/ssd.py says; ``y = S C + D x``; ``y
= RMSNorm_groups(y * silu(z)) * w`` (the gate before the norm, the norm over
each of ``norm_groups`` groups' ``d_inner / norm_groups`` channels); out ``y
W_out`` (the residual add is the model's).

The projection's ``xBC`` is rounded to the model's dtype before the
convolution, in a wave and in a piece alike: the tail a slot carries is then
what the piece itself convolved, however a prompt is cut.

The leaves a slot (models/decoder.py's ``"state"`` kind, models/state_layer.py's
frame): ``s [L_s, R, H / pack, N, pack * P]`` float32 (ops/ssd.py's packed
leaf) and ``conv [L_s, R, (taps - 1) * (d_inner + 2 G N)]`` in the model's
dtype.  A model calls ``_mamba_setup`` with its sizes, puts ``_mamba_weights``
into a state layer's tree and ``_state_arena`` into its arena.
"""

from __future__ import annotations

import math

from client_tpu.models.layers import rms_norm
from client_tpu.models.state_layer import StateLayer


class Mamba2Layer(StateLayer):
    """The mixer above, for a backend that sets ``d_model, dtype, rms_eps``
    and supplies ``_mm`` (models/seeded.py)."""

    state_leaves = ("s", "conv")

    def _mamba_setup(self, heads: int, head_dim: int, groups: int,
                     state_size: int, taps: int):
        self.m_heads, self.m_dim = int(heads), int(head_dim)
        self.n_groups, self.state_size = int(groups), int(state_size)
        self.taps = int(taps)
        if self.m_heads % self.n_groups:
            raise ValueError(f"{heads} state heads in {groups} groups")
        # (``S^T [N, P]`` a head: ops/ssd.py.)
        self.state_shape = (self.m_heads, self.state_size, self.m_dim)
        # The gated norm's groups: those of B and C.
        self.norm_groups = self.n_groups
        self.d_inner = self.m_heads * self.m_dim
        # What the convolution mixes: x | B | C.
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.state_size
        # Heads side by side in the state's leaf (ops/ssd.py): as many of one
        # group as fill a row of 128 lanes.
        self.pack = math.gcd(self.m_heads // self.n_groups,
                             max(1, 128 // self.m_dim))

    def _mamba_weights(self, w, mat, gain) -> dict:
        """A state layer's leaves behind its norm (models/seeded.py's
        makers): ``W_in`` by its columns, ``conv [taps, x | B | C]`` and its
        bias, the heads' ``dt_bias, a_log, skip`` (float32:
        ``softplus(dt_bias)`` about 0.001-0.1, ``exp(a_log)`` about 1-16),
        the gated norm's ``gnorm`` and ``wo``."""
        d, hm = self.d_model, self.m_heads
        return dict(
            wz=mat(d, self.d_inner), wxbc=mat(d, self.conv_dim),
            wdt=mat(d, hm),
            conv=w(self.taps, self.conv_dim,
                   scale=1.0 / math.sqrt(self.taps)),
            conv_b=w(self.conv_dim, scale=0.1),
            dt_bias=w(hm, scale=0.8, offset=-4.6, dtype="float32"),
            a_log=w(hm, scale=0.7, offset=1.4, dtype="float32"),
            skip=w(hm, scale=0.1, offset=1.0, dtype="float32"),
            gnorm=gain(self.d_inner), wo=mat(self.d_inner, d))

    def _state_arena(self, r: int, dt) -> dict:
        """The two leaves of ``r`` slots."""
        import jax.numpy as jnp

        n_state = self.layer_kinds.count("state")
        return {
            "s": jnp.zeros((n_state, r, self.m_heads // self.pack,
                            self.state_size, self.pack * self.m_dim),
                           jnp.float32),
            "conv": jnp.zeros((n_state, r, (self.taps - 1) * self.conv_dim),
                              dt)}

    # -- the state layer's parts (models/state_layer.py) --------------------------

    def _state_ops(self):
        from client_tpu.ops.ssd import (reference_ssd_update, ssd_chunk_scan,
                                        ssd_recurrence, ssd_wave_update)

        return (ssd_wave_update, reference_ssd_update, ssd_chunk_scan,
                ssd_recurrence)

    def _state_project(self, lp, x, dtype):
        """A state layer's x ``[n, d]`` float32 -> the convolution's new
        inputs ``xBC [n, conv_dim]`` in the cache's ``dtype``, nothing the
        convolution reads beside them, and what goes round it: the gate z
        ``[n, d_inner]`` and ``dt [n, H]`` (after the softplus), float32."""
        import jax

        h = rms_norm(x, lp["ln"], self.rms_eps)
        z, new = self._mm(h, lp["wz"]), self._mm(h, lp["wxbc"]).astype(dtype)
        dt = jax.nn.softplus(self._mm(h, lp["wdt"]) + lp["dt_bias"])
        return new, None, (z, dt)

    def _state_inputs(self, lp, beside, ext):
        """The convolution's inputs ext ``[..., n + taps - 1, conv_dim]`` (the
        tail, then these rows' projections) -> x ``[..., n, H, P]``, B, C
        ``[..., n, G, N]`` float32."""
        import jax
        import jax.numpy as jnp

        del beside
        n = ext.shape[-2] - self.taps + 1
        ext = ext.astype(jnp.float32)
        taps = lp["conv"].astype(jnp.float32)
        mixed = jax.nn.silu(
            sum(taps[j] * ext[..., j:j + n, :] for j in range(self.taps))
            + lp["conv_b"].astype(jnp.float32))
        lead, gn = mixed.shape[:-1], self.n_groups * self.state_size
        return (mixed[..., :self.d_inner].reshape(*lead, self.m_heads,
                                                  self.m_dim),
                mixed[..., self.d_inner:self.d_inner + gn].reshape(
                    *lead, self.n_groups, self.state_size),
                mixed[..., self.d_inner + gn:].reshape(
                    *lead, self.n_groups, self.state_size))

    def _ssm_output(self, lp, y, x, z):
        """The state's read-outs y ``[..., H, P]`` with the skip term, gated
        by z ``[..., d_inner]`` and normed a group -> ``[..., d_inner]``."""
        import jax
        import jax.numpy as jnp

        y = (y + lp["skip"][:, None] * x).reshape(z.shape) * jax.nn.silu(z)
        y = y.reshape(*z.shape[:-1], self.norm_groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.rms_eps)
        return y.reshape(z.shape) * lp["gnorm"].astype(jnp.float32)

    def _through_state(self, lp, ins, aside, run, pad):
        """A padded position has ``dt = 0``: it moves nothing."""
        import jax.numpy as jnp

        (xs, b, c), (z, dt) = ins, aside
        y = run(xs, pad(dt), -jnp.exp(lp["a_log"]), b, c)
        return self._ssm_output(lp, y, xs, z)
