"""Hybrid linear-attention decoder (`kimi_linear`): a recurrent state beside a
latent cache in one arena, one chip's share of an expert-parallel deployment,
served through the generative path.

The architecture is the public ``Kimi-Linear-48B-A3B-Instruct`` config's
(``model_type`` ``kimi_linear``): layers of two kinds, **KDA** (Kimi Delta
Attention: a gated delta rule on a per-head state, three of every four) and
**latent attention without positions** (``mla_use_nope``, no low-rank query,
one of every four); a leading dense layer and then layers of many routed
experts and a shared one; two RMSNorms a layer, SwiGLU, no biases; a float32
residual stream and float32 logits over bfloat16 matmuls.  With x ``[n, d]``:

- *Block*: ``x += Mixer(N1(x))``; ``x += FFN(N2(x))``; a final RMSNorm; logits
  ``x W_head``.  ``FFN`` as models/latent_moe.py has it (the gate has a
  selection bias: the 8 largest of ``s + b``, weighed by ``s``).
- *KDA layer*, per head of ``d_k = d_v``: ``[q | k | v] = silu(conv(x
  W_qkv))``, a causal depthwise convolution of ``taps`` positions; ``q =
  l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``; ``g = -exp(A_log[h]) *
  softplus(x W_fa W_fb + dt_bias)`` (a vector over ``d_k``); ``beta =
  sigmoid(x W_b)``; the state ``S [d_k, d_v]``, zero at position 0, advanced
  as ops/kda.py says; the output ``W_o [RMSNorm_head(o) * sigmoid(x W_ga
  W_gb)]``.
- *Latent layer*: models/latent_moe.py's, the query ``x W_q`` straight to the
  heads' ``[nope | rope]`` and **nothing rotated** (the ``rope`` features are
  64 more lanes of content that every head shares).

**Two kinds of cache in one arena** (``layer_kinds`` of models/decoder.py's
contract; ``L_r`` latent layers, ``L_s`` KDA layers): ``c [L_r, R, S, W]``
rows that grow with the context; ``s [L_s, R, H, d_k, d_v]`` float32 and
``conv [L_s, R, (taps - 1) * 3 H d_k]`` (the last inputs of the convolution,
as the projection leaves them, in the model's dtype; a slot's tail is one
row, since a leaf with three rows a slot made XLA re-lay the whole leaf around
every gather), fixed a slot.  Rows left by
a slot's last stream are masked by ``lens``; a state is not, so a prompt's
first piece (``starts == 0``) starts from a zero state and a zero tail.
**Decode** advances a wave's states in place (ops/kda.py ``kda_wave_update``,
or its oracle where the arena is not the kernels').  **Prefill** is by pieces
(models/experts.py's frame; this backend declares two lanes,
``models/pangu_moe.py`` keeps models/latent_moe.py's one): a KDA layer's part
is models/state_layer.py's around the chunked form (``kda_chunk_scan``; a
padded position has ``g = 0, beta = 0``: it moves nothing), a latent layer's
models/latent_moe.py's.  **A piece carries a wave** (``piece_wave``): the
decoding lanes' rows ride behind the piece's through every product, step
their slots' states by the wave's own kernel behind the piece lanes' chunked
form (models/state_layer.py ``_step_slots``) and read their slots' latent rows
by the wave's own kernel behind the piece lanes' flash calls
(models/latent_moe.py ``_piece_rows_layer``); the lanes' next token comes out
of the piece's program (PERF.md section 6, PR 60).

The projection's output is rounded to the model's dtype before the
convolution, in a wave and in a piece alike: the tail a slot carries is then
what the piece itself convolved, however a prompt is cut.
"""

from __future__ import annotations

import math

from client_tpu.models.decoder import record_width
from client_tpu.models.latent_moe import LatentMoeDecoder
from client_tpu.models.layers import rms_norm
from client_tpu.models.state_layer import StateLayer
from client_tpu.ops.kda import CHUNK

# The tiny preset's layer pattern, in the published config's form.
_TINY_LINEAR = {"kda_layers": [1, 2, 3], "full_attn_layers": [4],
                "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4}


def l2norm(x, eps=1e-6):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + eps))


class KimiLinearBackend(StateLayer, LatentMoeDecoder):
    """The decoder above.  ``linear_attn`` is the published
    ``linear_attn_config`` as it stands (entries past ``n_layers`` name
    layers that lie on further chips); ``dtype="float32"`` makes weights,
    caches and matmuls float32 (the tests' exact comparison)."""

    state_leaves = ("s", "conv")

    # Every piece program carries a wave of the top bucket: where a token
    # gap holds a piece, the decoding lanes' next token comes out of the
    # piece's pass over the weights (models/decoder.py ``piece_wave``; the
    # wave's rows step their slots' states, models/state_layer.py
    # ``_step_slots``, and take the decode step's path through the latent
    # cache, models/latent_moe.py ``_piece_rows_layer``; PERF.md section 6,
    # PR 60).
    piece_wave = True

    def __init__(self, name: str = "kimi_linear", n_layers: int = 4,
                 n_dense: int = 1, d_model: int = 64, n_heads: int = 4,
                 linear_attn: dict | None = None,
                 kv_rank: int = 32, nope_dim: int = 16, rope_dim: int = 8,
                 v_dim: int = 16, d_ff: int = 128, d_expert: int = 32,
                 n_experts: int = 16, experts_held: int = 4,
                 first_expert: int = 0, top_k: int = 4, n_shared: int = 1,
                 routed_scale: float = 2.446, vocab: int = 96,
                 max_seq_len: int = 64, piece: int = 16,
                 rms_eps: float = 1e-5, max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16"):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        linear = dict(linear_attn or _TINY_LINEAR)
        kinds = tuple(
            "state" if i in linear["kda_layers"] else
            "rows" if i in linear["full_attn_layers"] else None
            for i in range(1, n_layers + 1))
        if None in kinds or "state" not in kinds or "rows" not in kinds:
            raise ValueError(
                f"layers 1..{n_layers} are each a KDA or a full layer, "
                f"and both kinds are there: {linear}")
        if not 0 < n_dense < n_layers:
            raise ValueError("leading dense layers and then expert layers")
        self.layer_kinds = kinds
        self.n_layers, self.n_dense = int(n_layers), int(n_dense)
        self.d_model, self.n_heads = int(d_model), int(n_heads)
        self.kda_heads, self.kda_dim = int(linear["num_heads"]), int(
            linear["head_dim"])
        self.taps = int(linear["short_conv_kernel_size"])
        self.state_shape = (self.kda_heads, self.kda_dim, self.kda_dim)
        # The two low-rank pairs (decay, output gate): the head size.
        self.low_rank = self.kda_dim
        self.kv_rank = int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim, self.d_ff = int(v_dim), int(d_ff)
        self.d_expert, self.n_experts = int(d_expert), int(n_experts)
        self.experts_held, self.first_expert = int(experts_held), int(
            first_expert)
        self.top_k, self.n_shared = int(top_k), int(n_shared)
        self.routed_scale, self.rms_eps = float(routed_scale), float(rms_eps)
        self.piece = int(piece)
        # A piece is whole chunks of the chunked form (ops/kda.py ``CHUNK``;
        # a piece shorter than that is one chunk).
        self.chunk = min(CHUNK, self.piece)
        if self.piece % self.chunk:
            raise ValueError(f"a piece ({piece}) is whole chunks "
                             f"({self.chunk})")
        self.dtype = str(dtype)
        self._seed = seed
        # A stream may ask for its record (models/latent_moe.py).
        self.stream_record = record_width(self.n_layers - self.n_dense)
        self._latent_setup()
        # Two prompts a piece program at most (what was measured: PERF.md
        # section 6, PR 48); the scheduler runs the smallest compiled count
        # that holds those standing in line.
        self.prefill_piece = (self.piece, 2)

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves.  A KDA layer: ``wqkv``
        (q | k | v), ``conv [taps, 3 H d_k]``, the decay's ``wfa, wfb, a_log,
        dt_bias`` (``exp(a_log)`` about 1-16 and ``softplus(dt_bias)`` about
        0.01-0.1, float32), ``wb``, the gate's ``wga, wgb``, the heads' norm
        ``onorm`` and ``wo``; a latent layer: ``wqn, wqr`` (W_q by its
        columns), ``wkva, kvln, wkb, wvb, wo``; both: two norms and the
        feed-forward (an expert layer's gate with its selection bias)."""
        d, h = self.d_model, self.n_heads
        hk, dk, r = self.kda_heads, self.kda_dim, self.low_rank
        w, mat, gain = self._weight_makers()

        def layer(kind: str, dense: bool):
            lp = {"ln1": gain(d), "ln2": gain(d)}
            if kind == "state":
                lp.update(
                    wqkv=mat(d, 3 * hk * dk),
                    conv=w(self.taps, 3 * hk * dk,
                           scale=1.0 / math.sqrt(self.taps)),
                    wfa=mat(d, r), wfb=mat(r, hk * dk),
                    a_log=w(hk, scale=0.7, offset=1.4, dtype="float32"),
                    dt_bias=w(hk * dk, scale=0.7, offset=-3.4,
                              dtype="float32"),
                    wb=mat(d, hk), wga=mat(d, r), wgb=mat(r, hk * dk),
                    onorm=gain(dk), wo=mat(hk * dk, d))
            else:
                lp.update(
                    wqn=mat(d, h * self.nope_dim),
                    wqr=mat(d, h * self.rope_dim),
                    wkva=mat(d, self.kv_rank + self.rope_dim),
                    kvln=gain(self.kv_rank),
                    wkb=w(h, self.nope_dim, self.kv_rank,
                          scale=1.0 / math.sqrt(self.kv_rank)),
                    wvb=w(h, self.kv_rank, self.v_dim,
                          scale=1.0 / math.sqrt(self.kv_rank)),
                    wo=mat(h * self.v_dim, d))
            if dense:
                lp["wgu"] = mat(d, 2 * self.d_ff)
                lp["wd"] = mat(self.d_ff, d)
                return lp
            lp.update(self._expert_weights(w, mat))
            lp["router_bias"] = w(self.n_experts, scale=0.02,
                                  dtype="float32")
            return lp

        return {
            "embed": w(self.vocab, d, scale=1.0),
            "layers": [layer(kind, i < self.n_dense)
                       for i, kind in enumerate(self.layer_kinds)],
            "lnf": gain(d),
            "head": mat(d, self.vocab),
        }

    # -- the model's own blocks -------------------------------------------------

    def _queries_and_rows(self, lp, x, pos):
        """x ``[..., n, d]`` float32 -> q_nope ``[..., n, H, nope]``, q_r
        ``[..., n, H, rope]``, c ``[..., n, kv_rank]`` (normed) and k_r
        ``[..., n, rope]``, float32; no position enters."""
        del pos
        h = rms_norm(x, lp["ln1"], self.rms_eps)
        heads = (*x.shape[:-1], self.n_heads, -1)
        kv = self._mm(h, lp["wkva"])
        return (self._mm(h, lp["wqn"]).reshape(heads),
                self._mm(h, lp["wqr"]).reshape(heads),
                rms_norm(kv[..., :self.kv_rank], lp["kvln"], self.rms_eps),
                kv[..., self.kv_rank:])

    def _after_rows(self, lp, h, o, live, tile_m):
        """The block behind its mixer, for rows h ``[n, d]`` and the heads'
        outputs o ``[n, H * d_v]`` of either kind -> (h, routing counts,
        choices)."""
        import jax.numpy as jnp

        h = h + self._mm(o, lp["wo"])
        y, counts, route = self._ffn(lp, rms_norm(h, lp["ln2"], self.rms_eps),
                                     live, tile_m)
        return h + y, jnp.asarray(counts, jnp.int32), route

    def _kda_inputs(self, lp, h, ext):
        """Normed rows h ``[..., n, d]`` and the convolution's inputs ext
        ``[..., n + taps - 1, 3 H d_k]`` (the tail, then these rows'
        projections, in the cache's dtype) -> q, k, v, g ``[..., n, H,
        d_k]``, beta ``[..., n, H]`` and the output gate ``[..., n, H *
        d_k]``, float32."""
        import jax
        import jax.numpy as jnp

        n, hk = h.shape[-2], self.kda_heads
        ext = ext.astype(jnp.float32)
        taps = lp["conv"].astype(jnp.float32)
        mixed = jax.nn.silu(sum(taps[j] * ext[..., j:j + n, :]
                                for j in range(self.taps)))
        q, k, v = (part.reshape(*h.shape[:-1], hk, -1)
                   for part in jnp.split(mixed, 3, axis=-1))
        dt = jax.nn.softplus(self._mm(self._mm(h, lp["wfa"]), lp["wfb"])
                             + lp["dt_bias"])
        g = -jnp.exp(lp["a_log"])[:, None] * dt.reshape(q.shape)
        beta = jax.nn.sigmoid(self._mm(h, lp["wb"]))
        gate = jax.nn.sigmoid(self._mm(self._mm(h, lp["wga"]), lp["wgb"]))
        return (l2norm(q) / math.sqrt(self.kda_dim), l2norm(k), v, g, beta,
                gate)

    # -- the state layer's parts (models/state_layer.py) --------------------------

    def _state_ops(self):
        from client_tpu.ops.kda import (kda_chunk_scan, kda_recurrence,
                                        kda_wave_update, reference_kda_update)

        return (kda_wave_update, reference_kda_update, kda_chunk_scan,
                kda_recurrence)

    def _state_project(self, lp, x, dtype):
        """``wqkv``'s columns in the cache's dtype, and the normed rows, which
        the gates read (``_kda_inputs``: its five small matrices are read a
        lane)."""
        h = rms_norm(x, lp["ln1"], self.rms_eps)
        return self._mm(h, lp["wqkv"]).astype(dtype), h, None

    def _state_inputs(self, lp, h, ext):
        return self._kda_inputs(lp, h, ext)

    def _through_state(self, lp, ins, aside, run, pad):
        """A padded position has ``g = 0, beta = 0``: it moves nothing.  The
        heads' read-outs ``[..., H, d_v]`` are normed a head and gated ->
        ``[..., H * d_v]``."""
        q, k, v, g, beta, gate = ins
        o = run(q, k, v, pad(g), pad(beta))
        return rms_norm(o, lp["onorm"], self.rms_eps).reshape(
            gate.shape) * gate

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``c [L_r, R, max_seq_len, W]`` and ``conv [L_s, R, (taps - 1) * 3
        H d_k]`` in the model's dtype, ``s [L_s, R, H, d_k, d_v]`` float32 (``R
        = capacity + 1``: the last slot absorbs padded lanes) and ``tok
        [R]``, each slot's latest token on the device."""
        import jax.numpy as jnp

        r, dt = capacity + 1, jnp.dtype(self.dtype)
        n_state = self.layer_kinds.count("state")
        hk, dk = self.kda_heads, self.kda_dim
        return {
            "c": jnp.zeros((self.n_layers - n_state, r, self.max_seq_len,
                            self.row_width), dt),
            "s": jnp.zeros((n_state, r, hk, dk, dk), jnp.float32),
            "conv": jnp.zeros((n_state, r, (self.taps - 1) * 3 * hk * dk),
                              dt),
            "tok": jnp.zeros(r, jnp.int32)}
