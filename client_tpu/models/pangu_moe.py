"""Sparse-expert decoder with a latent cache (`pangu_moe`): one chip's share
of an expert-parallel deployment, served through the generative path.

The architecture is the public ``openPangu-Ultra-MoE`` config's
(``model_type`` ``pangu_ultra_moe``): multi-head latent attention, a few
leading dense layers and then layers of many routed experts and a shared one,
four RMSNorms a layer (``sandwich_norm``), rotary positions on a slice of each
head, SwiGLU, no biases; a float32 residual stream and float32 logits over
bfloat16 matmuls (models/evabyte.py's arithmetic).  With x ``[n, d]``:

- *Block*: ``x += N2(MLA(N1(x)))``; ``x += N4(FFN(N3(x)))``; a final RMSNorm;
  logits ``x W_head``.  ``FFN`` is a SwiGLU in a leading dense layer, the
  expert layer after.
- *MLA*: ``c_q = RMSNorm(x W_qa)``; per head ``[q_nope | q_rope] = c_q
  W_qb``; ``[c_kv | k_r] = x W_kva``; ``c = RMSNorm(c_kv)``; rotary on
  ``q_rope`` and on ``k_r``, which every head shares; per head ``k_nope = c
  W_kb``, ``v = c W_vb``; one causal softmax over ``(q_nope . k_nope + q_rope
  . k_r) / sqrt(nope + rope)``; ``o = concat_h(p v) W_o``.
- *Expert layer*: ``s = sigmoid(x W_g)`` over **all** ``n_experts``, in
  float32; the ``top_k`` largest; weights ``s_i / sum s_i * routed_scale``;
  ``y = shared(x) + sum_i w_i E_i(x)``.

**The share.**  This backend holds ``experts_held`` of the routed experts,
``first_expert ..``, as one chip of an expert-parallel group does.  The router
keeps its width and its ``top_k``; the layer computes ``shared(x)`` and the
terms of the chosen experts it holds; what the absent experts would add is left
out, and that partial result goes on to the next layer.  Nothing stands in for
the other chips or their exchange.  The (token, expert) pairs held here are
sorted by expert and multiplied in groups (ops/grouped_matmul.py): no pair is
dropped, and an expert no token chose is not read.

**The cache** is one leaf ``c`` of ``[L, R, S, W]``: a position's row is ``[c
| k_r | 0]`` (``latent_row_width``: 512 + 64 -> 640 lanes), what every head
reads.  **Decode absorbs** the up-projection: ``q_lat = q_nope W_kb^T`` per
head, scores ``[q_lat | q_rope] . row``, ``o_lat = p c``, ``o_h = o_lat
W_vb`` (``latent_attention`` of models/decoder.py's contract; the kernel is
ops/decode_kernel.py ``latent_wave_attention``, which takes the query and
leaves ``o_lat`` with the heads along the minor axis: the two einsums write
and read that layout).  **Prefill does not**: a prompt is consumed a piece of
``piece`` positions at a time (``prefill_piece``, one lane a call), each by
the flash kernel against the rows before it and its own: models/latent_moe.py
``_piece_rows_layer`` inside models/decoder.py's piece frame.  **A piece
carries a wave** (``piece_wave``): where a token gap holds a piece, the
decoding lanes' rows ride behind the piece's 512 through every product (the
16 held experts' matrices, the projections and the dense layer are read once
for both), through a latent layer by the wave's own kernel on their own
slots, and out through the one head; the lanes' next token comes out of the
piece's program (PERF.md section 6, PR 60).

What this decoder shares with ``models/kimi_linear.py`` (the cache's rows and
the absorbed products, the piece's attention, routing and the grouped expert
matmuls, the lazily made weights) is one copy, ``models/latent_moe.py``; this
file holds the query path, the rotary positions, the four norms and the
weights.  Layers are a Python loop over per-layer weights (five at the served depth):
a layer's matrices are operands as they are, with no slice of a stacked array
in front of a kernel.

What a wave routed is known on the device only: ``wave_stats`` names the three
counters the decode program returns behind its tokens (pairs held here, the
busiest held expert's, held experts touched, each summed over the expert
layers; padded lanes route nowhere).
"""

from __future__ import annotations

import math

from client_tpu.models.latent_moe import LatentMoeDecoder
from client_tpu.models.layers import rms_norm, rope


class PanguMoeBackend(LatentMoeDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``dtype="float32"`` makes weights, cache and matmuls float32
    (the tests' exact-routing comparison); the served form is bfloat16."""

    # Every piece program carries a wave of the top bucket: where a token
    # gap holds a piece, the decoding lanes' next token comes out of the
    # piece's pass over the weights (models/decoder.py ``piece_wave``; the
    # wave's rows take the decode step's path through the latent cache,
    # models/latent_moe.py ``_piece_rows_layer``; PERF.md section 6, PR 60).
    piece_wave = True

    def __init__(self, name: str = "pangu_moe", n_layers: int = 3,
                 n_dense: int = 1, d_model: int = 64, n_heads: int = 4,
                 q_rank: int = 48, kv_rank: int = 32, nope_dim: int = 16,
                 rope_dim: int = 8, v_dim: int = 16, d_ff: int = 128,
                 d_expert: int = 32, n_experts: int = 16,
                 experts_held: int = 4, first_expert: int = 0,
                 top_k: int = 4, n_shared: int = 1,
                 routed_scale: float = 2.5, vocab: int = 96,
                 max_seq_len: int = 64, piece: int = 16,
                 rope_theta: float = 25600000.0, rms_eps: float = 1e-5,
                 max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16"):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        if not 0 < n_dense < n_layers:
            raise ValueError("leading dense layers and then expert layers")
        self.n_layers, self.n_dense = int(n_layers), int(n_dense)
        self.d_model, self.n_heads = int(d_model), int(n_heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim, self.d_ff = int(v_dim), int(d_ff)
        self.d_expert, self.n_experts = int(d_expert), int(n_experts)
        self.experts_held, self.first_expert = int(experts_held), int(
            first_expert)
        self.top_k, self.n_shared = int(top_k), int(n_shared)
        self.routed_scale = float(routed_scale)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.piece = int(piece)
        self.dtype = str(dtype)
        self._seed = seed
        self._latent_setup()

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves (made, and rounded to
        bfloat16, when asked for).  Layers are a list: a leading dense layer
        has ``wgu``/``wd``, an expert layer the router (float32), the shared
        expert and the held experts' stacked ``egu [E, d, 2f]`` (gate | up)
        and ``ed [E, f, d]``."""
        d, h = self.d_model, self.n_heads
        w, mat, gain = self._weight_makers()

        def layer(dense: bool):
            lp = {
                "ln1": gain(d), "ln2": gain(d), "ln3": gain(d),
                "ln4": gain(d),
                "wqa": mat(d, self.q_rank), "qln": gain(self.q_rank),
                # W_qb by its columns: every head's nope part, then every
                # head's rotary part (a matrix each, so no program splits
                # 192-wide heads out of one); W_kvb by head, as the absorbed
                # products contract it: k_nope_h = c wkb[h]^T, v_h = c wvb[h].
                "wqn": mat(self.q_rank, h * self.nope_dim),
                "wqr": mat(self.q_rank, h * self.rope_dim),
                "wkva": mat(d, self.kv_rank + self.rope_dim),
                "kvln": gain(self.kv_rank),
                "wkb": w(h, self.nope_dim, self.kv_rank,
                         scale=1.0 / math.sqrt(self.kv_rank)),
                "wvb": w(h, self.kv_rank, self.v_dim,
                         scale=1.0 / math.sqrt(self.kv_rank)),
                "wo": mat(h * self.v_dim, d),
            }
            if dense:
                lp["wgu"] = mat(d, 2 * self.d_ff)
                lp["wd"] = mat(self.d_ff, d)
                return lp
            return {**lp, **self._expert_weights(w, mat)}

        return {
            "embed": w(self.vocab, d, scale=1.0),
            "layers": [layer(i < self.n_dense)
                       for i in range(self.n_layers)],
            "lnf": gain(d),
            "head": mat(d, self.vocab),
        }

    # -- the model's own blocks -------------------------------------------------

    def _queries_and_rows(self, lp, x, pos):
        """x ``[..., n, d]`` float32, pos ``[..., n]`` -> q_nope ``[..., n,
        H, nope]``, q_rope ``[..., n, H, rope]`` (rotated), c ``[..., n,
        kv_rank]`` (normed) and k_r ``[..., n, rope]`` (rotated), float32."""
        h = rms_norm(x, lp["ln1"], self.rms_eps)
        c_q = rms_norm(self._mm(h, lp["wqa"]), lp["qln"], self.rms_eps)
        heads = (*x.shape[:-1], self.n_heads, -1)
        q_nope = self._mm(c_q, lp["wqn"]).reshape(heads)
        q_rope = rope(self._mm(c_q, lp["wqr"]).reshape(heads), pos,
                      self.rope_theta)
        kv = self._mm(h, lp["wkva"])
        c = rms_norm(kv[..., :self.kv_rank], lp["kvln"], self.rms_eps)
        k_r = rope(kv[..., None, self.kv_rank:], pos, self.rope_theta)
        return q_nope, q_rope, c, k_r[..., 0, :]

    def _after_rows(self, lp, h, o, live, tile_m):
        """The block behind its attention, for rows h ``[n, d]`` and their
        heads' outputs o ``[n, H * v_dim]`` -> (h, routing counts, choices):
        of a dense layer 0 and ``()``, of an expert layer its counts and
        ``(choices [n, k],)``."""
        import jax.numpy as jnp

        h = h + rms_norm(self._mm(o, lp["wo"]), lp["ln2"], self.rms_eps)
        y, counts, route = self._ffn(lp, rms_norm(h, lp["ln3"], self.rms_eps),
                                     live, tile_m)
        return (h + rms_norm(y, lp["ln4"], self.rms_eps),
                jnp.asarray(counts, jnp.int32), route)

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``c [L, capacity + 1, max_seq_len, W]`` in the model's dtype (the
        +1 slot absorbs padded lanes) and ``tok [R]``, each slot's latest
        token on the device."""
        import jax.numpy as jnp

        shape = (self.n_layers, capacity + 1, self.max_seq_len,
                 self.row_width)
        return {"c": jnp.zeros(shape, jnp.dtype(self.dtype)),
                "tok": jnp.zeros(capacity + 1, jnp.int32)}
