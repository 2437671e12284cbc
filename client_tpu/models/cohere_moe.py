"""A parallel block over window and global layers: one LayerNorm whose output
the attention, the router, the routed experts and the averaged shared experts
all read, one add (``cohere_moe``), served through the generative path.

The architecture is the public ``command-a-plus-05-2026`` config's
(``model_type: cohere2_moe``): layers in periods of four (``layer_types``:
three ``sliding_attention`` layers, then a ``full_attention`` one), the
**window** layers over the last ``window`` positions with rotary positions in
the **interleaved** pairing (``rope_gptj``: lanes ``2i`` and ``2i + 1`` a
pair, the whole head), the **full** layer over every earlier position with
**no positions**; ``n_heads`` query heads over ``n_kv_heads`` key/value heads
(query head i reads key/value head ``i // (n_heads / n_kv_heads)``); a
mean-subtracting LayerNorm without bias; a **tied** head (the embedding's
rows, ``logit_scale``); a float32 residual stream and float32 logits over
bfloat16 matmuls.  With x ``[n, d]``:

- *Block* (``use_parallel_block``): ``h = LN(x)``; ``q, k, v = h W_q, h W_k,
  h W_v`` (a window layer rotates q and k); scores ``q . k / sqrt(D)``, key j
  for query t iff ``j <= t`` and, in a window layer, ``t - window < j``;
  ``attn = (softmax(scores) v) W_o``; ``s = sigmoid(h W_r)`` (float32), ``E``
  the ``top_k`` largest, ``w = s[E] / sum s[E]``; ``routed = sum_{e in E} w_e
  W_d^e (silu(h W_g^e) * (h W_u^e))``; ``shared = 1/n_shared sum_i S_d^i
  (silu(h S_g^i) * (h S_u^i))`` (``shared_expert_combination_strategy:
  average``); **``x = x + attn + routed + shared``**: the feed-forward reads
  what the attention reads, and the block adds once.  ``logits = LN(x) E^T *
  logit_scale``.
- *The shared experts are one pair of matrices*: ``sgu [d, 2 * n_shared * f]``
  holds every shared expert's gate columns, then every one's up columns, and
  ``sd [n_shared * f, d]`` their down rows in the same order, so that
  ``_between`` gates each expert's own half and one product sums the four;
  times ``1 / n_shared``: the same numbers as four experts averaged.

The rings beside whole-context rows, a piece's walk over its lanes and what
the scheduler counts of the two row kinds are ``models/grouped_query.py``'s
(:class:`RingPieces`, shared with ``models/smallthinker.py``); the expert
layer (router, grouped matmuls, the lazily made weights, a wave's counters, a
stream's record) is ``models/experts.py``'s.  This backend holds
``experts_held`` of the ``n_experts`` the router scores (``first_expert ..``:
one chip's share of an expert-parallel group; what the absent ones would add
is left out).  **Keys are rotated before they are written.**
"""

from __future__ import annotations

import math

from client_tpu.models.decoder import record_width
from client_tpu.models.experts import ExpertDecoder
from client_tpu.models.grouped_query import RingPieces
from client_tpu.models.layers import layer_norm, rope


class CohereMoeBackend(RingPieces, ExpertDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``dtype="float32"`` makes weights, cache and matmuls float32
    (the tests' exact comparison); the served form is bfloat16."""

    router_score = "sigmoid"
    expert_form = "gated"
    expert_act = "silu"
    routed_scale = 1.0
    # Every piece program carries a wave of the top bucket: where a token
    # gap holds a piece, the decoding lanes' next token comes out of the
    # piece's pass over the weights (models/decoder.py ``piece_wave``;
    # PERF.md section 6, PR 56).
    piece_wave = True

    def __init__(self, name: str = "cohere_moe", n_layers: int = 4,
                 d_model: int = 64, n_heads: int = 8, n_kv_heads: int = 2,
                 head_dim: int = 16, d_expert: int = 32, n_experts: int = 8,
                 experts_held: int | None = None, first_expert: int = 0,
                 top_k: int = 2, n_shared: int = 4, window: int = 16,
                 layer_types=("sliding_attention", "sliding_attention",
                              "sliding_attention", "full_attention"),
                 vocab: int = 96, max_seq_len: int = 64, piece: int = 8,
                 rope_theta: float = 50000.0, norm_eps: float = 1e-5,
                 logit_scale: float = 1.0, max_streams: int = 4,
                 seed: int = 0, attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16",
                 record: bool = False):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        self.n_layers, self.d_model = int(n_layers), int(d_model)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim, self.d_expert = int(head_dim), int(d_expert)
        self.n_experts, self.first_expert = int(n_experts), int(first_expert)
        self.experts_held = int(n_experts if experts_held is None
                                else experts_held)
        self.top_k, self.n_shared = int(top_k), int(n_shared)
        self.window, self.piece = int(window), int(piece)
        self.rope_theta, self.norm_eps = float(rope_theta), float(norm_eps)
        self.logit_scale = float(logit_scale)
        self.dtype = str(dtype)
        self._seed = seed
        # A layer each (the config's list, a period or the whole depth): the
        # window layers slide and rotate, the full layers do neither.
        slides = [layer_types[i % len(layer_types)] == "sliding_attention"
                  for i in range(self.n_layers)]
        self._ring_setup(slides, slides)
        self._check_experts()
        # One prompt a piece program (what was measured: PERF.md section 6,
        # PR 53).
        self.prefill_piece = (self.piece, 1)
        self.stream_record = record_width(
            self.n_layers * self.held_words) if record else 0

    # -- what the scheduler counts (models/decoder.py) ---------------------------

    def piece_pairs_by_kind(self, start: int, valid: int) -> tuple[int, int]:
        """(window pairs, global pairs): the (query, key) pairs a lane's piece
        of ``valid`` positions from ``start`` scores in its window layers and
        in its full layers, each summed over the layers of the kind (a query
        at position t: ``min(t + 1, window)`` keys and ``t + 1``)."""
        rings = self.layer_kinds.count("ring")
        end = start + valid

        def ramp(a, b):               # sum of t + 1 over a <= t < b
            return (b * (b + 1) - a * (a + 1)) // 2

        # The first position that sees a whole window of keys.
        edge = min(max(self.window - 1, start), end)
        return (rings * (ramp(start, edge) + (end - edge) * self.window),
                (self.n_layers - rings) * ramp(start, end))

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves (made, and rounded to
        bfloat16, when asked for).  Layers are a list; a layer holds its one
        norm, the four projections, the router (float32), the held experts'
        stacked ``egu [E, d, 2f]`` (gate | up) and ``ed [E, f, d]``, and the
        shared experts as one pair (module docstring).  The head is the
        embedding: one leaf."""
        d, hd = self.d_model, self.head_dim
        f, e, ns = self.d_expert, self.experts_held, self.n_shared
        w, mat, gain = self._weight_makers()

        def layer():
            return {
                "ln": gain(d),
                "wq": mat(d, self.n_heads * hd),
                "wk": mat(d, self.n_kv_heads * hd),
                "wv": mat(d, self.n_kv_heads * hd),
                "wo": mat(self.n_heads * hd, d),
                "router": w(d, self.n_experts, scale=1.0 / math.sqrt(d),
                            dtype="float32"),
                "egu": w(e, d, 2 * f, scale=1.0 / math.sqrt(d),
                         first=self.first_expert),
                "ed": w(e, f, d, scale=1.0 / math.sqrt(f),
                        first=self.first_expert),
                "sgu": mat(d, 2 * ns * f),
                "sd": w(ns * f, d, scale=1.0 / math.sqrt(f))}

        return {"embed": w(self.vocab, d, scale=1.0),
                "layers": [layer() for _ in range(self.n_layers)],
                "lnf": gain(d)}

    # -- the model's own blocks -------------------------------------------------

    def _norm(self, x, g):
        return layer_norm(x, g, self.norm_eps)

    def _rotate(self, t, pos):
        """q or k ``[n, heads, D]`` at ``pos``: the interleaved pairing."""
        return rope(t, pos, self.rope_theta, interleaved=True)

    def _project(self, lp, x, pos, kind: str = "rows"):
        """x ``[n, d]`` float32 -> q ``[n, H, D]``, k, v ``[n, Hkv, D]``
        float32, q and k rotated to ``pos`` where the layers of the ``kind``
        rotate."""
        q, k, v = self._heads(lp, self._norm(x, lp["ln"]))
        if self.rotate[kind]:
            q, k = self._rotate(q, pos), self._rotate(k, pos)
        return q, k, v

    def _shared(self, lp, h):
        """The shared experts, averaged: one product pair over all of them
        (module docstring)."""
        return self._dense_expert(h, lp["sgu"], lp["sd"]) * (
            1.0 / self.n_shared)

    def _after_rows(self, lp, x, o, live, tile_m):
        """The parallel block behind its attention, for rows x ``[n, d]``
        (the layer's input) and their heads' outputs o ``[n, H * D]`` -> (x,
        routing counts, (choices ``[n, k]``,)): the router and both kinds of
        expert read the norm the attention's projections read, and the
        three branches are added at once."""
        h = self._norm(x, lp["ln"])
        y, counts, top_i = self._experts(lp, h, live, tile_m)
        return (x + self._mm(o, lp["wo"]) + y + self._shared(lp, h), counts,
                (top_i,))

    def _logits(self, p, x):
        """The tied head: the final norm's rows against the embedding's rows
        (one leaf on the device: the product contracts the embedding's minor
        axis where it lies)."""
        h = x["h"] if isinstance(x, dict) else x
        return self._mm(self._norm(h, p["lnf"]),
                        p["embed"].T) * self.logit_scale
