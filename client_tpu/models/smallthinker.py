"""Window and global layers in one decoder, grouped-query heads, sparse ReGLU
experts routed before attention (`smallthinker`), served through the
generative path.

The architecture is the public ``SmallThinker-21BA3B-Instruct`` config's:
layers in periods of four (``sliding_window_layout`` = ``rope_layout`` = ``[0,
1, 1, 1]``), the first **global** (every earlier position, **no positions**),
the other three **window** layers (the last ``window`` positions, rotary
positions); ``n_heads`` query heads over ``n_kv_heads`` key/value heads (query
head i reads key/value head ``i // (n_heads / n_kv_heads)``); every layer's
feed-forward a router over ``n_experts`` small ReLU-gated experts, no shared
expert, no dense layer; RMSNorm, no biases, an untied head; a float32 residual
stream and float32 logits over bfloat16 matmuls.  With x ``[n, d]``:

- *Block*: ``h = N1(x)``; **the router first**: ``r = h W_r`` (float32), ``E``
  the ``top_k`` largest, ``w = softmax(r[E])``; ``q, k, v = h W_q, h W_k, h
  W_v``; a window layer rotates q and k (RoPE over the whole head, the
  rotate-half pairing); scores ``q . k / sqrt(D)``, key j for query t iff ``j
  <= t`` and, in a window layer, ``t - window < j``; ``x += (softmax(scores) v)
  W_o``; ``h2 = N2(x)``; ``x += sum_{e in E} w_e W_d^e (relu(h2 W_g^e) * (h2
  W_u^e))``.  A final RMSNorm and ``x W_head``.  (That the router reads
  ``N1(x)``, what the attention's projections read, is how this file reads
  "router placed before attention".)

**The cache is one arena with two row shapes** (``models/decoder.py``'s layer
kinds): the global layers' leaves ``kg, vg [layers of the kind, R,
max_seq_len, Hkv*D]`` hold a row a position; the window layers' ``kw, vw
[layers of the kind, R, window, Hkv*D]`` are **rings**, position n at row ``n
mod window`` (``ring_rows``: a window that is no multiple of the piece is
rounded up to one, and the rows that hold older positions are masked).  A
decode step writes that row and reads the ``min(n, window)`` live rows but the
one it overwrites (``ops/decode_kernel.py`` ``window_wave_attention``; the
global layers' is ``decode_wave_attention``, both with grouped-query rows).
**Keys are rotated before they are written**, so a ring's order means nothing
to the softmax.  A slot is ``(3 window + max_seq_len) / (4 max_seq_len)`` of
what it would be with every layer global.

**Prefill goes by pieces** of ``piece`` positions (``prefill_piece``), the
next piece of one prompt or of two a call (the two oldest that wait: a
layer's 64 experts are then read once for both; the projections and the
expert layer see both lanes' positions as one batch, the attention walks the
lanes one after the other, each from its own slot).  Piece i of a prompt has
``i * piece`` rows before it in a
global layer and ``min(i * piece, window)`` in a window layer, so a layer
holds one branch a count and lane (``lax.switch`` on the lane's own
``start``: nothing masked is computed but
inside the band's two edge blocks): the piece's queries attend to the rows
before them and, causally, to their own, with the flash kernel's band and
grouped-query heads (``ops/flash_attention.py``; the attention, the walk over
the lanes and a global layer's part of a piece are
``models/grouped_query.py``'s, shared with ``models/nemotron_h.py`` and
``models/ouro.py``; **the ring's parts** (the arena of two row shapes, the
ring's size, a piece's read and write of a ring, what the scheduler counts of
both kinds) **are that module's ``RingPieces``**, shared with
``models/cohere_moe.py``: this file supplies ``_project(lp, x, pos, kind)``
and which layers slide and rotate).  A ring that is full is read
whole, oldest position first, **before** the piece's rows overwrite its oldest
block (a ring holds whole pieces: a piece never wraps); a
prompt's last piece writes its valid rows only, the rows behind them being
positions a later step still reads.

The expert layer (router, grouped matmuls, the lazily made weights, a wave's
counters, the words of a stream's record) is ``models/experts.py``'s, shared
with ``models/latent_moe.py``; this backend holds all ``n_experts`` by default
(``experts_held``, ``first_expert``: a share of them as one chip of an
expert-parallel group would, what the absent ones add left out).  Layers are a
Python loop over per-layer weights: a matrix is an operand as it lies.

**A stream's record** (``stream_record``; ``record=True``): for every position
the programs consumed, ``held_words`` int32 an expert layer (bit e of word w:
held expert ``first_expert + 32 w + e`` was chosen) and the float32 bits of
``1 + RECORD_LOGITS`` logits of the row its token was chosen from.
"""

from __future__ import annotations

import math

from client_tpu.models.decoder import record_width
from client_tpu.models.experts import ExpertDecoder
from client_tpu.models.grouped_query import RingPieces
from client_tpu.models.layers import rms_norm, rope


class SmallThinkerBackend(RingPieces, ExpertDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``dtype="float32"`` makes weights, cache and matmuls float32
    (the tests' exact comparison); the served form is bfloat16."""

    router_score = "softmax"
    expert_act = "relu"
    # Every piece program carries a wave of the top bucket: where a token
    # gap holds a piece, the decoding lanes' next token comes out of the
    # piece's pass over the weights (models/decoder.py ``piece_wave``;
    # PERF.md section 6, PR 56).
    piece_wave = True

    def __init__(self, name: str = "smallthinker", n_layers: int = 4,
                 d_model: int = 64, n_heads: int = 4, n_kv_heads: int = 2,
                 head_dim: int = 16, d_expert: int = 32, n_experts: int = 8,
                 experts_held: int | None = None, first_expert: int = 0,
                 top_k: int = 2, window: int = 16,
                 window_layout=(0, 1, 1, 1), rope_layout=None,
                 vocab: int = 96, max_seq_len: int = 64, piece: int = 8,
                 rope_theta: float = 1500000.0, rms_eps: float = 1e-6,
                 max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
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
        self.top_k, self.window, self.piece = int(top_k), int(window), int(
            piece)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.dtype = str(dtype)
        self._seed = seed
        # Which layers slide and which rotate, a layer each (the config's
        # two lists, a period or the whole depth).
        def per_layer(layout):
            return [bool(layout[i % len(layout)])
                    for i in range(self.n_layers)]

        slides = per_layer(window_layout)
        self._ring_setup(slides, slides if rope_layout is None
                         else per_layer(rope_layout))
        self._check_experts()
        # Two prompts a piece program at most (what was measured: PERF.md
        # section 6, PR 52).
        self.prefill_piece = (self.piece, 2)
        self.stream_record = record_width(
            self.n_layers * self.held_words) if record else 0

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves (made, and rounded to
        bfloat16, when asked for).  Layers are a list; a layer holds its two
        norms, the four projections, the router (float32) and the held
        experts' stacked ``egu [E, d, 2f]`` (gate | up) and ``ed [E, f,
        d]``."""
        d, hd = self.d_model, self.head_dim
        f, e = self.d_expert, self.experts_held
        w, mat, gain = self._weight_makers()

        def layer():
            return {
                "ln1": gain(d), "ln2": gain(d),
                "wq": mat(d, self.n_heads * hd),
                "wk": mat(d, self.n_kv_heads * hd),
                "wv": mat(d, self.n_kv_heads * hd),
                "wo": mat(self.n_heads * hd, d),
                "router": w(d, self.n_experts, scale=1.0 / math.sqrt(d),
                            dtype="float32"),
                "egu": w(e, d, 2 * f, scale=1.0 / math.sqrt(d),
                         first=self.first_expert),
                "ed": w(e, f, d, scale=1.0 / math.sqrt(f),
                        first=self.first_expert)}

        return {"embed": w(self.vocab, d, scale=1.0),
                "layers": [layer() for _ in range(self.n_layers)],
                "lnf": gain(d), "head": mat(d, self.vocab)}

    # -- the model's own blocks -------------------------------------------------

    def _project(self, lp, x, pos, kind: str = "rows"):
        """x ``[n, d]`` float32 -> q ``[n, H, D]``, k, v ``[n, Hkv, D]``
        float32, q and k rotated to ``pos`` where the layers of the ``kind``
        rotate."""
        q, k, v = self._heads(lp, rms_norm(x, lp["ln1"], self.rms_eps))
        if self.rotate[kind]:
            q, k = rope(q, pos, self.rope_theta), rope(k, pos,
                                                       self.rope_theta)
        return q, k, v

    def _router_input(self, lp, x):
        """What the router reads of a layer's input x: what the attention's
        projections read (the one thing the published config leaves to a
        reader; a method of its own so that the benchmark's control can
        serve the other reading)."""
        return rms_norm(x, lp["ln1"], self.rms_eps)

    def _after_rows(self, lp, x, o, live, tile_m):
        """The block behind its attention, for rows x ``[n, d]`` (the
        layer's input) and their heads' outputs o ``[n, H * D]`` -> (x,
        routing counts, (choices ``[n, k]``,)).  The router reads what the
        attention read."""
        routing = self.route(lp, self._router_input(lp, x))
        x = x + self._mm(o, lp["wo"])
        y, counts, top_i = self._experts(
            lp, rms_norm(x, lp["ln2"], self.rms_eps), live, tile_m,
            routing=routing)
        return x + y, counts, (top_i,)
