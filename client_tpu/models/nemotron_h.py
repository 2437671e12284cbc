"""State-space, attention and expert layers that are each a block of their own
(`nemotron_h`), served through the generative path.

The architecture is the public ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` config's
(``model_type`` ``nemotron_h``): a pattern of layers, a letter each
(``hybrid_override_pattern``): **M** a Mamba-2 (SSD) mixer, **\\*** attention
with grouped-query heads and **no positions**, **E** a layer of routed experts
beside a shared one.  Every layer is ``x += Block(N(x))`` with one RMSNorm and
one block: there is no "attention, then feed-forward" pair.  No bias but the
convolution's; a final RMSNorm and an untied head; a float32 residual stream
and float32 logits over bfloat16 matmuls.  With x ``[n, d]`` and ``h = N(x)``:

- *M*: ``[z | xBC | dt] = h W_in`` (``d_inner | d_inner + 2 G N | H``
  columns, ``d_inner = H P``); ``xBC = silu(conv(xBC) + b_conv)``, a causal
  depthwise convolution of ``taps`` positions, zeros before position 0; ``xBC``
  splits into ``x [n, H, P]`` and ``B, C [n, G, N]`` (head h reads group ``h //
  (H / G)``); ``dt = softplus(dt + dt_bias)`` a head; ``A = -exp(A_log)``; the
  state ``S [P, N]`` a head, zero at position 0, advanced as ops/ssd.py says;
  ``y = S C + D x``; ``y = RMSNorm_groups(y * silu(z)) * w`` (the gate before
  the norm, the norm over each group's ``d_inner / G`` channels); out ``y
  W_out``.
- *\\**: ``q = h W_q`` (``n_heads`` of ``head_dim``), ``k, v = h W_k, h W_v``
  (``n_kv_heads``), a causal softmax of ``q k^T / sqrt(head_dim)``, query head
  i on key head ``i // (n_heads / n_kv_heads)``, **nothing rotated**; out ``o
  W_o``.
- *E*: models/experts.py's layer: ``s = sigmoid(h W_r)`` in float32, the
  ``top_k`` largest of ``s + b``, weights ``s_i / sum s_i * routed_scale``;
  **un-gated** experts ``E_i(h) = W_d relu(h W_u)^2``; a shared expert of the
  same form and another width beside them.

**Three kinds of layer in one arena** (``layer_kinds`` of models/decoder.py's
contract): an M layer is a ``"state"`` layer (``s [L_s, R, H / pack, N, pack *
P]`` float32, ops/ssd.py's packed leaf, and ``conv [L_s, R, (taps - 1) *
(d_inner + 2 G N)]``, the convolution's last inputs as the projection leaves
them, in the model's dtype, one row a slot), a \\* layer a ``"rows"`` layer
(``k, v [L_r, R, max_seq_len, Hkv * D]``), an E layer a ``"none"`` layer: no
leaf, no mixer.  **Decode** advances a wave's states in place
(``ssd_wave_update``, or its oracle where the arena is not the kernels') and
reads the lanes' rows with the grouped-query decode kernel.  **Prefill** is by
pieces (models/decoder.py's frame; this backend declares two lanes): an M
layer's part is models/state_layer.py's around models/mamba2.py's mixer and
the chunked form
(``ssd_chunk_scan``; a padded position has ``dt = 0``: it moves nothing), a
\\* layer's models/grouped_query.py's, an E layer the expert block over all
lanes' positions at once.  **Every piece program carries a wave**
(``piece_wave``, models/decoder.py): the decoding lanes' rows stand behind
the piece's, an M layer steps their states with the wave's own kernel behind
the piece lanes' chunked form (models/state_layer.py ``_step_slots``), a \\*
layer reads their rows with the decode kernel, and an E layer's held experts
are read once for the rows of both, so where a token gap holds a piece the
lanes' next token comes out of the piece's pass over the weights.

The projection's ``xBC`` is rounded to the model's dtype before the
convolution, in a wave and in a piece alike: the tail a slot carries is then
what the piece itself convolved, however a prompt is cut.
"""

from __future__ import annotations

import math

from client_tpu.models.decoder import record_width
from client_tpu.models.experts import TILE_M_WAVE, ExpertDecoder
from client_tpu.models.grouped_query import GroupedQueryPieces
from client_tpu.models.layers import rms_norm
from client_tpu.models.mamba2 import Mamba2Layer
from client_tpu.ops.ssd import CHUNK

_KINDS = {"M": "state", "*": "rows", "E": "none"}


class NemotronHBackend(Mamba2Layer, GroupedQueryPieces, ExpertDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``pattern`` is the published ``hybrid_override_pattern`` as it
    stands, of which the first ``n_layers`` letters are served (the rest name
    layers on further chips); ``dtype="float32"`` makes weights, caches and
    matmuls float32 (the tests' exact comparison)."""

    expert_form = "plain"
    expert_act = "relu2"
    # Every piece program carries a wave of the top bucket: where a token
    # gap holds a piece, the decoding lanes' next token comes out of the
    # piece's pass over the weights (models/decoder.py ``piece_wave``;
    # the wave's rows advance their slots' states by the wave's own step,
    # models/state_layer.py; PERF.md section 6, PR 58).
    piece_wave = True

    def __init__(self, name: str = "nemotron_h", pattern: str = "MEM*EME",
                 n_layers: int | None = None, d_model: int = 64,
                 n_heads: int = 4, n_kv_heads: int = 2, head_dim: int = 16,
                 mamba_heads: int = 4, mamba_head_dim: int = 16,
                 n_groups: int = 2, state_size: int = 16,
                 conv_kernel: int = 4, chunk: int = CHUNK,
                 d_expert: int = 24, d_shared: int = 48, n_experts: int = 8,
                 experts_held: int | None = None, first_expert: int = 0,
                 top_k: int = 2, routed_scale: float = 2.5, vocab: int = 96,
                 max_seq_len: int = 64, piece: int = 16,
                 rms_eps: float = 1e-5, max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16",
                 record: bool = False):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        served = pattern[:len(pattern) if n_layers is None else int(n_layers)]
        if not served or set(served) - set(_KINDS) or (
                n_layers is not None and len(served) != int(n_layers)):
            raise ValueError(f"a pattern of M, * and E, {n_layers} letters "
                             f"of it served: {pattern!r}")
        self.layer_kinds = tuple(_KINDS[c] for c in served)
        if not all(kind in self.layer_kinds for kind in _KINDS.values()):
            raise ValueError(f"{served!r} holds all three kinds of layer")
        self.n_layers, self.d_model = len(served), int(d_model)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = int(head_dim)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{n_heads} query heads over {n_kv_heads} "
                             "key/value heads")
        self._mamba_setup(mamba_heads, mamba_head_dim, n_groups, state_size,
                          conv_kernel)
        self.d_expert, self.d_shared = int(d_expert), int(d_shared)
        self.n_experts, self.first_expert = int(n_experts), int(first_expert)
        self.experts_held = int(n_experts if experts_held is None
                                else experts_held)
        self.top_k, self.routed_scale = int(top_k), float(routed_scale)
        self.rms_eps, self.piece = float(rms_eps), int(piece)
        # A piece is whole chunks of the chunked form (a piece shorter than
        # the published chunk is one chunk).
        self.chunk = min(int(chunk), self.piece)
        if self.piece % self.chunk or self.max_seq_len % self.piece:
            raise ValueError(f"max_seq_len divides into pieces ({piece}), a "
                             f"piece into chunks ({self.chunk})")
        self.dtype = str(dtype)
        self._seed = seed
        self._check_experts()
        # Two prompts a piece program at most (what was measured: PERF.md
        # section 6, PR 47); the scheduler runs the smallest compiled count
        # that holds those standing in line.
        self.prefill_piece = (self.piece, 2)
        self.stream_record = record_width(
            self.layer_kinds.count("none") * self.held_words) if record else 0

    # -- what the scheduler counts (models/decoder.py) ---------------------------

    def cache_rows_by_kind(self, n: int) -> tuple[int, int, int]:
        """(ring rows, whole-context rows, past the ring) of a decode step at
        context length ``n``: no ring; every attention layer reads every
        position's row."""
        return 0, self.layer_kinds.count("rows") * n, 0

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves (made, and rounded to
        bfloat16, when asked for).  Every layer its norm ``ln``; an M layer
        models/mamba2.py's leaves (``_mamba_weights``: ``W_in`` by its columns
        ``wz, wxbc, wdt``, the convolution, the heads' scalars, ``gnorm`` and
        ``wo``; a \\* layer ``wq, wk, wv, wo``; an E layer the router and its
        selection bias (float32), the shared expert's ``su, sd`` and the held
        experts' stacked ``eu`` (``W_u^T``) and ``ed``, both ``[E, f, d]``: a
        width that is no multiple of 128 lanes is never the minor axis."""
        d = self.d_model
        f, fs, e = self.d_expert, self.d_shared, self.experts_held
        hd = self.head_dim
        w, mat, gain = self._weight_makers()

        def layer(kind: str):
            lp = {"ln": gain(d)}
            if kind == "state":
                lp.update(self._mamba_weights(w, mat, gain))
            elif kind == "rows":
                lp.update(wq=mat(d, self.n_heads * hd),
                          wk=mat(d, self.n_kv_heads * hd),
                          wv=mat(d, self.n_kv_heads * hd),
                          wo=mat(self.n_heads * hd, d))
            else:
                lp.update(
                    router=w(d, self.n_experts, scale=1.0 / math.sqrt(d),
                             dtype="float32"),
                    router_bias=w(self.n_experts, scale=0.02,
                                  dtype="float32"),
                    su=mat(d, fs), sd=mat(fs, d),
                    eu=w(e, f, d, scale=1.0 / math.sqrt(d),
                         first=self.first_expert),
                    ed=w(e, f, d, scale=1.0 / math.sqrt(f),
                         first=self.first_expert))
            return lp

        return {"embed": w(self.vocab, d, scale=1.0),
                "layers": [layer(kind) for kind in self.layer_kinds],
                "lnf": gain(d), "head": mat(d, self.vocab)}

    # -- the model's own blocks -------------------------------------------------

    def _project(self, lp, x, pos):
        """An attention layer's x ``[n, d]`` float32 -> q ``[n, H, D]``, k, v
        ``[n, Hkv, D]`` float32; no position enters (``pos`` is there for the
        benchmark's control that serves a rotated reading)."""
        del pos
        return self._heads(lp, rms_norm(x, lp["ln"], self.rms_eps))

    def _expert_block(self, lp, x, live, tile_m):
        """An E layer for rows x ``[n, d]`` -> (x, routing counts, choices
        ``[n, k]``)."""
        h = rms_norm(x, lp["ln"], self.rms_eps)
        y, counts, top_i = self._experts(lp, h, live, tile_m)
        return (x + y + self._dense_expert(h, lp["su"], lp["sd"]), counts,
                top_i)

    # -- the decode step's parts (models/decoder.py) ---------------------------

    def _after_rows(self, lp, h, o, live, tile_m):
        """A mixer layer's tail: the residual add and nothing else (no
        routing: the experts are layers of their own)."""
        return h + self._mm(o, lp["wo"]), 0, ()

    def _after_attention(self, lp, x, o):
        h, _, _ = self._after_rows(lp, x["h"], o.reshape(o.shape[0], -1),
                                   x["live"], TILE_M_WAVE)
        return {**x, "h": h}

    def _feed_forward(self, lp, x):
        h, stats, top_i = self._expert_block(lp, x["h"], x["live"],
                                             TILE_M_WAVE)
        return {**x, "h": h, "stats": x["stats"] + stats,
                "route": x["route"] + (top_i,)}

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``k, v [L_r, R, max_seq_len, Hkv * D]`` and ``conv [L_s, R, (taps -
        1) * conv_dim]`` in the model's dtype, ``s [L_s, R, H / pack, N, pack
        * P]`` float32 (``R = capacity + 1``: the last slot absorbs padded
        lanes) and ``tok [R]``, each slot's latest token on the device."""
        import jax.numpy as jnp

        r, dt = capacity + 1, jnp.dtype(self.dtype)
        rows = (self.layer_kinds.count("rows"), r, self.max_seq_len,
                self.n_kv_heads * self.head_dim)
        return {"k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
                **self._state_arena(r, dt), "tok": jnp.zeros(r, jnp.int32)}
