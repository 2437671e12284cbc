"""A dense decoder with a recurrent state: nine Mamba-2 layers to one attention
layer, a SwiGLU feed-forward inside every layer, four scalar multipliers
(`granite_hybrid`), served through the generative path.

The architecture is the public ``granite-4.0-h-micro`` config's
(``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0: no routed
expert, no router).  A layer is a mixer **and** a feed-forward, each a
residual branch under ``residual_multiplier``; ``layer_types`` names the
mixer, ``mamba`` or ``attention``.  RMSNorm, no bias but the convolution's; a
float32 residual stream and float32 logits over bfloat16 matmuls.  With x
``[n, d]``::

    x = embedding_multiplier * E[ids]
    x = x + residual_multiplier * Mixer(N(x; ln))                 every layer
    [g | u] = N(x; ln2) W_in;  x = x + residual_multiplier * (silu(g) * u) W_out
    logits = N(x; lnf) E^T / logits_scaling                      (a tied head)

- *mamba*: models/mamba2.py's mixer with **one** group of B and C for all the
  heads, so the gated norm runs over all ``d_inner`` channels.
- *attention*: ``q = h W_q`` (``n_heads`` of ``head_dim``), ``k, v = h W_k, h
  W_v`` (``n_kv_heads``), a causal softmax of ``q k^T * attention_multiplier``
  (**not** ``1 / sqrt(head_dim)``: ``attn_scale`` of models/decoder.py), query
  head i on key head ``i // (n_heads / n_kv_heads)``, **nothing rotated**; out
  ``o W_o``.

**Two kinds of layer in one arena** (``layer_kinds`` of models/decoder.py's
contract): a mamba layer is a ``"state"`` layer (models/mamba2.py's leaves),
an attention layer a ``"rows"`` layer (``k, v [L_r, R, max_seq_len, Hkv *
D]``); the feed-forward follows either inside ``_after_attention``, so there
is no ``"none"`` layer.  **Decode** advances a wave's states in place
(``ssd_wave_update``) and reads the lanes' rows with the grouped-query decode
kernel.  **Prefill** is by pieces of whole chunks of the chunked form
(models/decoder.py's frame; two lanes a program at most), between waves: no
piece carries a wave (PERF.md section 6, PR 59).

The embedding is seeded at ``1 / embedding_multiplier``, so that ``x0`` has
unit rms as a trained model's does under its multiplier; at scale 1 the stream
would be 12 against branches of 0.22 and no comparison could see a layer.
"""

from __future__ import annotations

from client_tpu.models.decoder import record_width
from client_tpu.models.grouped_query import GroupedQueryPieces
from client_tpu.models.layers import rms_norm
from client_tpu.models.mamba2 import Mamba2Layer
from client_tpu.models.seeded import SeededDecoder

_KINDS = {"mamba": "state", "attention": "rows"}
# Nine state layers to one attention layer, the fifth of ten.
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


class GraniteHybridBackend(Mamba2Layer, GroupedQueryPieces, SeededDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``layer_types`` is the published list as it stands;
    ``dtype="float32"`` makes weights, caches and matmuls float32 (the tests'
    exact comparison)."""

    def __init__(self, name: str = "granite_hybrid",
                 layer_types: tuple[str, ...] = _PERIOD, d_model: int = 64,
                 n_heads: int = 4, n_kv_heads: int = 2,
                 head_dim: int | None = None, d_ff: int = 96,
                 mamba_heads: int = 4, mamba_head_dim: int = 16,
                 n_groups: int = 1, state_size: int = 16,
                 conv_kernel: int = 4, chunk: int = 256,
                 embedding_multiplier: float = 12.0,
                 residual_multiplier: float = 0.22,
                 attention_multiplier: float = 0.0625,
                 logits_scaling: float = 8.0, vocab: int = 96,
                 max_seq_len: int = 64, piece: int = 16,
                 rms_eps: float = 1e-5, max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16",
                 record: bool = False):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        if not layer_types or set(layer_types) - set(_KINDS):
            raise ValueError(f"layer_types of mamba and attention: "
                             f"{layer_types!r}")
        self.layer_kinds = tuple(_KINDS[t] for t in layer_types)
        if set(self.layer_kinds) != set(_KINDS.values()):
            raise ValueError(f"{layer_types!r} holds both kinds of layer")
        self.n_layers, self.d_model = len(layer_types), int(d_model)
        self.d_ff = int(d_ff)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = int(head_dim or self.d_model // self.n_heads)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{n_heads} query heads over {n_kv_heads} "
                             "key/value heads")
        self._mamba_setup(mamba_heads, mamba_head_dim, n_groups, state_size,
                          conv_kernel)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attn_scale = float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.rms_eps, self.piece = float(rms_eps), int(piece)
        # A piece is whole chunks of the chunked form (a piece shorter than
        # the published chunk is one chunk).
        self.chunk = min(int(chunk), self.piece)
        if self.piece % self.chunk or self.max_seq_len % self.piece:
            raise ValueError(f"max_seq_len divides into pieces ({piece}), a "
                             f"piece into chunks ({self.chunk})")
        self.dtype = str(dtype)
        self._seed = seed
        # Two prompts a piece program at most, as the other piece backends
        # that hold more than one (models/nemotron_h.py, PERF.md section 6, PR
        # 47); the scheduler runs the smallest compiled count that holds those
        # standing in line.
        self.prefill_piece = (self.piece, 2)
        self.stream_record = record_width() if record else 0

    # -- what the scheduler counts (models/decoder.py) ---------------------------

    def cache_rows_by_kind(self, n: int) -> tuple[int, int, int]:
        """(ring rows, whole-context rows, past the ring) of a decode step at
        context length ``n``: no ring; every attention layer reads every
        position's row."""
        return 0, self.layer_kinds.count("rows") * n, 0

    def piece_pairs_by_kind(self, start: int, valid: int) -> tuple[int, int]:
        """(window pairs, whole-context pairs) a lane's piece of ``valid``
        positions from ``start`` scores, summed over the attention layers: no
        window; a query at position t scores ``t + 1`` keys."""
        end = start + valid
        return 0, self.layer_kinds.count("rows") * (
            end * (end + 1) - start * (start + 1)) // 2

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves (made, and rounded to
        bfloat16, when asked for).  Every layer the mixer's norm ``ln``, the
        mixer's leaves (models/mamba2.py's ``_mamba_weights``, or ``wq, wk,
        wv, wo``), then the feed-forward's norm ``ln2``, ``wgu [d, 2f]`` (gate
        | up) and ``wd``.  The embedding, which is the head too, at ``1 /
        embedding_multiplier`` (the module docstring)."""
        d, hd, f = self.d_model, self.head_dim, self.d_ff
        w, mat, gain = self._weight_makers()

        def layer(kind: str):
            lp = {"ln": gain(d)}
            if kind == "state":
                lp.update(self._mamba_weights(w, mat, gain))
            else:
                lp.update(wq=mat(d, self.n_heads * hd),
                          wk=mat(d, self.n_kv_heads * hd),
                          wv=mat(d, self.n_kv_heads * hd),
                          wo=mat(self.n_heads * hd, d))
            lp.update(ln2=gain(d), wgu=mat(d, 2 * f), wd=mat(f, d))
            return lp

        return {"embed": w(self.vocab, d,
                           scale=1.0 / self.embedding_multiplier),
                "layers": [layer(kind) for kind in self.layer_kinds],
                "lnf": gain(d)}

    # -- the model's parts (models/decoder.py) ----------------------------------

    def _embed(self, p, tokens, pos):
        import jax.numpy as jnp

        return p["embed"][tokens].astype(jnp.float32) \
            * self.embedding_multiplier

    def _project(self, lp, x, pos):
        """An attention layer's x ``[n, d]`` float32 -> q ``[n, H, D]``, k, v
        ``[n, Hkv, D]`` float32; no position enters (``pos`` is there for the
        benchmark's control that serves a rotated reading)."""
        del pos
        return self._heads(lp, rms_norm(x, lp["ln"], self.rms_eps))

    def _qkv(self, lp, x, pos):
        return self._project(lp, x, pos)

    def _advance(self, lp, x, s_a, conv_a, rows, lens, ki):
        """(The carry between layers is the activations alone.)"""
        del lens
        return self._step_slots(
            lp, self._state_project(lp, x, conv_a.dtype), s_a, conv_a, rows,
            ki)

    def _after_attention(self, lp, x, o):
        """The layer behind its mixer, for rows x ``[n, d]`` and the mixer's
        output o (an attention layer's heads ``[n, H, D]`` or ``[n, H * D]``,
        a state layer's ``[n, d_inner]``): both branches enter under
        ``residual_multiplier``."""
        import jax

        r, f = self.residual_multiplier, self.d_ff
        x = x + r * self._mm(o.reshape(o.shape[0], -1), lp["wo"])
        gu = self._mm(rms_norm(x, lp["ln2"], self.rms_eps), lp["wgu"])
        return x + r * self._mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], lp["wd"])

    def _logits(self, p, x):
        """The tied head: the final norm's rows against the embedding's rows
        (one leaf on the device: the product contracts the embedding's minor
        axis), over ``logits_scaling``."""
        return self._mm(rms_norm(x, p["lnf"], self.rms_eps),
                        p["embed"].T) / self.logits_scaling

    def make_apply_params(self):
        """Full-context forward in the served precision: no cache, no pieces,
        a state walked position by position.  Logits of every position.
        Model-level entry (the engine takes the placed weights from it) and
        the tests' reference; serving goes through pieces and waves."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            import jax.numpy as jnp

            ids = inputs["INPUT_IDS"].astype("int32")
            pos = jnp.arange(ids.shape[0])
            x, *_ = self._walk_kinds(
                p, self._embed(p, ids, pos), None,
                lambda kind, ki, lp, x: getattr(self, f"_full_{kind}_layer")(
                    lp, x, pos))
            return {"logits": self._logits(p, x)}

        return apply, params

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``k, v [L_r, R, max_seq_len, Hkv * D]`` in the model's dtype, the
        state's two leaves (models/mamba2.py; ``R = capacity + 1``: the last
        slot absorbs padded lanes) and ``tok [R]``, each slot's latest token
        on the device."""
        import jax.numpy as jnp

        r, dt = capacity + 1, jnp.dtype(self.dtype)
        rows = (self.layer_kinds.count("rows"), r, self.max_seq_len,
                self.n_kv_heads * self.head_dim)
        return {"k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
                **self._state_arena(r, dt), "tok": jnp.zeros(r, jnp.int32)}
