"""A dense decoder whose layer stack runs several passes over one set of
weights (`ouro`), served through the generative path.

The architecture is the public ``Ouro-2.6B`` config's (``model_type``
``ouro``, ``total_ut_steps`` passes): full attention with rotary positions
(rotate-half over the whole head), a SwiGLU, **a norm before and after** every
mixer and feed-forward (a sandwich), no bias, an untied head; a float32
residual stream and float32 logits over bfloat16 matmuls.  With ``x = E[ids]``,
for pass ``t`` and layer ``l`` (the same weights in every pass)::

    a = N(x; ln1);  q, k, v = a Wq, a Wk, a Wv;  q, k = rope(q, pos), rope(k, pos)
    K[t, l, pos], V[t, l, pos] <- k, v      (a cache of its own a (pass, layer))
    o = softmax(q K[t, l, :pos + 1]^T / sqrt(D)) V[t, l, :pos + 1]
    x = x + N(o Wo; ln2)
    m = N(x; ln3);  x = x + N((silu(m Wg) * (m Wu)) Wd; ln4)
    after the last layer of EVERY pass:  x = N(x; lnf)   (the next pass's input)
    logits = x W_head  after the last pass

An **exit gate** ``lambda_t = sigmoid(x w_e + b_e)`` closes every pass; the
model leaves at the pass where the gates' cumulated exit probability reaches
``early_exit_threshold``.  At the published threshold of 1 that is the last
pass for every token (it takes the remaining mass), so the served programs
compute no gate: the weights are in the tree (``exit_w``, ``exit_b``) and a
threshold under 1 is refused at load, because lanes of one wave would then
stop at different passes, which the wave's frame cannot run yet.

**The pass axis is the frames'** (models/decoder.py ``passes``,
``_between_passes``): this file declares ``passes`` and supplies one layer's
parts; the wave and the piece walk ``passes x layers`` over the one list
``p["layers"]``, and the cache's leaves ``k, v [passes x layers, R,
max_seq_len, Hkv * D]`` hold pass ``t``'s rows of layer ``l`` at ``t x layers +
l``.  **Decode** reads them with the grouped-query decode kernel (a group of
one where every query head has its own key/value head); **prefill** is by
pieces through models/grouped_query.py's piece attention.
"""

from __future__ import annotations

import math

from client_tpu.models.decoder import record_width
from client_tpu.models.grouped_query import GroupedQueryPieces
from client_tpu.models.layers import rms_norm, rope
from client_tpu.models.seeded import SeededDecoder


class OuroBackend(GroupedQueryPieces, SeededDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``dtype="float32"`` makes weights, cache and matmuls float32
    (the tests' exact comparison); the served form is bfloat16."""

    def __init__(self, name: str = "ouro", n_layers: int = 3,
                 passes: int = 4, early_exit_threshold: float = 1.0,
                 d_model: int = 64, n_heads: int = 4, n_kv_heads: int = 4,
                 head_dim: int = 16, d_ff: int = 96, vocab: int = 96,
                 max_seq_len: int = 64, piece: int = 16,
                 rope_theta: float = 1000000.0, rms_eps: float = 1e-6,
                 max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16",
                 record: bool = False):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        if float(early_exit_threshold) < 1.0:
            raise ValueError(
                f"early_exit_threshold {early_exit_threshold} < 1: tokens "
                "would leave the stack at different passes, and a wave runs "
                "every lane through the same passes x layers (lanes of one "
                "wave that stop at different passes are what the system "
                "cannot run yet); only the published threshold of 1, every "
                f"token through all {passes} passes, is served")
        self.n_layers, self.passes = int(n_layers), int(passes)
        if self.passes < 1:
            raise ValueError(f"passes must be >= 1, got {passes}")
        self.d_model, self.d_ff = int(d_model), int(d_ff)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim, self.piece = int(head_dim), int(piece)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{n_heads} query heads over {n_kv_heads} "
                             "key/value heads")
        if self.max_seq_len % self.piece:
            raise ValueError(f"max_seq_len divides into pieces ({piece})")
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
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
        context length ``n``: no ring; every layer of every pass reads every
        position's row of its own cache."""
        return 0, self.passes * self.n_layers * n, 0

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves: **one** list of layers
        whatever ``passes`` is.  A layer holds its four norms, the four
        projections, the SwiGLU's ``wgu [d, 2f]`` (gate | up) and ``wd``; the
        tree the final norm, the head and the exit gate (float32)."""
        d, hd, f = self.d_model, self.head_dim, self.d_ff
        w, mat, gain = self._weight_makers()

        def layer():
            return {"ln1": gain(d), "ln2": gain(d), "ln3": gain(d),
                    "ln4": gain(d),
                    "wq": mat(d, self.n_heads * hd),
                    "wk": mat(d, self.n_kv_heads * hd),
                    "wv": mat(d, self.n_kv_heads * hd),
                    "wo": mat(self.n_heads * hd, d),
                    "wgu": mat(d, 2 * f), "wd": mat(f, d)}

        return {"embed": w(self.vocab, d, scale=1.0),
                "layers": [layer() for _ in range(self.n_layers)],
                "lnf": gain(d), "head": mat(d, self.vocab),
                "exit_w": w(d, scale=1.0 / math.sqrt(d), dtype="float32"),
                "exit_b": w(1, scale=0.1, dtype="float32")}

    # -- the model's parts (models/decoder.py) ----------------------------------

    def _embed(self, p, tokens, pos):
        import jax.numpy as jnp

        return p["embed"][tokens].astype(jnp.float32)

    def _project(self, lp, x, pos):
        """x ``[n, d]`` float32 -> q ``[n, H, D]``, k, v ``[n, Hkv, D]``
        float32, q and k rotated to ``pos`` (the same in every pass)."""
        q, k, v = self._heads(lp, rms_norm(x, lp["ln1"], self.rms_eps))
        return rope(q, pos, self.rope_theta), rope(k, pos,
                                                   self.rope_theta), v

    def _qkv(self, lp, x, pos):
        return self._project(lp, x, pos)

    def _after_attention(self, lp, x, o):
        """The block behind its attention, for rows x ``[n, d]`` and their
        heads' outputs o ``[n, H, D]`` or ``[n, H * D]``: both sub-blocks'
        outputs are normed before they are added."""
        import jax

        eps, f = self.rms_eps, self.d_ff
        x = x + rms_norm(self._mm(o.reshape(o.shape[0], -1), lp["wo"]),
                         lp["ln2"], eps)
        gu = self._mm(rms_norm(x, lp["ln3"], eps), lp["wgu"])
        y = self._mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], lp["wd"])
        return x + rms_norm(y, lp["ln4"], eps)

    def _between_passes(self, p, x):
        """The final norm closes every pass; the next starts from it."""
        return rms_norm(x, p["lnf"], self.rms_eps)

    def _logits(self, p, x):
        return self._mm(self._between_passes(p, x), p["head"])

    def make_apply_params(self):
        """Full-context forward in the served precision: no cache, no pieces,
        every pass over the whole prompt.  Logits of every position.
        Model-level entry (the engine takes the placed weights from it) and
        the tests' reference; serving goes through pieces and waves."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            import jax.numpy as jnp

            ids = inputs["INPUT_IDS"].astype("int32")
            pos = jnp.arange(ids.shape[0])
            x, *_ = self._walk_kinds(
                p, self._embed(p, ids, pos), None,
                lambda kind, ki, lp, x: self._full_rows_layer(lp, x, pos))
            return {"logits": self._logits(p, x)}

        return apply, params

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``k, v [passes x layers, R, max_seq_len, Hkv * D]`` in the model's
        dtype (``R = capacity + 1``: the last slot absorbs padded lanes) and
        ``tok [R]``, each slot's latest token on the device."""
        import jax.numpy as jnp

        dt = jnp.dtype(self.dtype)
        rows = (self.passes * self.n_layers, capacity + 1, self.max_seq_len,
                self.n_kv_heads * self.head_dim)
        return {"k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
                "tok": jnp.zeros(capacity + 1, jnp.int32)}
