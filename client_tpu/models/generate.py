"""Autoregressive decoder LM (`tiny_gpt`) for generative serving.

No reference counterpart exists (the reference's only streaming model is the
repeat/decoupled demo, src/python/examples/simple_grpc_custom_repeat.py);
this is the framework's generative workload: a decoder-only transformer
served token-by-token through the decoupled response protocol, with
**iteration-level (continuous) batching** — concurrent generation streams
share each decode step via a KV-cache arena in HBM
(client_tpu/engine/generative.py).

TPU-first shapes: the KV cache is one pytree whose k/v leaves are
``[n_layers, capacity+1, max_seq_len, heads*head_dim]`` float32: for this
full-attention decoder a stream's slot is one row per position (a slot is the
backend's to define: ``models/evabyte.py`` keeps bfloat16 chunk summaries and
a window of exact rows in the same ``[L, R, S, H*D]`` frame, and the scheduler
and the decode kernel take either).  The +1 slot absorbs padded decode lanes;
the heads' features lie side by side on the minor axis so the chip's (8, 128)
tile holds a leaf without padding; prefill writes a
whole row, each decode wave writes one position per active stream in place
and reads each live row once (ops/decode_kernel.py) — no dynamic shapes
anywhere, so XLA compiles one executable per (prompt bucket | wave bucket).

Weights are random (seeded) — generation is deterministic nonsense, which is
exactly what the correctness tests need: batched decode must produce
bit-identical token streams to solo decode.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from client_tpu.models import register_model
from client_tpu.models.decoder import DecoderBackend, sample_into_slots


def _ln(x, g, b, eps=1e-5):
    import jax.numpy as jnp

    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


class TinyGptBackend(DecoderBackend):
    """Decoder-only LM: pre-norm LayerNorm blocks, learned positions, a GELU
    feed-forward, float32 throughout (``models/decoder.py`` for what it is
    served through)."""

    def __init__(self, name: str = "tiny_gpt", n_layers: int = 4,
                 d_model: int = 256, n_heads: int = 4, d_ff: int = 1024,
                 vocab: int = 512, max_seq_len: int = 128,
                 max_streams: int = 64, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, kv_shards: int = 1):
        # "flash" is the long-context generation path (`tiny_gpt_long`:
        # max_seq 2048) for prefill and the full-context forward.
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl,
                         kv_shards=kv_shards)
        # Flash block caps (block_q, block_k), what one DMA brings.  On v5e
        # a layer of GPT-2's prefill (8 x 1024 positions) takes 0.36 ms in
        # one 1024 x 1024 block a head pair, 0.46 in 512 x 1024 and 0.61 in
        # 512 x 512 though that grid skips a quarter of the rectangle: a
        # grid step costs more than the arithmetic it saves, so the kernel
        # cuts the causal triangle inside the block (0.25 ms; PERF.md
        # section 6, PR 29).  Tests shrink them to drive the multi-block
        # grid at short sequence.
        self.flash_blocks = (1024, 1024)
        self.n_layers, self.d_model = n_layers, d_model
        self.n_heads, self.d_ff = n_heads, d_ff
        self.head_dim = d_model // n_heads
        self._seed = seed

    # -- params --------------------------------------------------------------

    def _init_params(self):
        rng = np.random.default_rng(self._seed)
        d, f, v = self.d_model, self.d_ff, self.vocab

        def w(*shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[0])
            return (rng.standard_normal(shape) * scale).astype(np.float32)

        layers = []
        for _ in range(self.n_layers):
            layers.append({
                "ln1g": np.ones(d, np.float32), "ln1b": np.zeros(d, np.float32),
                "wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
                "ln2g": np.ones(d, np.float32), "ln2b": np.zeros(d, np.float32),
                "w1": w(d, f), "w2": w(f, d),
            })
        return {
            "embed": w(v, d, scale=0.02), "pos": w(self.max_seq_len, d, scale=0.02),
            "layers": layers,
            "lnfg": np.ones(d, np.float32), "lnfb": np.zeros(d, np.float32),
            "head": w(d, v),
        }

    def make_apply_params(self):
        """Full-context forward (no cache): logits for every position.
        Model-level entry for warmup/diagnostics; serving goes through
        prefill/decode below."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            ids = inputs["INPUT_IDS"].astype("int32")
            x = self._stack(p, self._embed_prompt(p, ids[None]),
                            causal=True)[0]
            return {"logits": self._logits(p, x)}

        return apply, params

    # -- the model's parts (models/decoder.py) --------------------------------

    def _embed(self, p, tokens, pos):
        return p["embed"][tokens] + p["pos"][pos]

    def _embed_prompt(self, p, ids):
        import jax.numpy as jnp

        return self._embed(p, ids, jnp.arange(ids.shape[-1]))

    def _qkv(self, lp, x, pos, heads=True):
        """x ``[..., d]`` -> q, k, v ``[..., H, D]``, or with ``heads=False``
        ``[..., H*D]`` as the projections leave them: an arena row's layout
        and the flash kernel's.  Positions are in the embedding."""
        h = _ln(x, lp["ln1g"], lp["ln1b"])
        shape = ((*h.shape[:-1], self.n_heads, self.head_dim) if heads
                 else h.shape)
        return tuple((h @ lp[w]).reshape(shape) for w in ("wq", "wk", "wv"))

    def _ffn(self, lp, h):
        """Position-wise FFN on [T, d] rows; the MoE generative family
        (parallel/serving.py MoeGptBackend) overrides this with routed
        experts — attention, KV arena, and the prefill/decode programs are
        shared unchanged."""
        import jax

        return jax.nn.gelu(h @ lp["w1"]) @ lp["w2"]

    def _after_attention(self, lp, x, o):
        """x ``[B, d]`` (a wave: the lanes are the rows) or ``[B, n, d]``."""
        import jax

        x = x + o.reshape(x.shape) @ lp["wo"]
        h = _ln(x, lp["ln2g"], lp["ln2b"])
        # `_ffn` takes one sequence's [T, d] rows (a routed variant sizes
        # its expert queues by T).
        ffn = functools.partial(self._ffn, lp)
        return x + (jax.vmap(ffn)(h) if h.ndim == 3 else ffn(h))

    def _logits(self, p, x):
        return _ln(x, p["lnfg"], p["lnfb"]) @ p["head"]

    def _stack(self, p, x, causal, on_kv=None):
        """Full-context transformer stack (no cache reads) over ``x`` [B, n,
        d].  ``on_kv(li, k, v)`` observes each layer's K/V at trace time,
        ``[B, n, H*D]`` as the projections leave them, and returns them as
        the layer goes on to use them — the prefill path uses it to populate
        the KV arena with the same math the plain forward runs.  q, k and v
        stay in that layout from ``h @ w`` to ``@ wo``."""
        import jax
        import jax.numpy as jnp

        b, n, _ = x.shape
        h_, d_ = self.n_heads, self.head_dim
        pos = jnp.arange(n)
        mask = pos[None, :] <= pos[:, None] if causal else None
        use_flash = self.attention_impl == "flash" and causal

        def attend(q, k, v):
            if use_flash:
                from client_tpu.engine.backend_init import pallas_interpret
                from client_tpu.ops.decode_kernel import pick_block_s
                from client_tpu.ops.flash_attention import flash_attention

                cap_q, cap_k = self.flash_blocks
                return flash_attention(
                    q, k, v, causal=True, n_heads=h_,
                    block_q=pick_block_s(n, cap_q),
                    block_k=pick_block_s(n, cap_k),
                    interpret=pallas_interpret())
            q, k, v = (t.reshape(b, n, h_, d_) for t in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d_)
            if mask is not None:
                s = jnp.where(mask[None, None], s, -1e30)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s), v)

        def layer(x, lp, li):
            q, k, v = self._qkv(lp, x, None, heads=False)
            if on_kv is not None:
                k, v = on_kv(li, k, v)
            return self._after_attention(lp, x, attend(q, k, v))

        return self._walk_layers(p, layer, x)

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """KV arena pytree: k/v of shape [L, R, S, H*D] float32 (S =
        ``max_seq_len``: one row per position) plus ``tok`` [R] —
        each row's latest token, kept ON DEVICE so decode waves chain
        without a host round trip per step (the scheduler pipelines waves
        and fetches emitted tokens asynchronously).  A position's row is
        what ``h @ wk`` produced, heads side by side: lane-dense, so the
        device stores a leaf unpadded in row-major order and a decode wave
        can address one position of one row.  Unsharded, R is
        ``capacity + 1`` (the +1 dummy row absorbs padded decode lanes);
        with ``kv_shards > 1`` the rows carry a junk row per shard and the
        k/v leaves are placed row-sharded over the "kv" mesh
        (``NamedSharding``) — capacity beyond one chip's HBM."""
        import jax.numpy as jnp

        from client_tpu.parallel.kv_shard import (arena_row_layout,
                                                  shard_arena)

        total, _free, _dummy = arena_row_layout(capacity, self.kv_shards)
        shape = (self.n_layers, total, self.max_seq_len, self.d_model)
        arena = {"k": jnp.zeros(shape, jnp.float32),
                 "v": jnp.zeros(shape, jnp.float32),
                 "tok": jnp.zeros(total, jnp.int32)}
        if self.kv_shards > 1:
            arena = shard_arena(arena, self._mesh())
        return arena

    def prefill_fn(self):
        """``PREFILL_ARGS`` less ``starts`` (ids ``[B, S_pad]``) -> (arena,
        first_tokens[B]).

        BATCHED prefill: writes each prompt's K/V into its arena row and
        samples the first token after each prompt's last real position —
        B admits cost ONE device round trip instead of B (round-2's
        per-admit prefill stalled every live decode stream for each admit).
        Causal masking makes the padded tail invisible to every valid
        query; padded LANES (rows pointing at the dummy row) are absorbed
        the same way decode waves absorb them.  Each layer's K and V go
        from the projection into the donated arena's rows as they are
        produced: nothing is stacked, transposed or staged.
        """
        import jax
        import jax.numpy as jnp

        write = self._prompt_rows_writer()

        def prefill(p, arena, rows, ids, lens, seeds, temps, top_ks, top_ps,
                    sample=True):
            b = rows.shape[0]
            leaves = [arena["k"], arena["v"]]

            def on_kv(li, k, v):
                # The barrier orders the write before the layer's attention:
                # left alone the compiler defers all writes to the end and
                # keeps every layer's K and V alive until then.
                k_a, v_a, k, v = jax.lax.optimization_barrier(
                    (*write(*leaves, k, v, rows, li), k, v))
                leaves[:] = k_a, v_a
                return k, v

            x = self._stack(p, self._embed_prompt(p, ids), causal=True,
                            on_kv=on_kv)                 # [B, S_pad, d]
            logits = self._logits(p, x[jnp.arange(b), lens - 1])
            return sample_into_slots(
                {**arena, "k": leaves[0], "v": leaves[1]}, rows, logits,
                seeds, lens, temps, top_ks, top_ps, sample)

        return prefill


register_model("tiny_gpt")(TinyGptBackend)
# tiny_gpt's weights behind the XLA oracle of the decode step, whatever the
# platform: what chip_smoke's phase B compares the served kernel with.
register_model("tiny_gpt_oracle", default=False)(
    lambda: TinyGptBackend(name="tiny_gpt_oracle", attn_impl="reference"))
# Long-context generation: seq 2048 with flash-attention prefill (the
# O(S^2) einsum scores would dominate prompt admission at this length);
# opt-in — a default load-all server shouldn't pay the 2048-wide arena.
register_model("tiny_gpt_long", default=False)(
    lambda: TinyGptBackend(name="tiny_gpt_long", max_seq_len=2048,
                           max_streams=16, attention_impl="flash"))
