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
from client_tpu import config as envcfg

import numpy as np

from client_tpu.engine.config import ModelConfig, TensorConfig
from client_tpu.engine.model import ModelBackend
from client_tpu.models import register_model


def _ln(x, g, b, eps=1e-5):
    import jax.numpy as jnp

    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _sample_token(logits, seed, ctx_len, temp, top_k, top_p):
    """Per-stream token choice, fully jit-traceable (vmap over streams).

    - ``temp <= 0`` → greedy argmax (the default; bit-identical to the
      pre-sampling engine).
    - Otherwise: temperature-scaled logits, top-k rank cut (``top_k == 0``
      keeps all), nucleus top-p cumulative cut (first token always kept),
      then a categorical draw.

    Determinism contract: the PRNG key is ``fold_in(PRNGKey(seed),
    ctx_len)`` where ``ctx_len`` is the context length at sampling time —
    a pure function of (request seed, position), NOT of batch composition,
    so batched decode stays bit-identical to solo decode under sampling.
    """
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits).astype(jnp.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), ctx_len)
    scaled = logits / jnp.maximum(temp, 1e-6)
    order = jnp.argsort(-scaled)
    sl = scaled[order]
    probs = jax.nn.softmax(sl)
    cum = jnp.cumsum(probs)
    idx = jnp.arange(sl.shape[0])
    keep = ((cum - probs) < top_p) & jnp.where(top_k > 0, idx < top_k, True)
    keep = keep.at[0].set(True)
    choice = jax.random.categorical(key, jnp.where(keep, sl, -jnp.inf))
    sampled = order[choice].astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


class TinyGptBackend(ModelBackend):
    """Decoder-only LM: INPUT_IDS [-1] -> streamed (TOKEN, INDEX) responses.

    ``max_tokens`` request parameter bounds generation (default 16); the
    stream terminates with an empty ``triton_final_response`` like every
    decoupled model here.
    """

    generative = True

    def __init__(self, name: str = "tiny_gpt", n_layers: int = 4,
                 d_model: int = 256, n_heads: int = 4, d_ff: int = 1024,
                 vocab: int = 512, max_seq_len: int = 128,
                 max_streams: int = 64, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, kv_shards: int = 1):
        # "einsum": XLA-scheduled O(S^2) prefill scores — right for short
        # prompts.  "flash": the Pallas kernel (causal) for prefill and
        # the full-context forward — the long-context generation path
        # (`tiny_gpt_long`: max_seq 2048); decode waves are single-query
        # and always use the masked dense read over the KV arena.
        if attention_impl not in ("einsum", "flash"):
            # Silent fallback would serve the quadratic path at 2048+ —
            # the exact cliff the option exists to avoid.
            raise ValueError(
                f"attention_impl must be 'einsum' or 'flash', got "
                f"{attention_impl!r}")
        self.attention_impl = attention_impl
        # Flash block caps (block_q, block_k), what one DMA brings.  On v5e
        # a layer of GPT-2's prefill (8 x 1024 positions) takes 0.36 ms in
        # one 1024 x 1024 block a head pair, 0.46 in 512 x 1024 and 0.61 in
        # 512 x 512 though that grid skips a quarter of the rectangle: a
        # grid step costs more than the arithmetic it saves, so the kernel
        # cuts the causal triangle inside the block (0.25 ms; PERF.md
        # section 6, PR 29).  Tests shrink them to drive the multi-block
        # grid at short sequence.
        self.flash_blocks = (1024, 1024)
        # Decode-wave implementation: "fused" runs the Pallas kernel
        # (ops/decode_kernel.py): one row written in place, each live row
        # read once.  "reference" is the stacked-XLA oracle (scatter,
        # gather, dense masked softmax) on the same arena — same math,
        # same `_sample_token` sequence, so streams are token-identical
        # either way; the parity tests and chip_smoke's phase B serve it,
        # and so do the GSPMD-sharded families (parallel/serving.py), whose
        # programs XLA has to partition.  Unset ("") the platform decides:
        # the kernel wherever Mosaic compiles it (a TPU), the XLA step
        # where Pallas would only be interpreted.
        if attn_impl is None:
            attn_impl = envcfg.env_str("CLIENT_TPU_ATTN_IMPL")
        if attn_impl not in ("", "reference", "fused"):
            raise ValueError(
                f"attn_impl must be 'reference' or 'fused', got "
                f"{attn_impl!r}")
        self.attn_impl = attn_impl
        # KV arena shards over a "kv" mesh axis (parallel/kv_shard.py);
        # 1 = single-chip arena (the +1-dummy-row layout). >1 requires the
        # fused decode path — the row-sharded layout and the shard_map'd
        # kernel go together.
        self.kv_shards = int(kv_shards)
        if self.kv_shards < 1:
            raise ValueError(f"kv_shards must be >= 1, got {kv_shards}")
        if self.kv_shards > 1:
            if self.attn_impl == "reference":
                raise ValueError(
                    "kv_shards > 1 requires attn_impl='fused' (the "
                    "sharded arena is served by the shard_map'd kernel)")
            self.attn_impl = "fused"
            if max_streams % self.kv_shards:
                raise ValueError(
                    f"max_streams ({max_streams}) must be divisible by "
                    f"kv_shards ({self.kv_shards})")
        # Fused-kernel knobs: key-block tile (None = auto divisor of
        # max_seq_len) and the cross-shard combine ("ring" remote-DMA
        # kernel | "psum" XLA collective).
        self.decode_block_s: int | None = None
        self.kv_combine = "ring"
        self._kv_mesh = None
        self.n_layers, self.d_model = n_layers, d_model
        self.n_heads, self.d_ff = n_heads, d_ff
        self.head_dim = d_model // n_heads
        self.vocab, self.max_seq_len = vocab, max_seq_len
        self.max_streams = max_streams
        self.default_max_tokens = 16
        self._seed = seed
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=0,
            input=[TensorConfig("INPUT_IDS", "INT32", [-1])],
            output=[
                TensorConfig("TOKEN", "INT32", [1]),
                TensorConfig("INDEX", "UINT32", [1]),
            ],
            decoupled=True,
        )

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

    def place_params(self, params):
        """Device placement hook; sharded variants override with
        per-tensor NamedShardings (parallel/serving.py)."""
        import jax

        return jax.device_put(params)

    def make_apply_params(self):
        """Full-context forward (no cache): logits for every position.
        Model-level entry for warmup/diagnostics; serving goes through
        prefill/decode below."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            ids = inputs["INPUT_IDS"].astype("int32")
            x, _ = self._embed_positions(p, ids[None], 0)
            x = self._stack(p, x, causal=True)[0]
            logits = _ln(x, p["lnfg"], p["lnfb"]) @ p["head"]
            return {"logits": logits}

        return apply, params

    # -- shared blocks --------------------------------------------------------

    def _ffn(self, lp, h):
        """Position-wise FFN on [T, d] rows; the MoE generative family
        (parallel/serving.py MoeGptBackend) overrides this with routed
        experts — attention, KV arena, and the prefill/decode programs are
        shared unchanged."""
        import jax

        return jax.nn.gelu(h @ lp["w1"]) @ lp["w2"]

    def _embed_positions(self, p, ids, start):
        import jax.numpy as jnp

        pos = jnp.arange(ids.shape[-1]) + start
        return p["embed"][ids] + p["pos"][pos], pos

    def _stack(self, p, x, causal, on_kv=None):
        """Full-context transformer stack (no cache reads) over ``x`` [B, n,
        d].  ``on_kv(li, k, v)`` observes each layer's K/V at trace time,
        ``[B, n, H*D]`` as the projections leave them, and returns them as
        the layer goes on to use them — the prefill path uses it to populate
        the KV arena with the same math the plain forward runs.  q, k and v
        stay in that layout from ``h @ w`` to ``@ wo``: it is an arena
        row's and the flash kernel's."""
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
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s), v)
            return o.reshape(b, n, self.d_model)

        for li, lp in enumerate(p["layers"]):
            h = _ln(x, lp["ln1g"], lp["ln1b"])
            q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
            if on_kv is not None:
                k, v = on_kv(li, k, v)
            x = x + attend(q, k, v) @ lp["wo"]
            h2 = _ln(x, lp["ln2g"], lp["ln2b"])
            # `_ffn` takes one sequence's [T, d] rows (a routed variant
            # sizes its expert queues by T).
            x = x + jax.vmap(lambda t, lp=lp: self._ffn(lp, t))(h2)
        return x

    # -- generative interface (used by GenerativeScheduler) -------------------

    def arena_rows(self, capacity: int | None = None):
        """(free_rows, dummy_row) of the arena this backend builds: which
        rows the scheduler may hand to streams, and the junk row padded
        lanes point at.  Single-chip: rows 0..cap-1 plus the trailing
        dummy; sharded: one junk row per shard (parallel/kv_shard.py), so
        the free list is non-contiguous and the scheduler must not assume
        ``row == lane`` arithmetic."""
        cap = self.max_streams if capacity is None else int(capacity)
        from client_tpu.parallel.kv_shard import arena_row_layout

        _total, free, dummy = arena_row_layout(cap, self.kv_shards)
        return free, dummy

    def _mesh(self):
        if self._kv_mesh is None:
            from client_tpu.parallel.kv_shard import kv_mesh

            self._kv_mesh = kv_mesh(self.kv_shards)
        return self._kv_mesh

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

    def _use_kernel(self) -> bool:
        """Whether the arena is the Pallas kernels' (the decode wave and
        prefill's write): by ``attn_impl``, or unset wherever Mosaic
        compiles them."""
        from client_tpu.engine.backend_init import pallas_interpret

        return self.attn_impl == "fused" or (
            not self.attn_impl and not pallas_interpret())

    def _prompt_rows_writer(self):
        """``write(k_arena, v_arena, k, v, rows, layer)`` -> the two leaves
        with ``[layer, rows[b], :n]`` holding lane b's ``[n, H*D]`` slab.
        Where the decode wave is the kernel, so is this (one DMA a lane and
        leaf, ops/arena_write.py; per shard of a row-sharded arena); else,
        and for a prompt bucket shorter than a row group, XLA's in-place
        scatter."""
        from client_tpu.engine.backend_init import pallas_interpret
        from client_tpu.ops.arena_write import (kernel_writes,
                                                reference_write_prompt_rows,
                                                write_prompt_rows)

        interpret = pallas_interpret()
        kernel = self._use_kernel()
        if kernel and self.kv_shards > 1:
            from client_tpu.parallel.kv_shard import \
                sharded_write_prompt_rows

            put = functools.partial(sharded_write_prompt_rows, self._mesh())
        else:
            put = write_prompt_rows

        def write(k_a, v_a, k, v, rows, layer):
            if kernel and kernel_writes(k.shape[1], k_a.dtype):
                return put(k_a, v_a, k, v, rows, layer=layer,
                           interpret=interpret)
            return reference_write_prompt_rows(k_a, v_a, k, v, rows,
                                               layer=layer)

        return write

    def prefill_fn(self):
        """(params, arena, rows[B], ids[B, S_pad], lens[B], seeds[B],
        temps[B], top_ks[B], top_ps[B]) -> (arena, first_tokens[B]).

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

            x, _pos = self._embed_positions(p, ids, 0)       # [B, S_pad, d]
            x = self._stack(p, x, causal=True, on_kv=on_kv)
            xf = _ln(x[jnp.arange(b), lens - 1], p["lnfg"], p["lnfb"])
            logits = xf @ p["head"]                      # [B, vocab]
            # `sample` is a STATIC arg: the all-greedy variant (the default
            # workload) compiles without the sort/cumsum/PRNG pipeline —
            # jnp.where alone would keep both branches in the executable.
            if sample:
                tokens = jax.vmap(_sample_token)(
                    logits, seeds, lens, temps, top_ks, top_ps)
            else:
                tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # The first token lands in the device-side token slot so the
            # first decode wave can start without the host fetch.
            arena = {**arena, "k": leaves[0], "v": leaves[1],
                     "tok": arena["tok"].at[rows].set(tokens)}
            return arena, tokens

        return prefill

    def decode_chunk_fn(self):
        """(params, arena, rows[B], lens[B], seeds[B], temps[B], top_ks[B],
        top_ps[B], sample, k) -> (arena, tokens[k, B]).

        K decode waves in ONE device execution via ``lax.scan`` over the
        single-wave body: each scanned step gathers its inputs from the
        arena token slots the previous step wrote, so the whole chunk
        chains on device.  One dispatch (and one transport command round)
        then advances every live stream K tokens — on a high-latency
        transport this divides the scheduler's dispatch-side overhead by
        K.  ``k`` is static (one executable per (wave bucket, K)); the
        per-step math is the decode_fn body unchanged, so sampling's
        fold_in(seed, ctx_len) sequence is identical to K separate waves.
        """
        import jax

        decode = self.decode_fn()

        def decode_chunk(p, arena, rows, lens, seeds, temps, top_ks,
                         top_ps, sample=True, k=2):
            def body(carry, _):
                arena_c, lens_c = carry
                arena_c, nxt = decode(p, arena_c, rows, lens_c, seeds,
                                      temps, top_ks, top_ps, sample)
                return (arena_c, lens_c + 1), nxt

            (arena, _), toks = jax.lax.scan(body, (arena, lens), None,
                                            length=k)
            return arena, toks  # [k, B]

        return decode_chunk

    def decode_fn(self):
        """(params, arena, rows[B], lens[B], seeds[B], temps[B],
        top_ks[B], top_ps[B]) -> (arena, next[B]).

        One batched decode step: each stream's input token is GATHERED from
        the arena's device-side token slots (written by prefill / the
        previous wave), so consecutive waves chain on device with no host
        round trip between them — the scheduler dispatches waves ahead and
        fetches emitted tokens asynchronously. Write each stream's new
        K/V at its current position, attend over its valid prefix,
        per-stream sampled (or greedy) next token.

        The served step is ``_fused_decode_fn`` (the Pallas kernel);
        ``attn_impl="reference"`` selects the body below, the per-layer
        scatter/gather/dense-softmax stack kept as the parity oracle.
        """
        if self._use_kernel():
            return self._fused_decode_fn()
        import jax
        import jax.numpy as jnp

        h_, d_ = self.n_heads, self.head_dim

        def decode(p, arena, rows, lens, seeds, temps, top_ks,
                   top_ps, sample=True):
            b = rows.shape[0]
            tokens = arena["tok"][rows]                      # [B]
            x = p["embed"][tokens] + p["pos"][lens]          # [B, d]
            for li, lp in enumerate(p["layers"]):
                h = _ln(x, lp["ln1g"], lp["ln1b"])
                q = (h @ lp["wq"]).reshape(b, h_, d_)
                k = (h @ lp["wk"]).reshape(b, h_, d_)
                v = (h @ lp["wv"]).reshape(b, h_, d_)
                arena = {
                    **arena,
                    "k": arena["k"].at[li, rows, lens].set(
                        k.reshape(b, self.d_model)),
                    "v": arena["v"].at[li, rows, lens].set(
                        v.reshape(b, self.d_model)),
                }
                seq = self.max_seq_len
                ck = arena["k"][li, rows].reshape(b, seq, h_, d_)
                cv = arena["v"][li, rows].reshape(b, seq, h_, d_)
                s = jnp.einsum("bhd,bshd->bhs", q, ck) / math.sqrt(d_)
                mask = jnp.arange(seq)[None, :] <= lens[:, None]
                s = jnp.where(mask[:, None, :], s, -1e30)
                o = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s), cv)
                x = x + o.reshape(b, self.d_model) @ lp["wo"]
                h2 = _ln(x, lp["ln2g"], lp["ln2b"])
                x = x + self._ffn(lp, h2)
            xf = _ln(x, p["lnfg"], p["lnfb"])
            logits = xf @ p["head"]                          # [B, vocab]
            # ctx at sampling = lens + 1 (the token just written occupies
            # position lens) — continues the prefill fold sequence exactly.
            # `sample` static: all-greedy waves skip the sampling pipeline.
            if sample:
                nxt = jax.vmap(_sample_token)(
                    logits, seeds, lens + 1, temps, top_ks, top_ps)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            arena = dict(arena)
            arena["tok"] = arena["tok"].at[rows].set(nxt)
            return arena, nxt

        return decode

    def _fused_decode_fn(self):
        """The served decode step: each layer's K/V write + masked
        attention is ONE Pallas grid (ops/decode_kernel.py) over the
        donated arena — one row group written in place per lane, each
        live row streamed through VMEM once, no [B, S, ...] gather and no
        pass over an arena leaf.  With ``kv_shards > 1`` the per-layer call
        is the shard_map-wrapped variant over the row-sharded arena
        (parallel/kv_shard.py).  ``decode_chunk_fn`` scans this body
        unchanged, so chunked decode inherits the kernel for free."""
        import jax
        import jax.numpy as jnp

        h_, d_ = self.n_heads, self.head_dim
        from client_tpu.engine.backend_init import pallas_interpret

        interpret = pallas_interpret()
        block_s = self.decode_block_s

        if self.kv_shards > 1:
            from client_tpu.parallel.kv_shard import \
                sharded_decode_attention

            mesh, combine = self._mesh(), self.kv_combine

            def attend(k_a, v_a, q, k, v, rows, lens, layer):
                return sharded_decode_attention(
                    mesh, k_a, v_a, q, k, v, rows, lens, layer=layer,
                    block_s=block_s, interpret=interpret, combine=combine)
        else:
            from client_tpu.ops.decode_kernel import decode_wave_attention

            def attend(k_a, v_a, q, k, v, rows, lens, layer):
                return decode_wave_attention(
                    k_a, v_a, q, k, v, rows, lens, layer=layer,
                    block_s=block_s, interpret=interpret)

        def decode(p, arena, rows, lens, seeds, temps, top_ks,
                   top_ps, sample=True):
            b = rows.shape[0]
            tokens = arena["tok"][rows]                      # [B]
            x = p["embed"][tokens] + p["pos"][lens]          # [B, d]
            k_a, v_a = arena["k"], arena["v"]
            for li, lp in enumerate(p["layers"]):
                h = _ln(x, lp["ln1g"], lp["ln1b"])
                q = (h @ lp["wq"]).reshape(b, h_, d_)
                k = (h @ lp["wk"]).reshape(b, h_, d_)
                v = (h @ lp["wv"]).reshape(b, h_, d_)
                k_a, v_a, o = attend(k_a, v_a, q, k, v, rows, lens, li)
                x = x + o.reshape(b, self.d_model) @ lp["wo"]
                h2 = _ln(x, lp["ln2g"], lp["ln2b"])
                x = x + self._ffn(lp, h2)
            xf = _ln(x, p["lnfg"], p["lnfb"])
            logits = xf @ p["head"]                          # [B, vocab]
            # Same ctx/sample semantics as the reference body — sampling
            # is bit-identical across impls by construction.
            if sample:
                nxt = jax.vmap(_sample_token)(
                    logits, seeds, lens + 1, temps, top_ks, top_ps)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            arena = {**arena, "k": k_a, "v": v_a,
                     "tok": arena["tok"].at[rows].set(nxt)}
            return arena, nxt

        return decode


register_model("tiny_gpt")(TinyGptBackend)
# Long-context generation: seq 2048 with flash-attention prefill (the
# O(S^2) einsum scores would dominate prompt admission at this length);
# opt-in — a default load-all server shouldn't pay the 2048-wide arena.
register_model("tiny_gpt_long", default=False)(
    lambda: TinyGptBackend(name="tiny_gpt_long", max_seq_len=2048,
                           max_streams=16, attention_impl="flash"))
