"""EvaByte-style byte-level decoder (`evabyte`): EVA chunked linear attention
served through the generative path.

The architecture is the public ``EvaByte/EvaByte`` config's (``model_type``
``evabyte``, ``attention_class`` ``eva``): RMSNorm with a unit offset
``x / rms(x) * (1 + g)``, rotary positions, a SwiGLU feed-forward, no biases,
a float32 residual stream and float32 logits over bfloat16 matmuls, an output
head of ``num_pred_heads x vocab``, and the EVA attention layer
(arXiv:2302.04542, section 4).  Per head (d = head size, W = ``window``,
c = ``chunk``; q, k carry RoPE at their own positions), position i lies in
window j = i // W and attends with **one softmax** to

- the exact keys and values of its own window up to itself
  (``m // W == j and m <= i``), and
- one summary per c-position chunk t of every *earlier* window:
  ``a_m = softmax_{m in t}(phi . k_m)``, ``v~_t = sum_m a_m v_m``,
  ``k~_t = mean_m k_m + mu`` (phi, mu: learned vectors per head).

**The cache** is the model's own, not one slot per position.  A stream's slot
is ``[S_rows, H*D]`` per layer and leaf, bfloat16, summaries first and the
current window's exact rows directly behind them: rows ``[0, n_sum)``
summaries, rows ``[n_sum, n_sum + w_len)`` the window, and ``live = n_sum +
w_len`` is what the shared decode kernel (ops/decode_kernel.py, a prefix
kernel) takes as ``lens``.  Every W positions the window is **dumped**: its
W / c summaries overwrite the head of its own rows, ``n_sum += W / c``,
``w_len = 0`` (``transition_fn``; rows behind are stale and masked by
``lens``, as a padded tail is).  ``max_seq_len`` positions need
``(max_seq_len / W - 1) * W / c + W`` rows, not ``max_seq_len``.

**Prefill goes by window-sized pieces** (``prefill_piece``): piece p of a
prompt holds positions ``[pW, pW + m)``, attends to the slot's summaries (a
prefix every query sees: ``flash_attention(prefix=...)``) and causally to
itself; a full piece writes its W / c summaries only, the last, partial piece
writes its exact rows.  The scheduler (engine/generative.py) learns all of this
from what the backend declares of ``models/decoder.py``'s contract
(``prefill_piece``, ``cache_rows``, ``transition_due``, ``transition_fn``), not
from the model's name; the decode step is that module's frame over the parts
below.

Layers are walked one by one, a Python loop over **leaves of their own**
(``p["layers"][li]["wo"]`` is ``[d, d]``), the arena's leaves whole in the
carry and the layer's number a Python int (``models/decoder.py``'s
``_walk_layers``, as every served decoder).  Every weight is then an entry
parameter of the program, read by its product where it lies.  Under a
``lax.scan`` over stacked leaves ``[L, d, d]`` the compiler wrote ``wq[li]``
and ``wk[li]`` out every iteration and copied each into the layout its
product wanted, 1.30 ms of an 11.86 ms wave at the published widths; as
leaves of their own it still transposed them, so the three projections of a
layer's input are served ``[out, in]`` (``OUT_IN``, ``_mm_t``) and no
program copies a matrix (PERF.md section 6, PR 42;
``tests/test_tpu_compile.py``).  The price is a program that grows with the
depth (eight copies of a layer at ``evabyte_6b5``'s cut; a wave bucket
lowers in 2.4 s where one copy took 1.5): at 32 layers on one chip the
compile time is worth a second look.  The host's tree stays stacked and
``[in, out]`` (``_init_params``: a checkpoint and the benchmark's reference
hold that form) and is split where it is placed (``place_params``).

Tokens are sampled from head 0 only, one byte a wave; the other
``num_pred_heads - 1`` heads' logits are computed (the head keeps its
published width) and compared with the reference in tests, not served.
"""

from __future__ import annotations

import math

import numpy as np

from client_tpu.models import register_model
from client_tpu.models.decoder import DecoderBackend, sample_into_slots
# (``rope`` by this name too: benchmark/testdata's controls import it here.)
from client_tpu.models.layers import rms_norm, rope

_NEG_INF = -1e30
# The matrices served ``[out, in]`` and contracted on their minor axis
# (``_mm_t``): with a wave's 16 rows on the other side the compiler wants the
# products that go through ``rope`` that way round, and a piece's 2048 rows
# want ``wv`` so too.
OUT_IN = ("wq", "wk", "wv")


def summarize(k, v, phi, mu, chunk):
    """Chunk summaries of whole windows.  k, v ``[..., n, H, D]`` (the values
    the cache holds, as float32; n a multiple of ``chunk``), phi, mu
    ``[H, D]`` -> (k~, v~) ``[..., n / chunk, H, D]`` float32."""
    import jax
    import jax.numpy as jnp

    *lead, n, h, d = k.shape
    kc = k.reshape(*lead, n // chunk, chunk, h, d)
    vc = v.reshape(*lead, n // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("...chd,hd->...ch", kc, phi), axis=-2)
    v_s = jnp.einsum("...ch,...chd->...hd", a, vc)
    k_s = kc.mean(axis=-3) + mu
    return k_s, v_s


class EvaByteBackend(DecoderBackend):
    """Byte-level decoder (``models/decoder.py`` for what it is served
    through)."""

    def __init__(self, name: str = "evabyte", n_layers: int = 2,
                 d_model: int = 64, n_heads: int = 4, d_ff: int = 128,
                 vocab: int = 320, n_pred_heads: int = 8,
                 max_seq_len: int = 128, window: int = 32, chunk: int = 4,
                 rope_theta: float = 100000.0, rms_eps: float = 1e-5,
                 max_streams: int = 4, seed: int = 0,
                 prefill_lanes: int = 1, attention_impl: str = "einsum",
                 attn_impl: str | None = None):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        if d_model % n_heads or window % chunk or max_seq_len % window:
            raise ValueError(
                "d_model must divide into heads, the window into chunks and "
                "max_seq_len into windows")
        if (window // chunk) % 8:
            raise ValueError(
                "a window's summaries (window / chunk) must be a multiple "
                "of 8 rows")
        self.flash_blocks = (512, 1024)
        self.n_layers, self.d_model = int(n_layers), int(d_model)
        self.n_heads, self.d_ff = int(n_heads), int(d_ff)
        self.head_dim = self.d_model // self.n_heads
        self.n_pred_heads = int(n_pred_heads)
        self.window, self.chunk = int(window), int(chunk)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self._seed = seed
        # Summaries a window leaves, and the rows of a slot: the summaries
        # of every window but the last, then one window of exact rows,
        # rounded up so that kernel blocks divide it.
        self.sums_per_window = self.window // self.chunk
        used = ((self.max_seq_len // self.window - 1) * self.sums_per_window
                + self.window)
        step = min(512, self.window)
        self.slot_rows = -(-used // step) * step
        # What the scheduler asks (engine/generative.py): a prompt is
        # consumed ``window`` positions a piece, ``prefill_lanes`` prompts
        # a call.  The piece program is this file's own and runs its head
        # (320 ids) in every piece: it takes no ``ends``.
        self.prefill_piece = (self.window, int(prefill_lanes))
        self.piece_ends = False

    # -- what the scheduler asks of a cache that is not slot-per-position --

    def cache_rows(self, n: int) -> tuple[int, int]:
        """(summary rows, exact rows) a decode step at context length ``n``
        reads from its slot."""
        return (n // self.window) * self.sums_per_window, n % self.window

    def transition_due(self, n: int) -> bool:
        """Whether a stream that *decoded* its way to context length ``n``
        has to dump its window before its next step (a prompt that ends on
        a boundary was summarised by its last piece)."""
        return n > 0 and n % self.window == 0

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights, **already rounded to bfloat16** (numpy arrays of
        ``ml_dtypes.bfloat16``): a reference that casts them to float32
        holds exactly what the chip holds.  Layers are stacked on a leading
        axis: the form a checkpoint holds and ``benchmark/reference.py``
        reads; the programs take ``split_layers`` of it."""
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        rng = np.random.default_rng(self._seed)
        d, f, nl = self.d_model, self.d_ff, self.n_layers
        h, dh = self.n_heads, self.head_dim

        def w(*shape, scale):
            out = rng.standard_normal(shape, dtype=np.float32)
            out *= np.float32(scale)
            return out.astype(bf16)

        def mats(rows, cols):
            return np.stack([w(rows, cols, scale=1.0 / math.sqrt(rows))
                             for _ in range(nl)])

        layers = {
            "ln1": w(nl, d, scale=0.1), "ln2": w(nl, d, scale=0.1),
            "wq": mats(d, d), "wk": mats(d, d), "wv": mats(d, d),
            "wo": mats(d, d),
            "wg": mats(d, f), "wu": mats(d, f), "wd": mats(f, d),
            # adaptive_phi scores a chunk's keys (unit variance at random
            # init), adaptive_mu_k shifts its pooled key.
            "phi": w(nl, h, dh, scale=1.0 / math.sqrt(dh)),
            "mu": w(nl, h, dh, scale=0.5),
        }
        return {
            "embed": w(self.vocab, d, scale=1.0),
            "layers": layers,
            "lnf": w(d, scale=0.1),
            "head": w(d, self.n_pred_heads * self.vocab,
                      scale=1.0 / math.sqrt(d)),
        }

    def split_layers(self, params):
        """The tree the programs take, of the stacked one: each layer's
        weights as leaves of their own, ``params["layers"][li][name]``,
        those of ``OUT_IN`` turned ``[out, in]`` (on the host views of the
        stacked arrays: nothing is copied)."""
        layers = params["layers"]
        return {**params, "layers": [
            {name: leaf[li].T if name in OUT_IN else leaf[li]
             for name, leaf in layers.items()}
            for li in range(self.n_layers)]}

    def place_params(self, params):
        """Split on the host and placed leaf by leaf, so that the stacked
        tree never stands on the device beside the served one."""
        import jax

        def put(leaf):
            if leaf.flags.c_contiguous:
                return jax.device_put(leaf)
            # A turned view goes over as it lies and the device turns it:
            # numpy copies one element by element, 0.13 s a matrix at the
            # published widths, 3 s of set-up a model.
            return jax.device_put(leaf.T).T

        return jax.tree_util.tree_map(
            put, self.split_layers(jax.tree_util.tree_map(np.asarray,
                                                          params)))

    # -- shared blocks --------------------------------------------------------

    def _mm(self, x, w):
        """bfloat16 operands, float32 result (``fp32_skip_add``: the
        residual stream the result is added to stays float32)."""
        import jax.numpy as jnp

        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def _mm_t(self, x, w):
        """``_mm`` by a matrix served ``[out, in]`` (``OUT_IN``): the same
        products and sums, contracted on both minor axes."""
        import jax
        import jax.numpy as jnp

        return jax.lax.dot_general(
            x.astype(w.dtype), w, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _qkv(self, lp, x, pos):
        """A wave's: B sequences of the one position ``pos[b]``."""
        q, k, v = self._qkv_rows(lp, x[:, None], pos[:, None])
        return q[:, 0], k[:, 0], v[:, 0]

    def _qkv_rows(self, lp, x, pos):
        """x ``[..., n, d]`` float32 -> q, k (RoPE applied), v ``[..., n,
        H, D]`` float32."""
        h = rms_norm(x, lp["ln1"], self.rms_eps, unit_offset=True)
        shape = (*x.shape[:-1], self.n_heads, self.head_dim)
        q = rope(self._mm_t(h, lp["wq"]).reshape(shape), pos,
                 self.rope_theta)
        k = rope(self._mm_t(h, lp["wk"]).reshape(shape), pos,
                 self.rope_theta)
        return q, k, self._mm_t(h, lp["wv"]).reshape(shape)

    def _after_attention(self, lp, x, o, fence=None):
        """``fence``: applied to (x, h) between the attention's output
        projection and the feed-forward (a piece's; see
        ``piece_logits_fn``)."""
        import jax

        x = x + self._mm(o.reshape(x.shape), lp["wo"])
        h = rms_norm(x, lp["ln2"], self.rms_eps, unit_offset=True)
        if fence is not None:
            x, h = fence((x, h))
        return x + self._mm(
            jax.nn.silu(self._mm(h, lp["wg"])) * self._mm(h, lp["wu"]),
            lp["wd"])

    def _logits(self, p, x):
        """float32 logits of all heads, ``[..., n_pred_heads, vocab]``."""
        out = self._mm(rms_norm(x, p["lnf"], self.rms_eps, unit_offset=True),
                       p["head"])
        return out.reshape(*x.shape[:-1], self.n_pred_heads, self.vocab)

    def _served(self, logits):
        return logits[:, 0]

    def _summaries(self, lp, k_c, v_c):
        """Summaries of whole windows of cache rows ``[..., n, H, D]``
        (bfloat16, what the cache holds), in the cache's dtype."""
        import jax.numpy as jnp

        k_s, v_s = summarize(k_c.astype(jnp.float32),
                             v_c.astype(jnp.float32),
                             lp["phi"].astype(jnp.float32),
                             lp["mu"].astype(jnp.float32), self.chunk)
        return k_s.astype(k_c.dtype), v_s.astype(v_c.dtype)

    def _embed(self, p, tokens, pos):
        import jax.numpy as jnp

        return p["embed"][tokens].astype(jnp.float32)   # positions: RoPE

    def _live_rows(self, lens):
        win = self.window
        return (lens // win) * self.sums_per_window + lens % win

    # -- full-context forward (no cache) ----------------------------------------

    def make_apply_params(self):
        """Full-context forward in the served precision, no cache and no
        pieces: logits of every position and head.  Model-level entry for
        warm-up and diagnostics; serving goes through the pieces and waves
        below."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            import jax
            import jax.numpy as jnp

            ids = inputs["INPUT_IDS"].astype("int32")
            n = ids.shape[0]
            win, c = self.window, self.chunk
            pos = jnp.arange(n)
            n_pad = -(-n // win) * win          # whole windows for pooling
            tok_ok = ((pos[None, :] // win == pos[:, None] // win)
                      & (pos[None, :] <= pos[:, None]))
            ch_ok = (jnp.arange(n_pad // c)[None, :] * c // win
                     < pos[:, None] // win)
            scale = 1.0 / math.sqrt(self.head_dim)

            def body(x, lp, _li):
                q, k, v = self._qkv_rows(lp, x, pos)
                k_c, v_c = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
                pad = ((0, n_pad - n), (0, 0), (0, 0))
                k_s, v_s = self._summaries(lp, jnp.pad(k_c, pad),
                                           jnp.pad(v_c, pad))
                keys = jnp.concatenate([k_s, k_c]).astype(jnp.float32)
                vals = jnp.concatenate([v_s, v_c]).astype(jnp.float32)
                s = jnp.einsum("qhd,khd->hqk", q, keys) * scale
                ok = jnp.concatenate([ch_ok, tok_ok], axis=1)
                s = jnp.where(ok[None], s, _NEG_INF)
                o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vals)
                return self._after_attention(lp, x, o)

            x = self._walk_layers(p, body,
                                  p["embed"][ids].astype(jnp.float32))
            return {"logits": self._logits(p, x)}

        return apply, params

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """Cache arena: k/v ``[L, capacity + 1, slot_rows, H*D]`` bfloat16
        (the +1 slot absorbs padded lanes) plus ``tok`` [R], each slot's
        latest token on the device.  A slot's rows are summaries then the
        current window (module docstring), not positions."""
        import jax.numpy as jnp

        shape = (self.n_layers, capacity + 1, self.slot_rows, self.d_model)
        return {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16),
                "tok": jnp.zeros(capacity + 1, jnp.int32)}

    def _piece_attention(self, q, k_c, v_c, pk, pv, n_sum):
        """One piece's attention: q ``[B, W, H, D]`` float32, its own keys
        and values k_c/v_c (bfloat16) seen causally, and the slot's first
        ``P`` rows pk/pv ``[B, P, H, D]`` of which the ``n_sum[b]``
        summaries are seen by every query."""
        import jax
        import jax.numpy as jnp

        b, w = q.shape[:2]
        pre = pk.shape[1]
        idx = jnp.arange(pre + w)
        seen = (idx[None, :] < n_sum[:, None]) | (idx[None, :] >= pre)
        if self.attention_impl == "flash":
            from client_tpu.engine.backend_init import pallas_interpret
            from client_tpu.ops.decode_kernel import pick_block_s
            from client_tpu.ops.flash_attention import flash_attention

            def dense(t):
                # The kernel's layout is a cache row's: [B, n, H*D].
                return t.reshape(b, t.shape[1], self.d_model)

            cap_q, cap_k = self.flash_blocks
            return flash_attention(
                dense(q).astype(k_c.dtype),
                jnp.concatenate([dense(pk), dense(k_c)], axis=1),
                jnp.concatenate([dense(pv), dense(v_c)], axis=1),
                jnp.where(seen, 0.0, _NEG_INF).astype(jnp.float32),
                causal=True, prefix=pre, n_heads=self.n_heads,
                block_q=pick_block_s(w, cap_q),
                block_k=pick_block_s(pre + w, cap_k),
                interpret=pallas_interpret()
            ).astype(jnp.float32).reshape(q.shape)
        keys = jnp.concatenate([pk, k_c], axis=1)
        vals = jnp.concatenate([pv, v_c], axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, keys.astype(jnp.float32))
        s = s / math.sqrt(self.head_dim)
        causal = (idx[None, :] - pre) <= jnp.arange(w)[:, None]   # [W, P+W]
        ok = seen[:, None, :] & causal[None]
        s = jnp.where(ok[:, None], s, _NEG_INF)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                          vals.astype(jnp.float32))

    def piece_logits_fn(self):
        """(params, arena, rows[B], ids[B, W], lens[B], starts[B]) ->
        (arena, logits[B, n_pred_heads, vocab] after each lane's last valid
        position).  One prefill piece: positions ``starts[b] ..
        starts[b] + lens[b]`` of each lane's prompt (``starts`` a multiple
        of the window)."""
        import jax
        import jax.numpy as jnp

        win, spw, hd = self.window, self.sums_per_window, self.d_model
        pre = self.slot_rows - win

        def piece(p, arena, rows, ids, lens, starts):
            b = rows.shape[0]
            pos = starts[:, None] + jnp.arange(win)[None, :]
            n_sum = (starts // win) * spw
            full = lens == win

            # Three fences a layer keep the unrolled piece at the time it
            # had as a ``scan``'s body.  With eight layers in sight the
            # compiler's memory-space assignment gives the fast memory to
            # prefetched weights (of no use to products of 2048 rows, which
            # the MXU bounds) where the loop's body kept activations there:
            # 55.65 ms a piece with none, 54.2 / 54.3 / 53.0 with one,
            # 50.7 with all three against the scan's 51.03 (PERF.md section
            # 6, PR 42).  A wave, which the weights' reads bound, is better
            # off without them (11.80 ms against 11.83).
            fence = jax.lax.optimization_barrier

            def body(carry, lp, li):
                x, k_a, v_a = fence(carry)
                q, k, v = self._qkv_rows(lp, x, pos)
                k_c, v_c = k.astype(k_a.dtype), v.astype(v_a.dtype)
                shape = (b, pre, self.n_heads, self.head_dim)

                def head_rows(leaf):
                    return jnp.stack([jax.lax.dynamic_slice(
                        leaf, (li, rows[i], 0, 0), (1, 1, pre, hd))
                        for i in range(b)]).reshape(shape)

                o = self._piece_attention(q, k_c, v_c, head_rows(k_a),
                                          head_rows(v_a), n_sum)
                x, o, k_c, v_c = fence((x, o, k_c, v_c))
                # A full piece leaves its summaries, a partial one (the
                # prompt's last) its exact rows; what lies behind either is
                # beyond ``live`` and never read.
                k_s, v_s = self._summaries(lp, k_c, v_c)
                tail = ((0, 0), (0, win - spw), (0, 0))

                def put(leaf, exact, sums):
                    blk = jnp.where(full[:, None, None],
                                    jnp.pad(sums.reshape(b, spw, hd), tail),
                                    exact.reshape(b, win, hd))
                    for i in range(b):
                        leaf = jax.lax.dynamic_update_slice(
                            leaf, blk[i][None, None],
                            (li, rows[i], n_sum[i], 0))
                    return leaf

                return (self._after_attention(lp, x, o, fence),
                        put(k_a, k_c, k_s), put(v_a, v_c, v_s))

            x, k_a, v_a = self._walk_layers(
                p, body, (p["embed"][ids].astype(jnp.float32),
                          arena["k"], arena["v"]))
            logits = self._logits(p, x[jnp.arange(b), lens - 1])
            return {**arena, "k": k_a, "v": v_a}, logits

        return piece

    def prefill_fn(self):
        """(params, arena, rows[B], ids[B, W], lens[B], seeds[B], temps[B],
        top_ks[B], top_ps[B], sample, starts[B]) -> (arena, tokens[B]).

        One **piece** of each lane's prompt (``piece_logits_fn``); the token
        sampled from head 0 after a lane's last valid position lands in
        the slot's device-side token, and means something for a prompt's
        last piece only."""
        piece = self.piece_logits_fn()

        def prefill(p, arena, rows, ids, lens, seeds, temps, top_ks, top_ps,
                    sample, starts):
            arena, logits = piece(p, arena, rows, ids, lens, starts)
            return sample_into_slots(arena, rows, self._served(logits),
                                     seeds, starts + lens, temps, top_ks,
                                     top_ps, sample)

        return prefill

    def transition_fn(self):
        """(params, arena, rows[T], lens[T]) -> arena: **dump** the full
        window of each lane whose context length ``lens`` is a positive
        multiple of the window: its W / c summaries overwrite the head of
        its own rows.  Lanes on the dummy slot summarise junk into junk."""
        import jax
        import jax.numpy as jnp

        win, spw, hd = self.window, self.sums_per_window, self.d_model
        nl = self.n_layers

        def transition(p, arena, rows, lens):
            src = jnp.maximum(lens // win - 1, 0) * spw
            # All layers at once: the two small leaves stacked in the
            # program (2 x L x H x D values).
            phi, mu = (jnp.stack([lp[name] for lp in p["layers"]])
                       for name in ("phi", "mu"))
            k_a, v_a = arena["k"], arena["v"]
            for i in range(rows.shape[0]):
                def take(leaf):
                    return jax.lax.dynamic_slice(
                        leaf, (0, rows[i], src[i], 0),
                        (nl, 1, win, hd)).reshape(
                            nl, win, self.n_heads, self.head_dim)

                k_s, v_s = jax.vmap(
                    lambda phi, mu, k, v: self._summaries(
                        {"phi": phi, "mu": mu}, k, v))(
                            phi, mu, take(k_a), take(v_a))
                k_a = jax.lax.dynamic_update_slice(
                    k_a, k_s.reshape(nl, 1, spw, hd), (0, rows[i], src[i], 0))
                v_a = jax.lax.dynamic_update_slice(
                    v_a, v_s.reshape(nl, 1, spw, hd), (0, rows[i], src[i], 0))
            return {**arena, "k": k_a, "v": v_a}

        return transition


# Opt-in (a default load-all server should not pay its arena); the tiny
# preset is what the tier-1 tests serve.
register_model("evabyte", default=False)(EvaByteBackend)
