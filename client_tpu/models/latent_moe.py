"""What the decoders with a latent cache and sparse experts share: one copy.

``models/pangu_moe.py`` (latent attention in every layer, rotary positions, a
low-rank query) and ``models/kimi_linear.py`` (latent attention without
positions in one layer of four, a recurrent state in the others) both serve
one chip's share of an expert-parallel group through ``models/decoder.py``.
What does not differ between them lives here, in :class:`LatentMoeDecoder`:

- weights made when asked for (:class:`SeededWeight`) and put on the device a
  leaf at a time;
- **the latent cache**: one leaf ``c`` of ``[layers that read rows, R, S,
  W]``, a position's row ``[c | k_r | 0]`` (``latent_row_width``: 512 + 64 ->
  640 lanes), what every head reads.  **Decode absorbs** the up-projection:
  ``q_lat = q_nope W_kb^T`` per head, scores ``[q_lat | q_r] . row``, ``o_lat =
  p c``, ``o_h = o_lat W_vb`` (``latent_attention`` of the decoder's contract;
  the kernel is ops/decode_kernel.py ``latent_wave_attention``, which takes
  the query and leaves ``o_lat`` with the heads along the minor axis: the two
  einsums write and read that layout).  **Prefill does not**: a piece of
  ``piece`` positions computes ``k_nope`` and ``v`` of its own rows and, from
  the cache, of the rows before it, and attends with the flash kernel (per
  head q and k ``nope + rope`` wide, padded to whole tiles, v ``v_dim``).
  Piece i of a prompt has exactly ``i * piece`` rows before it, so a latent
  layer holds one branch a count (``lax.switch``) and computes nothing that is
  masked; the switch is the layer's, not the model's;
- **the expert layer's share**: the backend holds ``experts_held`` of the
  routed experts, ``first_expert ..``.  The router keeps its width and its
  ``top_k`` (``s = sigmoid(x W_g)`` in float32, the ``top_k`` largest of ``s``,
  or of ``s + b`` where the gate has a selection bias; weights ``s_i / sum s_i
  * routed_scale``); the layer computes ``shared(x)`` and the terms of the
  chosen experts it holds; what the absent experts would add is left out, and
  that partial result goes on.  Nothing stands in for the other chips or their
  exchange.  The (token, expert) pairs held here are sorted by expert and
  multiplied in groups (ops/grouped_matmul.py): no pair is dropped, and an
  expert no token chose is not read;
- the wave's carry (activations, routing counts, choices, live lanes) and the
  three counters behind a wave's tokens (``wave_stats``: pairs held here, the
  busiest held expert's, held experts touched, each summed over the expert
  layers; padded lanes route nowhere);
- **a stream's record** (``stream_record`` of the decoder's contract, for a
  model that declares ``record_width(expert layers)`` ints a position; off by
  default): behind a program's tokens, for every position it consumed, one
  int32 an expert layer whose bit ``e`` says that held expert ``first_expert
  + e`` was among the position's ``top_k`` (``held_mask``: the one thing about
  a routing that a share's output depends on discontinuously), then the
  float32 bits of ``1 + RECORD_LOGITS`` logits of the row the position's
  token was chosen from: the token's own and those of the first ids (0 for a
  prompt position that emitted nothing).  It costs a program nothing but the
  words it returns, and lets a comparison follow the served routing instead
  of guessing which near-ties fell the other way, and weigh the precision on
  values instead of on which token won.

A model supplies ``_queries_and_rows`` (its query path and what it does to
positions), ``_after_rows`` (its norms around ``_ffn``), its weights and its
piece.
"""

from __future__ import annotations

import concurrent.futures
import math
import os

import numpy as np

from client_tpu.models.decoder import DecoderBackend, sample_into_slots

_NEG_INF = -1e30
_CHUNK = 1 << 24          # elements of a weight made by one task
_BLOCK = 1 << 17          # elements made at a time (cache-sized)
# Rows of a grouped matmul's tile: a wave's groups are a few rows (16 is
# bfloat16's sublane tile), a prefill piece's some dozens.
TILE_M_WAVE, TILE_M_PIECE = 16, 64
# Logits of a row's first ids in a stream's record, beside its token's.
RECORD_LOGITS = 8


def record_width(expert_layers: int) -> int:
    """int32 a position of a stream's record."""
    return expert_layers + 1 + RECORD_LOGITS


def rms_norm(x, g, eps):
    """``x / rms(x) * g`` in float32."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jnp.reciprocal(jnp.sqrt(var + eps)) * g.astype(jnp.float32)


class SeededWeight:
    """A weight that is made when it is asked for: ``offset + scale * N(0,
    1)`` from its own seed, **rounded to bfloat16** whatever dtype it is asked
    in, so a reference that asks for float32 (``np.asarray(w, np.float32)``)
    holds exactly what the chip holds and never a second copy.  Chunks of
    ``_CHUNK`` elements have seeds of their own and are filled by as many
    threads as the process may use (numpy's generators release the
    interpreter lock): the values do not depend on the thread count.  With
    ``first`` given, entry i of the leading axis is made from ``first + i``
    alone: the experts a share holds are the model's, whichever share holds
    them."""

    def __init__(self, seed, shape, scale, offset=0.0, dtype="bfloat16",
                 first=None):
        self.seed, self.shape = tuple(int(s) for s in seed), tuple(shape)
        self.scale, self.offset = float(scale), float(offset)
        self.dtype = str(dtype)          # "bfloat16" | "float32"
        self.first = first

    def _spans(self):
        """(lo, hi, seed) of every chunk of the flattened weight."""
        n = int(np.prod(self.shape))
        unit = n if self.first is None else n // self.shape[0]
        return [(u + lo, u + min(lo + _CHUNK, unit),
                 [*self.seed, lo // _CHUNK] + (
                     [] if self.first is None else [self.first + u // unit]))
                for u in range(0, n, unit) for lo in range(0, unit, _CHUNK)]

    def _fill(self, out, lo, hi, seed):
        """Chunk ``[lo, hi)`` of the flattened weight into ``out`` (float32,
        or uint16 holding bfloat16's bits), a block at a time and in place:
        whole-chunk temporaries would be mapped and unmapped by every thread
        at once, which the kernel serializes."""
        rng = np.random.default_rng(seed)
        wide = out.dtype == np.float32
        scratch = None if wide else np.empty(_BLOCK, np.float32)
        carry = np.empty(_BLOCK, np.uint32)
        for a in range(lo, hi, _BLOCK):
            b = min(a + _BLOCK, hi)
            part = out[a:b] if wide else scratch[:b - a]
            rng.standard_normal(b - a, dtype=np.float32, out=part)
            part *= np.float32(self.scale)
            if self.offset:
                part += np.float32(self.offset)
            bits, t = part.view(np.uint32), carry[:b - a]
            np.right_shift(bits, 16, out=t)      # round to nearest even
            t &= np.uint32(1)
            t += np.uint32(0x7FFF)
            bits += t
            if wide:
                bits &= np.uint32(0xFFFF0000)
            else:
                np.right_shift(bits, 16, out=t)
                out[a:b] = t

    def __array__(self, dtype=None, copy=None):
        import ml_dtypes

        wide = self.dtype == "float32" or (
            dtype is not None and np.dtype(dtype) == np.float32)
        out = np.empty(int(np.prod(self.shape)),
                       np.float32 if wide else np.uint16)
        spans = self._spans()
        workers = max(1, min(len(spans), len(os.sched_getaffinity(0))))
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            list(pool.map(lambda s: self._fill(out, *s), spans))
        out = out.reshape(self.shape)
        return out if wide else out.view(ml_dtypes.bfloat16)


class LatentMoeDecoder(DecoderBackend):
    """The shared parts above.  A model sets, before ``_latent_setup()``:
    ``n_heads, kv_rank, nope_dim, rope_dim, v_dim, d_model, d_expert,
    n_experts, experts_held, first_expert, top_k, n_shared, routed_scale,
    rms_eps, piece, dtype, _seed`` (``dtype="float32"`` makes weights, cache
    and matmuls float32, the tests' exact-routing comparison; the served form
    is bfloat16)."""

    cache_leaves = ("c",)
    wave_stats = ("expert_pairs_local", "expert_pairs_busiest",
                  "experts_touched")

    def _latent_setup(self):
        from client_tpu.ops.decode_kernel import latent_row_width

        if self.max_seq_len % self.piece or self.piece % 8:
            raise ValueError("max_seq_len must divide into prefill pieces "
                             "of a multiple of 8 positions")
        if (self.first_expert + self.experts_held > self.n_experts
                or self.top_k > self.n_experts):
            raise ValueError(
                f"experts {self.first_expert}.."
                f"{self.first_expert + self.experts_held} and "
                f"top {self.top_k} do not fit a router of {self.n_experts}")
        if self.stream_record and self.experts_held > 32:
            raise ValueError("a stream's record holds a layer's held experts "
                             f"in 32 bits; {self.experts_held} are held")
        self.row_width = latent_row_width(self.kv_rank, self.rope_dim)
        # Per head q and k are nope + rope wide; the flash kernel takes them
        # in whole 128-lane tiles (192 -> 256; narrower models as they are).
        qk = self.nope_dim + self.rope_dim
        self.qk_pad = -(-qk // 128) * 128 if qk > 128 else qk
        self.sm_scale = 1.0 / math.sqrt(qk)
        self.latent_attention = self.kv_rank
        self.prefill_piece = (self.piece, 1)

    # -- params --------------------------------------------------------------

    def _weight_makers(self):
        """``w(*shape, scale, ...)``, ``mat(rows, cols)`` and ``gain(n)``:
        ``SeededWeight`` leaves numbered in the order they are asked for.  A
        float32 model's weights are still rounded to bfloat16 values: the
        same numbers in both forms of the program."""
        count = iter(range(1 << 20))

        def w(*shape, scale, offset=0.0, dtype=None, first=None):
            return SeededWeight((self._seed, next(count)), shape, scale,
                                offset, dtype or self.dtype, first)

        def mat(rows, cols):
            return w(rows, cols, scale=1.0 / math.sqrt(rows))

        def gain(n):
            return w(n, scale=0.1, offset=1.0)

        return w, mat, gain

    def _expert_weights(self, w, mat):
        """An expert layer's: the router (float32), the shared expert and the
        held experts' stacked ``egu [E, d, 2f]`` (gate | up) and ``ed [E, f,
        d]``."""
        d = self.d_model
        f, fs = self.d_expert, self.d_expert * self.n_shared
        e = self.experts_held
        return {
            "router": w(d, self.n_experts, scale=1.0 / math.sqrt(d),
                        dtype="float32"),
            "sgu": mat(d, 2 * fs), "sd": mat(fs, d),
            "egu": w(e, d, 2 * f, scale=1.0 / math.sqrt(d),
                     first=self.first_expert),
            "ed": w(e, f, d, scale=1.0 / math.sqrt(f),
                    first=self.first_expert)}

    def place_params(self, params):
        """Leaf by leaf: a weight is made, put on the device and let go, so
        the host never holds the model."""
        import jax

        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(np.asarray(leaf)), params)

    # -- shared blocks --------------------------------------------------------

    def _mm(self, x, w):
        """Operands in the weights' dtype, float32 result."""
        import jax.numpy as jnp

        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def _heads_mm(self, eq, x, w):
        """A product a head (a batched matmul): operands in the weights'
        dtype, float32 sums.  XLA's CPU backend has no batched bfloat16 dot:
        where Pallas is interpreted the operands are widened, which changes
        no product (a bfloat16 pair's is exact in float32)."""
        import jax.numpy as jnp

        from client_tpu.engine.backend_init import pallas_interpret

        dtype = jnp.float32 if pallas_interpret() else w.dtype
        return jnp.einsum(eq, x.astype(w.dtype).astype(dtype),
                          w.astype(dtype),
                          preferred_element_type=jnp.float32)

    def _cache_rows_of(self, c, k_r, dtype):
        """``[c | k_r | 0]``: the rows the cache holds, in its dtype."""
        import jax.numpy as jnp

        pad = self.row_width - self.kv_rank - self.rope_dim
        return jnp.concatenate(
            [c, k_r, jnp.zeros((*c.shape[:-1], pad), c.dtype)],
            axis=-1).astype(dtype)

    def _qkv(self, lp, x, pos):
        """A wave's absorbed query and new row: ``q [B, W, H]``, column h
        ``[q_nope W_kb^T | q_rope | 0] * sm_scale`` (scaled in float32, then
        rounded to the cache's dtype: what the kernel multiplies), and the
        row ``[B, W]``."""
        import jax.numpy as jnp

        # The wave's lanes stand where a sequence's positions would.
        q_nope, q_rope, c, k_r = self._queries_and_rows(lp, x["h"], pos)
        q_lat = self._heads_mm("bhn,hnr->brh", q_nope, lp["wkb"])
        pad = self.row_width - self.kv_rank - self.rope_dim
        q = jnp.concatenate(
            [q_lat, q_rope.swapaxes(1, 2),
             jnp.zeros((q_lat.shape[0], pad, self.n_heads), jnp.float32)],
            axis=1)
        return ((q * self.sm_scale).astype(jnp.dtype(self.dtype)),
                self._cache_rows_of(c, k_r, jnp.float32))

    def _attention_output(self, lp, o):
        """``o_lat [B, kv_rank, H]`` -> ``concat_h(o_lat W_vb) [B, H *
        v_dim]``."""
        return self._heads_mm("brh,hrv->bhv", o, lp["wvb"]).reshape(
            o.shape[0], self.n_heads * self.v_dim)

    def _keys_values(self, lp, c):
        """Cache values c ``[n, kv_rank]`` -> k_nope ``[n, H, nope]``, v
        ``[n, H, v_dim]`` float32: the up-projection prefill does not
        absorb."""
        import jax.numpy as jnp

        c = c.astype(lp["wkb"].dtype)
        return (jnp.einsum("sr,hnr->shn", c, lp["wkb"],
                           preferred_element_type=jnp.float32),
                jnp.einsum("sr,hrv->shv", c, lp["wvb"],
                           preferred_element_type=jnp.float32))

    def _swiglu(self, h, wgu, wd):
        import jax

        gu = self._mm(h, wgu)
        f = gu.shape[-1] // 2
        return self._mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], wd)

    def route(self, lp, h):
        """The router: h ``[n, d]`` float32 (normed) -> (experts ``[n, k]``,
        weights ``[n, k]`` float32); float32 at full precision whatever the
        matmuls'.  A gate with a selection bias (``router_bias``) chooses by
        ``s + b`` and weighs by ``s``."""
        import jax
        import jax.numpy as jnp

        s = jax.nn.sigmoid(jnp.matmul(
            h, lp["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        if "router_bias" in lp:
            _, top_i = jax.lax.top_k(s + lp["router_bias"], self.top_k)
            top_s = jnp.take_along_axis(s, top_i, axis=-1)
        else:
            # No bias: the scores are ``top_k``'s own values.  A zero bias
            # through the branch above would be one path, but it gives
            # models/pangu_moe.py another program than the recorded one
            # (an add and a gather more; tests/test_served_programs.py).
            top_s, top_i = jax.lax.top_k(s, self.top_k)
        weights = top_s / top_s.sum(-1, keepdims=True) * self.routed_scale
        return top_i, weights

    def _experts(self, lp, h, live, tile_m):
        """The held experts' part of the layer for tokens h ``[n, d]``:
        ``sum_i w_i E_i(h)`` over the chosen experts held here, (pairs here,
        the busiest expert's, experts touched), and every token's choices
        ``[n, k]``."""
        import jax
        import jax.numpy as jnp

        from client_tpu.engine.backend_init import pallas_interpret
        from client_tpu.ops.grouped_matmul import (capacity_rows,
                                                   grouped_matmul,
                                                   plan_groups,
                                                   reference_grouped_matmul)

        n, held, k = h.shape[0], self.experts_held, self.top_k
        top_i, weights = self.route(lp, h)
        here = ((top_i >= self.first_expert)
                & (top_i < self.first_expert + held) & live[:, None])
        expert = jnp.where(here, top_i - self.first_expert, held).reshape(-1)
        rows = capacity_rows(n * min(k, held), held, tile_m)
        plan = plan_groups(expert.astype(jnp.int32), held, tile_m, rows)
        # The sorted layout by gather: row r holds the token of the pair
        # that goes there, a zero row where none does.
        token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        src = jnp.full(rows + 1, n, jnp.int32).at[plan["dest"]].set(
            token)[:rows]
        wdt = lp["egu"].dtype
        xs = jnp.concatenate([h.astype(wdt), jnp.zeros((1, h.shape[1]), wdt)
                              ])[src]
        if self._use_kernel():
            def gmm(x, w):
                return grouped_matmul(x, w, plan["tile_expert"],
                                      plan["n_tiles"], tile_m=tile_m,
                                      interpret=pallas_interpret())
        else:
            def gmm(x, w):
                return reference_grouped_matmul(x, w, plan["padded"])
        gu = gmm(xs, lp["egu"])
        f = gu.shape[-1] // 2
        ys = gmm((jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(wdt), lp["ed"])
        # Back to tokens: a pair's row by ``dest``; rows no pair points at
        # (the kernel leaves those behind the last tile unwritten) are
        # never read.
        dest = plan["dest"].reshape(n, k)
        got = dest < rows
        picked = ys[jnp.where(got, dest, 0)]                  # [n, k, d]
        y = jnp.sum(jnp.where(got[..., None], picked, 0.0)
                    * weights[..., None], axis=1)
        sizes = plan["sizes"]
        counts = jnp.stack([sizes.sum(), sizes.max(),
                            (sizes > 0).sum()]).astype(jnp.int32)
        return y, counts, top_i

    def held_mask(self, top_i):
        """Choices ``[..., k]`` -> int32 ``[...]``: bit ``e`` set where held
        expert ``first_expert + e`` is among them (a ``top_k``'s choices are
        distinct, so the sum is the union)."""
        import jax
        import jax.numpy as jnp

        e = top_i - self.first_expert
        bits = jnp.where((e >= 0) & (e < self.experts_held),
                         jnp.left_shift(jnp.uint32(1),
                                        jnp.clip(e, 0, 31).astype(jnp.uint32)),
                         jnp.uint32(0))
        return jax.lax.bitcast_convert_type(
            bits.sum(axis=-1, dtype=jnp.uint32), jnp.int32)

    def _ffn(self, lp, f, live, tile_m):
        """The layer's feed-forward for normed rows f ``[n, d]`` -> (y,
        routing counts, choices): of a dense layer 0 and ``()``, of an expert
        layer its counts and ``(choices [n, k],)``."""
        if "wgu" in lp:
            return self._swiglu(f, lp["wgu"], lp["wd"]), 0, ()
        y, counts, top_i = self._experts(lp, f, live, tile_m)
        return y + self._swiglu(f, lp["sgu"], lp["sd"]), counts, (top_i,)

    # -- the decode step's parts (models/decoder.py) ---------------------------

    def _embed(self, p, tokens, pos):
        """The wave's carry: activations, the routing counts and the
        choices so far (a tuple that grows by a layer's ``[B, k]``; nothing
        a served program returns, so nothing it computes), and which lanes
        hold a stream (a padded lane's length is 0)."""
        import jax.numpy as jnp

        return {"h": p["embed"][tokens].astype(jnp.float32),
                "stats": jnp.zeros(3, jnp.int32), "route": (),
                "live": pos > 0}

    def _after_attention(self, lp, x, o):
        h, stats, route = self._after_rows(lp, x["h"], o, x["live"],
                                           TILE_M_WAVE)
        return {**x, "h": h, "stats": x["stats"] + stats,
                "route": x["route"] + route}

    def _logits(self, p, x):
        h = x["h"] if isinstance(x, dict) else x
        return self._mm(rms_norm(h, p["lnf"], self.rms_eps), p["head"])

    def _wave_stats(self, x):
        return x["stats"]

    def _record(self, x, logits, tokens):
        """A wave's rows of the streams' record ``[B, stream_record]``."""
        import jax.numpy as jnp

        from client_tpu.models.decoder import logit_bits

        return jnp.concatenate(
            [jnp.stack([self.held_mask(r) for r in x["route"]], axis=1),
             logit_bits(logits, tokens, RECORD_LOGITS)], axis=1)

    # -- a prefill piece's latent attention -------------------------------------

    def _piece_attention(self, lp, q_nope, q_rope, own, before, impl=None):
        """One piece's attention, nothing absorbed: its queries against the
        keys and values of the ``before`` rows ahead of it ``[P, W]`` and,
        causally, of its ``own`` rows ``[n, W]`` (both as the cache holds
        them), by ``impl`` (the backend's ``attention_impl`` unless given).
        -> ``[n, H * v_dim]`` float32."""
        import jax
        import jax.numpy as jnp

        n, pre = own.shape[0], before.shape[0]
        rows = jnp.concatenate([before, own]) if pre else own
        c_c = rows[:, :self.kv_rank]
        k_r = rows[:, self.kv_rank:self.kv_rank + self.rope_dim]
        h, dq = self.n_heads, self.qk_pad
        k_nope, v = self._keys_values(lp, c_c)
        v = v.astype(rows.dtype)                              # [P+n, H, v]
        if (impl or self.attention_impl) == "flash":
            from client_tpu.engine.backend_init import pallas_interpret
            from client_tpu.ops.flash_attention import flash_attention

            pad = dq - self.nope_dim - self.rope_dim

            def heads(nope, shared_or_own):
                parts = [nope, shared_or_own]
                if pad:
                    parts.append(jnp.zeros((*nope.shape[:2], pad),
                                           nope.dtype))
                return jnp.concatenate(parts, -1).astype(rows.dtype).reshape(
                    1, nope.shape[0], h * dq)

            k_all = heads(k_nope, jnp.broadcast_to(
                k_r[:, None].astype(jnp.float32),
                (pre + n, h, self.rope_dim)))
            return flash_attention(
                heads(q_nope, q_rope), k_all,
                v.reshape(1, pre + n, h * self.v_dim), causal=True,
                prefix=pre, n_heads=h, sm_scale=self.sm_scale,
                block_q=n, block_k=n, interpret=pallas_interpret()
            )[0].astype(jnp.float32)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_r.astype(jnp.float32))
             ) * self.sm_scale
        seen = (jnp.arange(pre + n)[None, :] - pre) <= jnp.arange(n)[:, None]
        s = jnp.where(seen[None], s, _NEG_INF)
        return jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(s, -1),
            v.astype(jnp.float32)).reshape(n, h * self.v_dim)

    def _piece_latent_layer(self, lp, c_a, li, row, start, x, pos):
        """A latent layer's part of a piece: the piece's queries against the
        slot's ``start`` rows before it and its own (one ``lax.switch``
        branch a count of earlier rows: ``start`` is a multiple of the
        piece), its rows written behind them.  ``li`` is the layer's index
        into ``c_a``.  -> (c_a, o ``[piece, H * v_dim]``)."""
        import jax

        n, w = self.piece, self.row_width
        q_nope, q_rope, c, k_r = self._queries_and_rows(lp, x, pos)
        own = self._cache_rows_of(c, k_r, c_a.dtype)

        def attend(pre):
            before = jax.lax.dynamic_slice(
                c_a, (li, row, 0, 0), (1, 1, pre, w))[0, 0]
            return self._piece_attention(lp, q_nope, q_rope, own, before)

        o = jax.lax.switch(
            start // n,
            [lambda pre=i * n: attend(pre)
             for i in range(self.max_seq_len // n)])
        return jax.lax.dynamic_update_slice(
            c_a, own[None, None], (li, row, start, 0)), o

    def prefill_fn(self):
        """``PREFILL_ARGS`` -> (arena, tokens[1]): one **piece** of the
        lane's prompt; the token sampled after its last valid position lands
        in the slot's device-side token, and means something for a prompt's
        last piece only.  With ``stream_record`` the piece's rows of the
        record follow the token, ``[1 + piece x stream_record]``."""
        piece = self.piece_hidden_fn()

        def prefill(p, arena, rows, ids, lens, seeds, temps, top_ks, top_ps,
                    sample, starts):
            import jax.numpy as jnp

            arena, x, routes = piece(p, arena, rows, ids, lens, starts)
            logits = self._logits(p, x[lens - 1])
            arena, tokens = sample_into_slots(
                arena, rows, logits, seeds, starts + lens, temps, top_ks,
                top_ps, sample)
            if not self.stream_record:
                return arena, tokens
            from client_tpu.models.decoder import logit_bits

            last = jnp.arange(self.piece) == lens[0] - 1
            rec = jnp.concatenate(
                [self.held_mask(routes).T,
                 jnp.where(last[:, None],
                           logit_bits(logits, tokens, RECORD_LOGITS), 0)],
                axis=1)
            return arena, jnp.concatenate([tokens, rec.reshape(-1)])

        return prefill
