"""What the decoders with a latent cache and sparse experts share: one copy.

``models/pangu_moe.py`` (latent attention in every layer, rotary positions, a
low-rank query) and ``models/kimi_linear.py`` (latent attention without
positions in one layer of four, a recurrent state in the others) both serve
one chip's share of an expert-parallel group through ``models/decoder.py``.
What does not differ between them lives here, in :class:`LatentMoeDecoder`:

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
  masked; the switch is the layer's, not the model's.  Of a piece of several
  lanes (models/decoder.py ``piece_hidden_fn``) the projections see every
  lane's rows at once; the switch, the flash call and the rows' write go a
  lane at a time.  **A piece carries a wave** (models/decoder.py
  ``piece_wave``, which both models declare): the decoding lanes' rows stand
  behind the piece's through the projections, and behind the last lane's
  write they take the decode step's path, absorbed, on their own slots
  (``_piece_rows_layer(..., wave)``; ``_absorbed`` is the one copy of what a
  wave's query and new row are, for a wave of its own and for one that
  rides);
- **the expert layer** beside its shared expert (``_ffn``): the router, the
  held experts' grouped matmuls, the lazily made weights, the wave's carry and
  its three counters, the final norm and head, and the words of a stream's
  record are ``models/experts.py``'s
  (:class:`ExpertDecoder`, which a decoder without a latent cache shares:
  ``models/smallthinker.py``); here ``s = sigmoid(x W_g)`` and SwiGLU experts,
  that module's defaults;
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
positions), ``_after_rows`` (its norms around ``_ffn``) and its weights; the
piece program is models/experts.py's frame around ``_piece_rows_layer``.
"""

from __future__ import annotations

import math

from client_tpu.models.decoder import RECORD_LOGITS, logit_bits
from client_tpu.models.experts import ExpertDecoder

_NEG_INF = -1e30


class LatentMoeDecoder(ExpertDecoder):
    """The shared parts above.  A model sets, before ``_latent_setup()``:
    ``n_heads, kv_rank, nope_dim, rope_dim, v_dim, d_model, d_expert,
    n_experts, experts_held, first_expert, top_k, n_shared, routed_scale,
    rms_eps, piece, dtype, _seed`` (``dtype="float32"`` makes weights, cache
    and matmuls float32, the tests' exact-routing comparison; the served form
    is bfloat16)."""

    cache_leaves = ("c",)

    def _latent_setup(self):
        from client_tpu.ops.decode_kernel import latent_row_width

        if self.max_seq_len % self.piece or self.piece % 8:
            raise ValueError("max_seq_len must divide into prefill pieces "
                             "of a multiple of 8 positions")
        self._check_experts()
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

    # -- shared blocks --------------------------------------------------------

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

    def _absorbed(self, lp, q_nope, q_rope, c, k_r):
        """What ``_queries_and_rows`` made of a wave's ``B`` rows -> the
        absorbed query and the new row as the kernel takes them: ``q [B, W,
        H]``, column h ``[q_nope W_kb^T | q_rope | 0] * sm_scale`` (scaled in
        float32, then rounded to the cache's dtype: what the kernel
        multiplies), and the row ``[B, W]``.  The one copy: a wave of its own
        runs it (``_qkv``), and so does the wave that rides in a piece's
        program (``_piece_rows_layer``)."""
        import jax.numpy as jnp

        q_lat = self._heads_mm("bhn,hnr->brh", q_nope, lp["wkb"])
        pad = self.row_width - self.kv_rank - self.rope_dim
        q = jnp.concatenate(
            [q_lat, q_rope.swapaxes(1, 2),
             jnp.zeros((q_lat.shape[0], pad, self.n_heads), jnp.float32)],
            axis=1)
        return ((q * self.sm_scale).astype(jnp.dtype(self.dtype)),
                self._cache_rows_of(c, k_r, jnp.float32))

    def _qkv(self, lp, x, pos):
        """A wave's absorbed query and new row (``_absorbed``); the wave's
        lanes stand where a sequence's positions would."""
        return self._absorbed(lp, *self._queries_and_rows(lp, x["h"], pos))

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

    def _ffn(self, lp, f, live, tile_m):
        """The layer's feed-forward for normed rows f ``[n, d]`` -> (y,
        routing counts, choices): of a dense layer 0 and ``()``, of an expert
        layer its counts and ``(choices [n, k],)``."""
        if "wgu" in lp:
            return self._dense_expert(f, lp["wgu"], lp["wd"]), 0, ()
        y, counts, top_i = self._experts(lp, f, live, tile_m)
        return (y + self._dense_expert(f, lp["sgu"], lp["sd"]), counts,
                (top_i,))

    # -- the decode step's parts (models/decoder.py) ---------------------------

    def _record(self, x, logits, tokens):
        """A wave's rows of the streams' record ``[B, stream_record]``."""
        import jax.numpy as jnp

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

    def _full_rows_layer(self, lp, x, pos):
        """A latent layer over a whole prompt, nothing cached (models/
        experts.py ``make_apply_params``): dense scores."""
        import jax.numpy as jnp

        q_nope, q_rope, c, k_r = self._queries_and_rows(lp, x, pos)
        own = self._cache_rows_of(c, k_r, jnp.dtype(self.dtype))
        return self._piece_attention(lp, q_nope, q_rope, own, own[:0],
                                     impl="einsum")

    def _piece_words(self, routes):
        """A piece's choices ``[expert layers, n, top_k]`` -> its record's
        words ``[n, expert layers]``: one word a layer."""
        return [self.held_mask(routes).T]

    def _piece_rows_layer(self, lp, c_a, li, rows, starts, lens, x, pos,
                          wave=None):
        """A latent layer's part of a piece of ``L`` lanes, x ``[L * piece,
        d]`` (models/decoder.py ``piece_hidden_fn``): the projections over
        every lane's positions at once, then a lane at a time the piece's
        queries against the slot's ``start`` rows before it and its own (one
        ``lax.switch`` branch a count of earlier rows: ``start`` is a
        multiple of the piece), its rows written behind them.  ``li`` is the
        layer's index into ``c_a``.  -> (c_a, o ``[L * piece, H * v_dim]``).

        **With a wave** (models/decoder.py ``piece_wave``; ``wave``: the
        wave's step of the kind, ``_decode_attend``'s one-leaf form, its rows
        ``[B]`` and their live rows): x holds the wave's ``B`` rows behind the
        piece's and the projections run once over all of them (``wqa``,
        ``wqn``, ``wqr``, ``wkva`` are read once a program).  Behind the last
        lane's write the wave's rows take the decode step's path: the
        absorbed query and the new row (``_absorbed``), the step on their own
        slots, ``_attention_output``; o is then ``[L * piece + B, H *
        v_dim]``.  The last lane's rows and output pass a barrier before its
        write, so the order of that lane's reads and the wave's writes in the
        one leaf is the data's and not the compiler's to choose (as
        models/grouped_query.py ``_lane_by_lane`` says it)."""
        import jax
        import jax.numpy as jnp

        del lens
        n, w, lanes = self.piece, self.row_width, rows.shape[0]
        parts = self._queries_and_rows(lp, x, pos)
        if wave is not None:
            parts, riding = zip(*((t[:lanes * n], t[lanes * n:])
                                  for t in parts))
        q_nope, q_rope, c, k_r = parts
        rows_new, outs = self._cache_rows_of(c, k_r, c_a.dtype), []
        for i in range(lanes):
            row, start, lane = rows[i], starts[i], slice(i * n, (i + 1) * n)
            own = rows_new[lane]

            def attend(pre):        # (traced at once, by the switch below)
                before = jax.lax.dynamic_slice(
                    c_a, (li, row, 0, 0), (1, 1, pre, w))[0, 0]
                return self._piece_attention(lp, q_nope[lane], q_rope[lane],
                                             own, before)

            o = jax.lax.switch(
                start // n,
                [lambda pre=j * n: attend(pre)
                 for j in range(self.max_seq_len // n)])
            if wave is not None and i + 1 == lanes:
                own, o = jax.lax.optimization_barrier((own, o))
            outs.append(o)
            c_a = jax.lax.dynamic_update_slice(
                c_a, own[None, None], (li, row, start, 0))
        if wave is not None:
            step, w_rows, w_live = wave
            c_a, o = step(c_a, *self._absorbed(lp, *riding), w_rows, w_live,
                          li)
            outs.append(self._attention_output(lp, o))
        return c_a, jnp.concatenate(outs)
