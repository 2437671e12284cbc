"""A prefill piece's attention over key/value rows with grouped-query heads:
one copy.

``models/smallthinker.py`` (its global layers; its window layers put a band
and a ring around the same attention), ``models/nemotron_h.py`` (its
attention layers) and ``models/ouro.py`` hold ``n_heads`` query heads over
``n_kv_heads`` key/value heads of ``head_dim`` in two leaves ``[layers of the
kind, R, max_seq_len, Hkv*D]`` and prefill by pieces of ``piece`` positions,
one prompt's or two's a program: a layer projects every lane's positions at
once and then walks the lanes one after the other (``_lane_by_lane``), a
lane's read of its slot, then its write, then the next lane's.  Piece i of a
prompt has exactly ``i * piece`` rows before it, so a
layer holds one branch a count and lane (``lax.switch``) and computes nothing
that is masked but inside the causal block: the piece's queries attend to the
rows before them and, causally, to their own (the flash kernel's grouped-query
heads, ``ops/flash_attention.py``, or dense scores, by ``attention_impl``),
and the piece's rows are written behind them.

**A ring beside whole-context rows** (:class:`RingPieces`): the backends whose
layers are window and global layers mixed (``models/smallthinker.py``,
``models/cohere_moe.py``) keep two row shapes in one arena, ``kg, vg [global
layers, R, max_seq_len, Hkv*D]`` and the rings ``kw, vw [window layers, R,
ring rows, Hkv*D]``, position n at row ``n mod ring rows``.  What a piece does
to a ring (read it whole, oldest position first, before the piece's rows
overwrite its oldest block; a prompt's last piece writes its valid rows
alone), the arena of two row shapes, the ring's size and what the scheduler
counts of both kinds live here once, by ``layer_kinds`` and ``rotate`` alone:
which layers slide, and whether the layers of a kind take positions.
"""

from __future__ import annotations

import math

_NEG_INF = -1e30


class GroupedQueryPieces:
    """The shared parts above, for a backend that sets ``n_heads, n_kv_heads,
    head_dim, piece, max_seq_len`` and ``attention_impl`` and supplies
    ``_project(lp, x, pos)`` -> q ``[n, H, D]``, k, v ``[n, Hkv, D]`` (of a
    wave's rows it is the decode step's ``_qkv``)."""

    def _attend(self, q, own_k, own_v, before_k, before_v, window,
                impl=None):
        """A piece's attention: q ``[n, H, D]`` float32 against the keys and
        values of the rows before it ``[P, Hkv*D]`` and, causally, of its own
        ``[n, Hkv*D]`` (both as the cache holds them), in a band of ``window``
        keys where one is given, by ``impl`` (the backend's
        ``attention_impl`` unless given), the scores scaled by ``1 /
        sqrt(D)`` or by the backend's ``attn_scale``.  -> ``[n, H * D]``
        float32."""
        import jax
        import jax.numpy as jnp

        n, pre = own_k.shape[0], before_k.shape[0]
        k_all = jnp.concatenate([before_k, own_k]) if pre else own_k
        v_all = jnp.concatenate([before_v, own_v]) if pre else own_v
        h, hk, d = self.n_heads, self.n_kv_heads, self.head_dim
        if (impl or self.attention_impl) == "flash":
            from client_tpu.engine.backend_init import pallas_interpret
            from client_tpu.ops.flash_attention import flash_attention

            if hk != h and d % 128:
                # The kernel's grouped-query heads fill whole 128-lane
                # tiles; narrower ones (models/granite_hybrid.py: 32 over 8
                # of 64) go in repeated to the query heads, two a tile.  By
                # a one-hot product, exact (a value times 1): a reshape to
                # heads of 64 lanes is a layout of its own, which the
                # compiler carried back to the cache's leaf and copied the
                # leaf to (tests/test_tpu_compile.py).
                lane = jnp.arange(h * d)
                pick = (jnp.arange(hk * d)[:, None]
                        == (lane // d // (h // hk) * d + lane % d)[None, :]
                        ).astype(k_all.dtype)
                k_all, v_all = (
                    jnp.matmul(t, pick, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32
                               ).astype(t.dtype) for t in (k_all, v_all))
                hk = h
            return flash_attention(
                q.reshape(1, n, h * d).astype(k_all.dtype), k_all[None],
                v_all[None], causal=True, prefix=pre, window=window,
                n_heads=h, n_kv_heads=hk, block_q=n, block_k=n,
                sm_scale=self.attn_scale,
                interpret=pallas_interpret())[0].astype(jnp.float32)
        group = h // hk
        k_f = jnp.repeat(k_all.astype(jnp.float32).reshape(pre + n, hk, d),
                         group, axis=1)
        v_f = jnp.repeat(v_all.astype(jnp.float32).reshape(pre + n, hk, d),
                         group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k_f)
        s = (s / math.sqrt(d) if self.attn_scale is None
             else s * self.attn_scale)
        ago = (pre + jnp.arange(n)[:, None]) - jnp.arange(pre + n)[None, :]
        seen = ago >= 0
        if window is not None:
            seen = seen & (ago < window)
        s = jnp.where(seen[None], s, _NEG_INF)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                          v_f).reshape(n, h * d)

    def _rows_before(self, leaf, ki, row, count):
        """The first ``count`` rows of slot ``row`` in layer ``ki`` of a
        leaf."""
        import jax

        return jax.lax.dynamic_slice(
            leaf, (ki, row, 0, 0),
            (1, 1, count, self.n_kv_heads * self.head_dim))[0, 0]

    def _qkv(self, lp, x, pos):
        return self._project(lp, x["h"], pos)

    def _heads(self, lp, h):
        """Normed rows h ``[n, d]`` -> q ``[n, H, D]``, k, v ``[n, Hkv, D]``
        float32 (``wq, wk, wv``)."""
        n, d = h.shape[0], self.head_dim
        return (self._mm(h, lp["wq"]).reshape(n, self.n_heads, d),
                self._mm(h, lp["wk"]).reshape(n, self.n_kv_heads, d),
                self._mm(h, lp["wv"]).reshape(n, self.n_kv_heads, d))

    def _as_cached(self, k, v, dtype):
        """k, v ``[n, Hkv, D]`` -> the rows ``[n, Hkv*D]`` the cache holds."""
        hd = self.n_kv_heads * self.head_dim
        return (t.reshape(-1, hd).astype(dtype) for t in (k, v))

    def _full_layer(self, qkv, window):
        """A layer's attention over a whole prompt from its projections,
        nothing cached (models/experts.py ``make_apply_params``): dense
        scores, in a band of ``window`` keys where one is given."""
        import jax.numpy as jnp

        q, k, v = qkv
        own_k, own_v = self._as_cached(k, v, jnp.dtype(self.dtype))
        return self._attend(q, own_k, own_v, own_k[:0], own_v[:0], window,
                            impl="einsum")

    def _full_rows_layer(self, lp, x, pos):
        return self._full_layer(self._project(lp, x, pos), None)

    def _lane_by_lane(self, read, write, qkv, k_a, v_a, ki, rows, starts,
                      lens, wave=None):
        """A layer's part of a piece of ``L`` lanes from its projections over
        every lane's positions at once (``qkv``: q ``[L * piece, H, D]``, k, v
        ``[L * piece, Hkv, D]`` float32): the rows as the cache holds them,
        then lane after lane ``read(k_a, v_a, ki, row, start, q, own_k,
        own_v)`` -> o ``[piece, H * D]`` and ``write(k_a, v_a, ki, row,
        start, n_valid, own_k, own_v)`` -> (K leaf, V leaf).  **A lane's rows
        go into the leaf behind its own reads and before the next lane's**:
        where another lane follows, the rows pass a barrier beside the
        lane's output, so the order is the data's and not the compiler's to
        choose (left to it, the two-lane program of ``smallthinker_21b``'s
        widths copied a 1.5 GB leaf to keep one lane's reads apart from
        the other's writes; tests/test_tpu_compile.py).  -> (K leaf, V leaf,
        o ``[L * piece, H * D]``).

        **With a wave** (models/decoder.py ``piece_wave``; ``wave``: the
        wave's step of the layer's kind, its rows ``[B]``, its live rows or
        lengths, and the layer's weights): ``qkv`` hold the wave's ``B`` rows
        behind the piece's, and behind the last lane's write (that lane's
        rows pass the barrier too) the wave's step writes each lane's row
        into its own slot and reads that slot, its output taken through
        ``_attention_output`` as a decode step's is; o is then ``[L * piece +
        B, H * D]``."""
        import jax
        import jax.numpy as jnp

        n, (q, k, v) = self.piece, qkv
        lanes, outs = rows.shape[0], []
        if wave is not None:
            (q, q_w), (k, k_w), (v, v_w) = (
                (t[:lanes * n], t[lanes * n:]) for t in qkv)
        own_k, own_v = self._as_cached(k, v, k_a.dtype)
        for i in range(lanes):
            own = slice(i * n, (i + 1) * n)
            # (The lane's operands in the order the recorded one-lane
            # programs take them: tests/test_served_programs.py.)
            at, n_valid = (ki, rows[i], starts[i]), lens[i]
            new_k, new_v = own_k[own], own_v[own]
            o = read(k_a, v_a, *at, q[own], new_k, new_v)
            if i + 1 < lanes or wave is not None:
                new_k, new_v, o = jax.lax.optimization_barrier(
                    (new_k, new_v, o))
            k_a, v_a = write(k_a, v_a, *at, n_valid, new_k, new_v)
            outs.append(o)
        if wave is not None:
            step, w_rows, w_live, lp = wave
            k_a, v_a, o = step(k_a, v_a, q_w, k_w, v_w, w_rows, w_live, ki)
            o = self._attention_output(lp, o)
            outs.append(o.reshape(o.shape[0], -1))
        return k_a, v_a, jnp.concatenate(outs)

    def _piece_rows_layer(self, lp, k_a, v_a, ki, rows, starts, lens, x, pos,
                          wave=None):
        """A whole-context layer's part of a piece (models/decoder.py
        ``piece_hidden_fn``), by the backend's ``_project(lp, x, pos)``."""
        return self._lane_by_lane(
            self._read_rows, self._write_rows, self._project(lp, x, pos),
            k_a, v_a, ki, rows, starts, lens, wave and (*wave, lp))

    def _read_rows(self, k_a, v_a, ki, row, start, q, own_k, own_v):
        """A whole-context layer's part of one lane's piece: q ``[piece, H,
        D]`` against the slot's ``start`` rows before the piece and its own
        ``own_k, own_v [piece, Hkv*D]`` (as the cache holds them).  -> o
        ``[piece, H * D]``."""
        import jax

        n = self.piece

        def attend(pre):
            before = [self._rows_before(leaf, ki, row, pre)
                      for leaf in (k_a, v_a)]
            return self._attend(q, own_k, own_v, *before, None)

        return jax.lax.switch(
            start // n, [lambda pre=i * n: attend(pre)
                         for i in range(self.max_seq_len // n)])

    def _write_rows(self, k_a, v_a, ki, row, start, n_valid, own_k, own_v):
        """A lane's piece written behind the slot's ``start`` rows (those
        behind the ``n_valid`` are beyond the slot's live rows).  -> (K
        leaf, V leaf)."""
        import jax

        del n_valid
        return tuple(jax.lax.dynamic_update_slice(
            leaf, own[None, None], (ki, row, start, 0))
            for leaf, own in ((k_a, own_k), (v_a, own_v)))


class RingPieces(GroupedQueryPieces):
    """Window layers (a ring a slot) beside global layers (a row a position)
    in one arena, for a backend that supplies ``_project(lp, x, pos, kind)``
    and sets, beside :class:`GroupedQueryPieces`'s sizes, ``n_layers``,
    ``window`` and ``dtype`` and calls ``_ring_setup``."""

    cache_leaves = ("kg", "vg")
    ring_leaves = ("kw", "vw")

    def _ring_setup(self, slides, rotates):
        """``layer_kinds`` (``"ring"`` where a layer slides, ``"rows"`` where
        it sees every earlier position), ``rotate`` (kind -> whether its
        layers take positions: the frame hands a layer over by its kind, so
        the layers of a kind rotate alike), and the ring's rows: the window's
        keys rounded up to whole pieces (a piece never wraps)."""
        self.layer_kinds = tuple("ring" if s else "rows" for s in slides)
        self.rotate = {kind: rotates[self.layer_kinds.index(kind)]
                       for kind in set(self.layer_kinds)}
        if any(r != self.rotate[k]
               for r, k in zip(rotates, self.layer_kinds)):
            raise ValueError("the layers of a kind (window | global) rotate "
                             "alike or not at all")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} key/value heads")
        self.ring_rows = -(-self.window // self.piece) * self.piece
        if self.ring_rows != self.window:
            self.ring_window = self.window
        if self.max_seq_len % self.piece or self.ring_rows > self.max_seq_len:
            raise ValueError("max_seq_len divides into prefill pieces, and "
                             "a window's ring fits a slot")

    # -- what the scheduler counts (models/decoder.py) ---------------------------

    def cache_rows_by_kind(self, n: int) -> tuple[int, int, int]:
        """(ring rows, whole-context rows, past the ring) of a decode step at
        context length ``n``: a window layer reads its live rows but the one
        it overwrites, a global layer every position's."""
        rings = self.layer_kinds.count("ring")
        return (rings * min(n, self.window - 1),
                (self.n_layers - rings) * n, int(n > self.window))

    # -- the decode step's parts (models/decoder.py) ---------------------------

    def _ring_qkv(self, lp, x, pos):
        return self._project(lp, x["h"], pos, "ring")

    # -- a piece's attention over a ring ------------------------------------------

    def _piece_ring_layer(self, lp, k_a, v_a, ki, rows, starts, lens, x, pos,
                          wave=None):
        """A window layer's part of a piece (models/decoder.py
        ``piece_hidden_fn``)."""
        return self._lane_by_lane(
            self._read_ring, self._write_ring,
            self._project(lp, x, pos, "ring"), k_a, v_a, ki, rows, starts,
            lens, wave and (*wave, lp))

    def _full_ring_layer(self, lp, x, pos):
        return self._full_layer(self._project(lp, x, pos, "ring"), self.window)

    def _read_ring(self, k_a, v_a, ki, row, start, q, own_k, own_v):
        """A window layer's part of one lane's piece: q ``[piece, H, D]``
        against the slot's ring and, causally, its own ``own_k, own_v [piece,
        Hkv*D]`` (as the cache holds them).  -> o ``[piece, H * D]``."""
        import jax
        import jax.numpy as jnp

        n = self.piece

        def attend(pre, rolled=False):
            before = [self._rows_before(leaf, ki, row, pre)
                      for leaf in (k_a, v_a)]
            if rolled:
                # A full ring, oldest position first: row (start mod ring)
                # holds position start - ring.
                before = [jnp.roll(b, -(start % self.ring_rows), axis=0)
                          for b in before]
            return self._attend(q, own_k, own_v, *before, self.window)

        full = self.ring_rows // n
        branches = [lambda pre=i * n: attend(pre) for i in range(full)]
        branches.append(lambda: attend(self.ring_rows, rolled=True))
        return jax.lax.switch(jnp.minimum(start // n, full), branches)

    def _write_ring(self, k_a, v_a, ki, row, start, n_valid, own_k, own_v):
        """A lane's piece written into the slot's ring, over its oldest
        block.  -> (K leaf, V leaf)."""
        import jax
        import jax.numpy as jnp

        n, hd = self.piece, self.n_kv_heads * self.head_dim
        at = start % self.ring_rows
        # A prompt's last piece: the rows behind its valid ones hold
        # positions a later step still reads.
        valid = (jnp.arange(n) < n_valid)[:, None]
        own_k, own_v = (
            jnp.where(valid, own, jax.lax.dynamic_slice(
                leaf, (ki, row, at, 0), (1, 1, n, hd))[0, 0])
            for own, leaf in ((own_k, k_a), (own_v, v_a)))
        return tuple(jax.lax.dynamic_update_slice(
            leaf, own[None, None], (ki, row, at, 0))
            for leaf, own in ((k_a, own_k), (v_a, own_v)))

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``kg, vg [global layers, R, max_seq_len, Hkv*D]`` and ``kw, vw
        [window layers, R, ring rows, Hkv*D]`` in the model's dtype (``R =
        capacity + 1``: the last slot absorbs padded lanes) and ``tok [R]``,
        each slot's latest token on the device."""
        import jax.numpy as jnp

        r, dt = capacity + 1, jnp.dtype(self.dtype)
        hd = self.n_kv_heads * self.head_dim
        rings = self.layer_kinds.count("ring")
        whole = (self.n_layers - rings, r, self.max_seq_len, hd)
        ring = (rings, r, self.ring_rows, hd)
        return {"kg": jnp.zeros(whole, dt), "vg": jnp.zeros(whole, dt),
                "kw": jnp.zeros(ring, dt), "vw": jnp.zeros(ring, dt),
                "tok": jnp.zeros(r, jnp.int32)}
