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
pieces (``prefill_piece``), one prompt or two a call: every
matrix product over positions (the projections, the shared expert, and above
all the held experts' grouped matmuls) sees all lanes' positions as one batch
and reads its weights once; the mixers run a lane at a time: an M layer runs
the chunked form (``ssd_chunk_scan``) from the lane's slot's state and tail
and writes both back (a padded position has ``dt = 0``: it moves nothing, and
the tail is that of the last valid positions; a prompt's first piece starts
from zeros); a \\* layer is models/grouped_query.py's, a lane's own count
of rows before it.

The projection's ``xBC`` is rounded to the model's dtype before the
convolution, in a wave and in a piece alike: the tail a slot carries is then
what the piece itself convolved, however a prompt is cut.
"""

from __future__ import annotations

import math

from client_tpu.models.experts import (TILE_M_PIECE, TILE_M_WAVE,
                                       ExpertDecoder, record_width, rms_norm)
from client_tpu.models.grouped_query import GroupedQueryPieces
from client_tpu.ops.ssd import CHUNK

_KINDS = {"M": "state", "*": "rows", "E": "none"}


class NemotronHBackend(GroupedQueryPieces, ExpertDecoder):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``pattern`` is the published ``hybrid_override_pattern`` as it
    stands, of which the first ``n_layers`` letters are served (the rest name
    layers on further chips); ``dtype="float32"`` makes weights, caches and
    matmuls float32 (the tests' exact comparison)."""

    state_leaves = ("s", "conv")
    expert_form = "plain"
    expert_act = "relu2"

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
        self.m_heads, self.m_dim = int(mamba_heads), int(mamba_head_dim)
        self.n_groups, self.state_size = int(n_groups), int(state_size)
        self.taps = int(conv_kernel)
        # The gated norm's groups: those of B and C.
        self.norm_groups = self.n_groups
        self.d_inner = self.m_heads * self.m_dim
        # What the convolution mixes: x | B | C.
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.state_size
        if self.n_heads % self.n_kv_heads or self.m_heads % self.n_groups:
            raise ValueError(
                f"{n_heads} query heads over {n_kv_heads} key/value heads, "
                f"{mamba_heads} state heads in {n_groups} groups")
        # Heads side by side in the state's leaf (ops/ssd.py): as many of one
        # group as fill a row of 128 lanes.
        self.pack = math.gcd(self.m_heads // self.n_groups,
                             max(1, 128 // self.m_dim))
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
        ``W_in`` by its columns (``wz, wxbc, wdt``: leaves of whole lanes,
        which no slice of a product has to cut), ``conv [taps, x | B | C]`` and its bias, the
        heads' ``dt_bias, a_log, skip`` (float32: ``softplus(dt_bias)`` about
        0.001-0.1, ``exp(a_log)`` about 1-16), the group norm's ``gnorm`` and
        ``wo``; a \\* layer ``wq, wk, wv, wo``; an E layer the router and its
        selection bias (float32), the shared expert's ``su, sd`` and the held
        experts' stacked ``eu`` (``W_u^T``) and ``ed``, both ``[E, f, d]``: a
        width that is no multiple of 128 lanes is never the minor axis."""
        d, hm = self.d_model, self.m_heads
        f, fs, e = self.d_expert, self.d_shared, self.experts_held
        hd = self.head_dim
        w, mat, gain = self._weight_makers()

        def layer(kind: str):
            lp = {"ln": gain(d)}
            if kind == "state":
                lp.update(
                    wz=mat(d, self.d_inner), wxbc=mat(d, self.conv_dim),
                    wdt=mat(d, hm),
                    conv=w(self.taps, self.conv_dim,
                           scale=1.0 / math.sqrt(self.taps)),
                    conv_b=w(self.conv_dim, scale=0.1),
                    dt_bias=w(hm, scale=0.8, offset=-4.6, dtype="float32"),
                    a_log=w(hm, scale=0.7, offset=1.4, dtype="float32"),
                    skip=w(hm, scale=0.1, offset=1.0, dtype="float32"),
                    gnorm=gain(self.d_inner), wo=mat(self.d_inner, d))
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
        h = rms_norm(x, lp["ln"], self.rms_eps)
        n = x.shape[0]
        return (self._mm(h, lp["wq"]).reshape(n, self.n_heads, self.head_dim),
                self._mm(h, lp["wk"]).reshape(n, self.n_kv_heads,
                                              self.head_dim),
                self._mm(h, lp["wv"]).reshape(n, self.n_kv_heads,
                                              self.head_dim))

    def _ssm_project(self, lp, x, dtype):
        """An M layer's x ``[..., d]`` float32 -> the gate z ``[..., d_inner]``
        float32, the convolution's new inputs ``[..., conv_dim]`` in the
        cache's ``dtype`` and ``dt [..., H]`` float32 (after the softplus)."""
        import jax

        h = rms_norm(x, lp["ln"], self.rms_eps)
        return (self._mm(h, lp["wz"]), self._mm(h, lp["wxbc"]).astype(dtype),
                jax.nn.softplus(self._mm(h, lp["wdt"]) + lp["dt_bias"]))

    def _ssm_inputs(self, lp, ext, n):
        """The convolution's inputs ext ``[..., n + taps - 1, conv_dim]`` (the
        tail, then these rows' projections) -> x ``[..., n, H, P]``, B, C
        ``[..., n, G, N]`` float32."""
        import jax
        import jax.numpy as jnp

        ext = ext.astype(jnp.float32)
        taps = lp["conv"].astype(jnp.float32)
        mixed = jax.nn.silu(
            sum(taps[j] * ext[..., j:j + n, :] for j in range(self.taps))
            + lp["conv_b"].astype(jnp.float32))
        lead, gn = mixed.shape[:-1], self.n_groups * self.state_size
        return (mixed[..., :self.d_inner].reshape(*lead, self.m_heads,
                                                  self.m_dim),
                mixed[..., self.d_inner:self.d_inner + gn].reshape(
                    *lead, self.n_groups, self.state_size),
                mixed[..., self.d_inner + gn:].reshape(
                    *lead, self.n_groups, self.state_size))

    def _ssm_output(self, lp, y, x, z):
        """The state's read-outs y ``[..., H, P]`` with the skip term, gated
        by z ``[..., d_inner]`` and normed a group -> ``[..., d_inner]``."""
        import jax
        import jax.numpy as jnp

        y = (y + lp["skip"][:, None] * x).reshape(z.shape) * jax.nn.silu(z)
        y = y.reshape(*z.shape[:-1], self.norm_groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.rms_eps)
        return y.reshape(z.shape) * lp["gnorm"].astype(jnp.float32)

    def _expert_block(self, lp, x, live, tile_m):
        """An E layer for rows x ``[n, d]`` -> (x, routing counts, choices
        ``[n, k]``)."""
        h = rms_norm(x, lp["ln"], self.rms_eps)
        y, counts, top_i = self._experts(lp, h, live, tile_m)
        return (x + y + self._dense_expert(h, lp["su"], lp["sd"]), counts,
                top_i)

    # -- the decode step's parts (models/decoder.py) ---------------------------

    def _qkv(self, lp, x, pos):
        return self._project(lp, x["h"], pos)

    def _after_attention(self, lp, x, o):
        """A mixer layer's tail: the residual add and nothing else."""
        return {**x, "h": x["h"] + self._mm(o.reshape(o.shape[0], -1),
                                            lp["wo"])}

    def _feed_forward(self, lp, x):
        h, stats, top_i = self._expert_block(lp, x["h"], x["live"],
                                             TILE_M_WAVE)
        return {**x, "h": h, "stats": x["stats"] + stats,
                "route": x["route"] + (top_i,)}

    def _advance(self, lp, x, s_a, conv_a, rows, lens, ki):
        """An M layer's part of a wave: each lane's projection joins its
        slot's tail (models/decoder.py ``slot_tails``), the slot's state moves
        one position in place (ops/ssd.py: the kernel, or its oracle where the
        arena is not the kernels')."""
        import jax.numpy as jnp

        from client_tpu.engine.backend_init import pallas_interpret
        from client_tpu.models.decoder import put_slot_tails, slot_tails
        from client_tpu.ops.ssd import reference_ssd_update, ssd_wave_update

        del lens
        z, new, dt = self._ssm_project(lp, x["h"], conv_a.dtype)
        lanes = new.shape[0]
        pick, slots, tail = slot_tails(conv_a, ki, rows)
        ext = jnp.concatenate(
            [tail.reshape(lanes, self.taps - 1, self.conv_dim),
             new[:, None]], axis=1)
        xs, b, c = (t[:, 0] for t in self._ssm_inputs(lp, ext, 1))
        conv_a = put_slot_tails(conv_a, ki, pick, slots, ext)
        a = -jnp.exp(lp["a_log"])
        if self._use_kernel():
            s_a, y = ssd_wave_update(s_a, xs, dt, a, b, c, rows, layer=ki,
                                     interpret=pallas_interpret())
        else:
            s_a, y = reference_ssd_update(s_a, xs, dt, a, b, c, rows,
                                          layer=ki)
        return s_a, conv_a, self._ssm_output(lp, y, xs, z)

    # -- full-context forward (no cache) ----------------------------------------

    def make_apply_params(self):
        """Full-context forward in the served precision: no cache, no pieces,
        the state walked position by position.  Logits of every position,
        and each expert layer's choices ``[expert layers, n, top_k]``.
        Model-level entry for diagnostics; serving goes through pieces and
        waves."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            import jax.numpy as jnp

            from client_tpu.ops.ssd import ssd_recurrence

            ids = inputs["INPUT_IDS"].astype("int32")
            n = ids.shape[0]
            live = jnp.ones(n, bool)
            cdt = jnp.dtype(self.dtype)
            hd = self.n_kv_heads * self.head_dim
            x = p["embed"][ids].astype(jnp.float32)
            routes = []
            for lp, kind in zip(p["layers"], self.layer_kinds):
                if kind == "none":
                    x, _, top_i = self._expert_block(lp, x, live,
                                                     TILE_M_PIECE)
                    routes.append(top_i)
                    continue
                if kind == "state":
                    z, new, dt = self._ssm_project(lp, x, cdt)
                    ext = jnp.concatenate(
                        [jnp.zeros((self.taps - 1, self.conv_dim), cdt), new])
                    xs, b, c = self._ssm_inputs(lp, ext, n)
                    zero = jnp.zeros((self.m_heads, self.state_size,
                                      self.m_dim), jnp.float32)
                    y, _ = ssd_recurrence(xs, dt, -jnp.exp(lp["a_log"]), b,
                                          c, zero)
                    o = self._ssm_output(lp, y, xs, z)
                else:
                    q, k, v = self._project(lp, x, jnp.arange(n))
                    own_k, own_v = (t.reshape(n, hd).astype(cdt)
                                    for t in (k, v))
                    o = self._attend(q, own_k, own_v, own_k[:0], own_v[:0],
                                     None, impl="einsum")
                x = x + self._mm(o, lp["wo"])
            return {"logits": self._logits(p, x),
                    "routing": jnp.stack(routes)}

        return apply, params

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``k, v [L_r, R, max_seq_len, Hkv * D]`` and ``conv [L_s, R, (taps -
        1) * conv_dim]`` in the model's dtype, ``s [L_s, R, H / pack, N, pack
        * P]`` float32 (``R = capacity + 1``: the last slot absorbs padded
        lanes) and ``tok [R]``, each slot's latest token on the device."""
        import jax.numpy as jnp

        r, dt = capacity + 1, jnp.dtype(self.dtype)
        n_state = self.layer_kinds.count("state")
        rows = (self.layer_kinds.count("rows"), r, self.max_seq_len,
                self.n_kv_heads * self.head_dim)
        return {
            "k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
            "s": jnp.zeros((n_state, r, self.m_heads // self.pack,
                            self.state_size, self.pack * self.m_dim),
                           jnp.float32),
            "conv": jnp.zeros((n_state, r, (self.taps - 1) * self.conv_dim),
                              dt),
            "tok": jnp.zeros(r, jnp.int32)}

    def _piece_state_layer(self, lp, s_a, conv_a, ki, rows, fresh, lens, x):
        """An M layer's part of a piece of ``L`` lanes, x ``[L * piece, d]``:
        the projections over every lane's positions at once, then a lane at a
        time the convolution, the chunked form from its slot's state and tail
        (zeros for a prompt's first piece), both written back, and the gated
        norm.  -> (s_a, conv_a, o ``[L * piece, d_inner]``)."""
        import jax
        import jax.numpy as jnp

        from client_tpu.ops.ssd import ssd_chunk_scan

        n = self.piece
        z, new, dt = self._ssm_project(lp, x, conv_a.dtype)
        a, outs = -jnp.exp(lp["a_log"]), []
        for i in range(rows.shape[0]):
            own = slice(i * n, (i + 1) * n)
            valid = jnp.arange(n) < lens[i]
            tail = jnp.where(fresh[i], 0, conv_a[ki, rows[i]]).reshape(
                -1, self.conv_dim)
            ext = jnp.concatenate([tail, new[own]])
            xs, b, c = self._ssm_inputs(lp, ext, n)
            y, s = ssd_chunk_scan(
                xs, jnp.where(valid[:, None], dt[own], 0.0), a, b, c,
                jnp.where(fresh[i], 0.0, s_a[ki, rows[i]]), chunk=self.chunk)
            s_a = jax.lax.dynamic_update_slice(
                s_a, s.astype(s_a.dtype)[None, None], (ki, rows[i], 0, 0, 0))
            # The inputs of the last valid positions (with the old tail's,
            # where the piece holds fewer than a tail).
            tail = jax.lax.dynamic_slice(ext, (lens[i], 0),
                                         (self.taps - 1, self.conv_dim))
            conv_a = jax.lax.dynamic_update_slice(
                conv_a, tail.reshape(1, 1, -1), (ki, rows[i], 0))
            outs.append(self._ssm_output(lp, y, xs, z[own]))
        return s_a, conv_a, jnp.concatenate(outs)

    def piece_hidden_fn(self):
        """(params, arena, rows[L], ids[L, piece], lens[L], starts[L]) ->
        (arena, x ``[L * piece, d]``, choices ``[expert layers, L * piece,
        top_k]``), lane after lane: one prefill piece of each of ``L``
        prompts, positions ``starts .. starts + lens`` of a lane's prompt
        (``starts`` a multiple of the piece).  Whatever is a matrix product
        over positions sees all lanes' positions as one batch, so a weight,
        and above all a layer's held experts, is read once a program; the
        mixers run a lane at a time, each from its own slot."""
        import jax.numpy as jnp

        n = self.piece
        hd = self.n_kv_heads * self.head_dim

        def piece(p, arena, rows, ids, lens, starts):
            lanes = rows.shape[0]
            at = jnp.arange(n)
            live = (at < lens[:, None]).reshape(-1)
            tile_m = self._piece_tile(lanes * n)
            k_a, v_a = arena["k"], arena["v"]
            s_a, conv_a = arena["s"], arena["conv"]
            x = p["embed"][ids.reshape(-1)].astype(jnp.float32)
            routes = []
            for li, lp in enumerate(p["layers"]):
                kind, ki = self._layer_kind(li)
                if kind == "none":
                    x, _, top_i = self._expert_block(lp, x, live, tile_m)
                    routes.append(top_i)
                    continue
                if kind == "state":
                    s_a, conv_a, o = self._piece_state_layer(
                        lp, s_a, conv_a, ki, rows, starts == 0, lens, x)
                else:
                    q, k, v = self._project(
                        lp, x, (starts[:, None] + at).reshape(-1))
                    own_k, own_v = (t.reshape(-1, hd).astype(k_a.dtype)
                                    for t in (k, v))
                    outs = []
                    for i in range(lanes):
                        own = slice(i * n, (i + 1) * n)
                        k_a, v_a, o = self._piece_rows(
                            k_a, v_a, ki, rows[i], starts[i], q[own],
                            own_k[own], own_v[own])
                        outs.append(o)
                    o = jnp.concatenate(outs)
                x = x + self._mm(o, lp["wo"])
            return ({**arena, "k": k_a, "v": v_a, "s": s_a, "conv": conv_a},
                    x, jnp.stack(routes))

        return piece
