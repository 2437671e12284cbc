"""The sparse expert layer of a served decoder, and what goes with it: one
copy.

Four decoders route every token to a few of many small feed-forward experts:
``models/pangu_moe.py`` and ``models/kimi_linear.py`` (a latent cache; sigmoid
scores, SwiGLU experts, a shared expert beside them: ``models/latent_moe.py``),
``models/smallthinker.py`` (key/value rows in two leaves; a softmax over
the chosen logits, ReLU-gated experts, no shared expert) and
``models/nemotron_h.py`` (expert layers that are blocks of their own between
state-space and attention layers; sigmoid scores with a selection bias,
**un-gated** squared-ReLU experts of two matrices, a shared expert of the same
form).  What does not differ between them lives here, in
:class:`ExpertDecoder`:

- weights made when asked for and put on the device a leaf at a time
  (models/seeded.py, which a dense decoder shares);
- **the router** (``route``): float32 at full precision whatever the matmuls',
  the score function a parameter (``router_score``: ``"sigmoid"`` |
  ``"softmax"``);
- **the experts held here** (``_experts``): the backend holds ``experts_held``
  of the routed experts, ``first_expert ..``; the (token, expert) pairs held
  here are sorted by expert and multiplied in groups (ops/grouped_matmul.py),
  what stands between the two products two parameters: the expert's form
  (``expert_form``: ``"gated"``, ``E(h) = W_d (act(h W_g) * h W_u)`` over a
  fused ``egu [E, d, 2f]``, or ``"plain"``, ``E(h) = W_d act(h W_u)`` over ``eu
  [E, f, d]``, ``W_u`` lying as ``W_d`` does: two matrices an expert, not
  three) and the activation
  (``expert_act``: ``"silu"`` | ``"relu"``, a name of ``jax.nn``, or
  ``"relu2"``, the ReLU squared); ``_dense_expert`` is one expert of that form
  on plain matrices (a shared expert).  No pair is dropped, and an
  expert no token chose is not read.  What absent experts would add is left
  out; nothing stands in for other chips or their exchange;
- the wave's carry (``_embed``: activations, routing counts, choices, live
  lanes), the final norm and head (``_logits``) and the three counters behind
  a wave's tokens (``wave_stats``: pairs held here,
  the busiest held expert's, held experts touched, each summed over the
  expert layers; padded lanes route nowhere);
- **a stream's record** of its routing (``held_mask``): one bit a held expert
  in int32 words, 32 experts a word, the one thing about a routing that a
  share's output depends on discontinuously; ``_words``, ``_record`` and
  ``_piece_words`` leave ``held_words`` words an expert layer and a few logits
  behind a program's tokens (``stream_record`` of the decoder's contract),
  for a model whose ``piece_hidden_fn`` returns a piece's choices
  (models/latent_moe.py keeps its one-word form: its own ``_record`` and
  ``_piece_words``);
- **what a piece carries beside its activations** (the parts of
  models/decoder.py's piece frame, ``piece_hidden_fn``: ``_piece_start``,
  ``_piece_after``, ``_piece_block``, ``_piece_end``): which positions are
  live, the sorted layout's tile (``_piece_tile``) and the expert layers'
  choices, around ``_after_rows`` and ``_expert_block``, which a backend
  supplies; and the full-context forward (``make_apply_params``) over the
  same walk (``_walk_kinds``).

A model sets ``d_model, d_expert, n_experts, experts_held, first_expert,
top_k, routed_scale, dtype, rms_eps, _seed`` and, where they differ from the
defaults, ``router_score``, ``expert_form`` and ``expert_act``.
"""

from __future__ import annotations

from client_tpu.models.decoder import RECORD_LOGITS, logit_bits
from client_tpu.models.layers import rms_norm
from client_tpu.models.seeded import SeededDecoder

# Rows of a grouped matmul's tile: a wave's groups are a few rows (16 is
# bfloat16's sublane tile), a prefill piece's some dozens.
TILE_M_WAVE, TILE_M_PIECE = 16, 64
# Tokens whose pairs come back from the sorted layout in one gather.
BACK_ROWS = 512
class ExpertDecoder(SeededDecoder):
    """The shared parts above."""

    wave_stats = ("expert_pairs_local", "expert_pairs_busiest",
                  "experts_touched")
    router_score = "sigmoid"
    expert_form = "gated"
    expert_act = "silu"
    routed_scale = 1.0

    def _check_experts(self):
        if (self.first_expert + self.experts_held > self.n_experts
                or self.top_k > self.n_experts):
            raise ValueError(
                f"experts {self.first_expert}.."
                f"{self.first_expert + self.experts_held} and "
                f"top {self.top_k} do not fit a router of {self.n_experts}")
        if self.router_score not in ("sigmoid", "softmax"):
            raise ValueError(f"router_score {self.router_score!r}")
        if self.expert_form not in ("gated", "plain"):
            raise ValueError(f"expert_form {self.expert_form!r}")

    # -- shared blocks --------------------------------------------------------

    def route(self, lp, h):
        """The router: h ``[n, d]`` float32 (normed) -> (experts ``[n, k]``,
        weights ``[n, k]`` float32); float32 at full precision whatever the
        matmuls'.  ``router_score = "sigmoid"``: ``s = sigmoid(h W_g)``, the
        ``top_k`` largest of ``s`` (of ``s + b`` where the gate has a
        selection bias, ``router_bias``), weights ``s_i / sum s_i *
        routed_scale``.  ``"softmax"``: the ``top_k`` largest logits and a
        softmax over those alone (a softmax over all of them renormalised
        over the chosen is the same numbers)."""
        import jax
        import jax.numpy as jnp

        s = jnp.matmul(h, lp["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        if self.router_score == "softmax":
            top_s, top_i = jax.lax.top_k(s, self.top_k)
            return top_i, jax.nn.softmax(top_s, axis=-1)
        s = jax.nn.sigmoid(s)
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

    def _between(self, u):
        """What stands between an expert's two products: ``u [..., 2f]``
        (gate | up) -> ``act(gate) * up`` where the form is gated, ``u [...,
        f]`` -> ``act(u)`` where it is plain."""
        import jax

        act = ((lambda t: jax.nn.relu(t) ** 2) if self.expert_act == "relu2"
               else getattr(jax.nn, self.expert_act))
        if self.expert_form == "plain":
            return act(u)
        f = u.shape[-1] // 2
        return act(u[..., :f]) * u[..., f:]

    def _dense_expert(self, h, up, down):
        """One expert of the layer's form on plain matrices (a shared
        expert): h ``[n, d]`` -> ``[n, d]`` float32."""
        return self._mm(self._between(self._mm(h, up)), down)

    def _piece_tile(self, tokens: int) -> int:
        """The sorted layout's tile for a piece call of ``tokens`` positions:
        the smallest of 32, 64 and 128 rows (the MXU's) that holds a held
        expert's mean share of the call's (token, expert) pairs.  A tile over
        the share only lengthens the layout that every operation around the
        products walks; a tile under it, while the MXU has rows to spare,
        leaves the products short of the matrices' read.  (On the v5e at the
        cells' widths, ms a program by tile, PERF.md section 6: 64 of 128
        experts of 1856 held, 6 a token, PR 47: a share of 24 rows 15.20 |
        13.70 | 13.96 in tiles of 16 | 32 | 64, of 48 rows 22.56 | 22.49 |
        24.4 in 32 | 64 | 128; 32 of 256 experts of 1024 held, 8 a token, PR
        48: a share of 16 rows 18.06 | 17.36 | 17.47 in 16 | 32 | 64, of 32
        rows 32.19 | 33.51 in 32 | 64; 64 experts of 768, all held, 6 a
        token, PR 52: a share of 96 rows 28.41 | 27.07 in 64 | 128; 16 of 128
        experts of 4096 held, 8 a token, PR 56: a share of 33.5 rows, a
        piece's 512 and the 24 of the wave it carries, 37.55 | 36.49 in 32 |
        64.)"""
        share = tokens * self.top_k / self.n_experts
        return next((tile for tile in (TILE_M_PIECE // 2, TILE_M_PIECE)
                     if share <= tile), 2 * TILE_M_PIECE)

    def _experts(self, lp, h, live, tile_m, routing=None):
        """The held experts' part of the layer for tokens h ``[n, d]``:
        ``sum_i w_i E_i(h)`` over the chosen experts held here (``E`` by
        ``expert_form`` and ``expert_act``: ``_between``), (pairs here,
        the busiest expert's, experts touched), and every token's choices
        ``[n, k]``."""
        import jax.numpy as jnp

        from client_tpu.engine.backend_init import pallas_interpret
        from client_tpu.ops.grouped_matmul import (capacity_rows,
                                                   grouped_matmul,
                                                   plan_groups,
                                                   reference_grouped_matmul)

        n, held, k = h.shape[0], self.experts_held, self.top_k
        # (A router that does not read h has routed already: ``routing``.)
        top_i, weights = self.route(lp, h) if routing is None else routing
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
        # (A plain expert's ``eu`` lies ``[E, f, d]``, as ``ed`` does.)
        plain = self.expert_form == "plain"
        up = lp["eu" if plain else "egu"]
        wdt = up.dtype
        xs = jnp.concatenate([h.astype(wdt), jnp.zeros((1, h.shape[1]), wdt)
                              ])[src]
        if self._use_kernel():
            def gmm(x, w, **how):
                return grouped_matmul(x, w, plan["tile_expert"],
                                      plan["n_tiles"], tile_m=tile_m,
                                      interpret=pallas_interpret(), **how)
        else:
            def gmm(x, w, transposed=False):
                return reference_grouped_matmul(
                    x, w.swapaxes(1, 2) if transposed else w, plan["padded"])
        mid = gmm(xs, up, transposed=True) if plain else gmm(xs, up)
        ys = gmm(self._between(mid).astype(wdt), lp["ed"])
        # Back to tokens: a pair's row by ``dest``; rows no pair points at
        # (the kernel leaves those behind the last tile unwritten) are
        # never read.
        dest = plan["dest"].reshape(n, k)
        got = dest < rows

        def back(part):
            picked = ys[jnp.where(got[part], dest[part], 0)]   # [., k, d]
            return jnp.sum(jnp.where(got[part][..., None], picked, 0.0)
                           * weights[part][..., None], axis=1)

        # (The gather of more tokens' pairs at once than a piece holds reads
        # three times slower a token on the v5e at 2688 lanes and 6 choices,
        # and as fast as the cut one at 2304 and 8: PERF.md section 6, PR 47
        # and 48.)
        y = back(...) if n <= BACK_ROWS else jnp.concatenate(
            [back(slice(i, i + BACK_ROWS)) for i in range(0, n, BACK_ROWS)])
        sizes = plan["sizes"]
        counts = jnp.stack([sizes.sum(), sizes.max(),
                            (sizes > 0).sum()]).astype(jnp.int32)
        return y, counts, top_i

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

    def _logits(self, p, x):
        h = x["h"] if isinstance(x, dict) else x
        return self._mm(rms_norm(h, p["lnf"], self.rms_eps), p["head"])

    def _wave_stats(self, x):
        return x["stats"]

    def held_mask(self, top_i, word: int = 0):
        """Choices ``[..., k]`` -> int32 ``[...]``: bit ``e`` set where held
        expert ``first_expert + 32 * word + e`` is among them (a ``top_k``'s
        choices are distinct, so the sum is the union).  A share of more
        than 32 experts takes ``held_words`` words a layer."""
        import jax
        import jax.numpy as jnp

        e = top_i - (self.first_expert + 32 * word)
        bits = jnp.where((e >= 0) & (e < min(32, self.experts_held
                                             - 32 * word)),
                         jnp.left_shift(jnp.uint32(1),
                                        jnp.clip(e, 0, 31).astype(jnp.uint32)),
                         jnp.uint32(0))
        return jax.lax.bitcast_convert_type(
            bits.sum(axis=-1, dtype=jnp.uint32), jnp.int32)

    @property
    def held_words(self) -> int:
        """int32 words that hold one bit a held expert."""
        return -(-self.experts_held // 32)

    def _words(self, top_i):
        """Choices ``[..., k]`` -> the record's words ``[..., held_words]``."""
        import jax.numpy as jnp

        return jnp.stack([self.held_mask(top_i, w)
                          for w in range(self.held_words)], axis=-1)

    def _record(self, x, logits, tokens):
        """A wave's rows of the streams' record ``[B, stream_record]``."""
        import jax.numpy as jnp

        return jnp.concatenate(
            [self._words(r) for r in x["route"]]
            + [logit_bits(logits, tokens, RECORD_LOGITS)], axis=1)

    def _piece_words(self, routes):
        """A piece's choices ``[expert layers, n, top_k]`` -> its record's
        words, ``[n, held_words]`` a layer."""
        return [self._words(r) for r in routes]

    def _after_attention(self, lp, x, o):
        h, stats, route = self._after_rows(
            lp, x["h"], o.reshape(o.shape[0], -1), x["live"], TILE_M_WAVE)
        return {**x, "h": h, "stats": x["stats"] + stats,
                "route": x["route"] + route}

    # -- the piece's parts (models/decoder.py ``piece_hidden_fn``) ---------------

    def _piece_start(self, p, ids, pos, live, riders: int = 0):
        """A piece's first x and its trail: which positions hold a token,
        the sorted layout's tile for this many positions, and the expert
        layers' choices so far.  With ``riders`` (the last that many rows
        are a wave's that rides in the program, models/decoder.py
        ``piece_wave``): how many, and the wave's counts so far."""
        import jax.numpy as jnp

        trail = {"live": live, "tile": self._piece_tile(ids.shape[0]),
                 "routes": ()}
        if riders:
            trail.update(riders=riders, stats=jnp.zeros(3, jnp.int32))
        return p["embed"][ids].astype(jnp.float32), trail

    def _held_counts(self, top_i, live):
        """(pairs held here, the busiest held expert's, held experts
        touched) of the rows whose choices are ``top_i [B, k]`` (``live
        [B]``: which of them hold a stream): what ``_experts`` counts of a
        whole call, for the rows of a wave that rides in a piece's."""
        import jax.numpy as jnp

        held = self.experts_held
        e = top_i - self.first_expert
        e = jnp.where((e >= 0) & (e < held) & live[:, None], e, held)
        sizes = (e.reshape(-1, 1) == jnp.arange(held)).sum(axis=0)
        return jnp.stack([sizes.sum(), sizes.max(),
                          (sizes > 0).sum()]).astype(jnp.int32)

    def _trail_on(self, trail, routes):
        """The trail behind an expert layer that chose ``routes`` (a tuple
        of ``[n, k]``): the choices kept, and a riding wave's rows counted
        apart (a piece touches every held expert: a union would say nothing
        of the wave)."""
        trail = {**trail, "routes": trail["routes"] + routes}
        if "riders" in trail:
            b = trail["riders"]
            trail["stats"] = trail["stats"] + sum(
                self._held_counts(r[-b:], trail["live"][-b:])
                for r in routes)
        return trail

    def _piece_after(self, lp, x, o, trail):
        x, _, route = self._after_rows(lp, x, o, trail["live"], trail["tile"])
        return x, self._trail_on(trail, route)

    def _piece_block(self, lp, x, trail):
        x, _, top_i = self._expert_block(lp, x, trail["live"], trail["tile"])
        return x, self._trail_on(trail, (top_i,))

    def _piece_end(self, trail):
        """(the choices ``[expert layers, n, top_k]``, a riding wave's counts
        or ``None``)."""
        import jax.numpy as jnp

        return jnp.stack(trail["routes"]), trail.get("stats")

    def make_apply_params(self):
        """Full-context forward in the served precision: no cache, no pieces,
        nothing absorbed, a state walked position by position.  Logits of
        every position, and each expert layer's choices ``[expert layers, n,
        top_k]``.  A backend supplies a kind's mixer over a whole prompt,
        ``_full_rows_layer``, ``_full_ring_layer``, ``_full_state_layer``
        ``(lp, x, pos)`` -> o.  Model-level entry (the engine takes the placed
        weights from it) and the tests' reference; serving goes through
        pieces and waves."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            import jax.numpy as jnp

            ids = inputs["INPUT_IDS"].astype("int32")
            n = ids.shape[0]
            pos = jnp.arange(n)
            x, routes, _ = self._walk_kinds(
                p, p["embed"][ids].astype(jnp.float32),
                {"live": jnp.ones(n, bool), "tile": TILE_M_PIECE,
                 "routes": ()},
                lambda kind, ki, lp, x: getattr(self, f"_full_{kind}_layer")(
                    lp, x, pos))
            return {"logits": self._logits(p, x), "routing": routes}

        return apply, params
