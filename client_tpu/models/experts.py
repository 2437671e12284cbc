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

- weights made when asked for (:class:`SeededWeight`) and put on the device a
  leaf at a time;
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
  ``prefill_fn`` leave ``held_words`` words an expert layer and a few logits
  behind a program's tokens (``stream_record`` of the decoder's contract),
  for a model whose ``piece_hidden_fn`` returns a piece's choices
  (models/latent_moe.py keeps its one-word form: its own ``_record`` and
  ``_piece_words``);
- **the piece program** (``piece_hidden_fn``) and the full-context forward
  (``make_apply_params``): one walk over the layers by their kinds
  (``_walk_kinds``) around the parts a backend supplies a kind.

A model sets ``d_model, d_expert, n_experts, experts_held, first_expert,
top_k, routed_scale, dtype, rms_eps, _seed`` and, where they differ from the
defaults, ``router_score``, ``expert_form`` and ``expert_act``.
"""

from __future__ import annotations

import concurrent.futures
import math
import os

import numpy as np

from client_tpu.models.decoder import (DecoderBackend, logit_bits,
                                       sample_into_slots)
from client_tpu.models.layers import rms_norm

_CHUNK = 1 << 24          # elements of a weight made by one task
_BLOCK = 1 << 17          # elements made at a time (cache-sized)
# Rows of a grouped matmul's tile: a wave's groups are a few rows (16 is
# bfloat16's sublane tile), a prefill piece's some dozens.
TILE_M_WAVE, TILE_M_PIECE = 16, 64
# Tokens whose pairs come back from the sorted layout in one gather.
BACK_ROWS = 512
# Logits of a row's first ids in a stream's record, beside its token's.
RECORD_LOGITS = 8


def record_width(expert_layers: int) -> int:
    """int32 a position of a stream's record."""
    return expert_layers + 1 + RECORD_LOGITS


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


class ExpertDecoder(DecoderBackend):
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
        half a piece's where a held expert's mean share of the call's (token,
        expert) pairs fits in half; a tile over the share only lengthens the
        layout that every operation around the products walks.  (On the v5e
        at the cells' widths, ms a program by tile, PERF.md section 6: 64 of
        128 experts of 1856 held, 6 a token, PR 47: a share of 24 rows 15.20
        | 13.70 | 13.96 in tiles of 16 | 32 | 64, of 48 rows 22.56 | 22.49 |
        24.4 in 32 | 64 | 128; 32 of 256 experts of 1024 held, 8 a token, PR
        48: a share of 16 rows 18.06 | 17.36 | 17.47 in 16 | 32 | 64, of 32
        rows 32.19 | 33.51 in 32 | 64.)"""
        share = tokens * self.top_k / self.n_experts
        half = TILE_M_PIECE // 2
        return half if share <= half else TILE_M_PIECE

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

    def _walk_kinds(self, p, x, live, tile_m, mixer):
        """x ``[n, d]`` through the layers by their kinds: ``mixer(kind, ki,
        lp, x)`` -> o for a layer that has one, then ``_after_rows``; a
        ``"none"`` layer is ``_expert_block``.  -> (x, choices ``[expert
        layers, n, top_k]``)."""
        import jax.numpy as jnp

        routes = []
        for li, lp in enumerate(p["layers"]):
            kind, ki = self._layer_kind(li)
            if kind == "none":
                x, _, top_i = self._expert_block(lp, x, live, tile_m)
                routes.append(top_i)
                continue
            x, _, route = self._after_rows(lp, x, mixer(kind, ki, lp, x),
                                           live, tile_m)
            routes += route
        return x, jnp.stack(routes)

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
            x, routes = self._walk_kinds(
                p, p["embed"][ids].astype(jnp.float32), jnp.ones(n, bool),
                TILE_M_PIECE,
                lambda kind, ki, lp, x: getattr(self, f"_full_{kind}_layer")(
                    lp, x, pos))
            return {"logits": self._logits(p, x), "routing": routes}

        return apply, params

    def piece_hidden_fn(self):
        """(params, arena, rows[L], ids[L, piece], lens[L], starts[L]) ->
        (arena, x ``[L * piece, d]``, choices ``[expert layers, L * piece,
        top_k]``), lane after lane: one prefill piece of each of ``L``
        prompts (any ``L`` up to what ``prefill_piece`` declares), positions
        ``starts .. starts + lens`` of a lane's prompt (``starts`` a multiple
        of the piece).  **The piece's frame**, as ``_decode_hidden_fn`` is
        the wave's (models/decoder.py): a layer gets the leaves of its kind.
        Whatever is a matrix product over positions sees all lanes'
        positions as one batch, so a weight, and above all a layer's held
        experts, is read once a program; a mixer runs a lane at a time, each
        from its own slot.  A backend supplies a part for each kind it
        declares, ``_piece_rows_layer``, ``_piece_ring_layer``,
        ``_piece_state_layer`` ``(lp, *the kind's leaves, ki, rows, starts,
        lens, x, pos)`` -> (*leaves, o ``[L * piece, *]``), what follows a
        mixer, ``_after_rows(lp, x, o, live, tile_m)`` -> (x, routing counts,
        choices: ``()`` or ``(top_i,)``), and a ``"none"`` layer whole,
        ``_expert_block(lp, x, live, tile_m)`` -> (x, counts, top_i)."""
        import jax.numpy as jnp

        n = self.piece
        leaves_of = {"rows": self.cache_leaves, "ring": self.ring_leaves,
                     "state": self.state_leaves}

        def piece(p, arena, rows, ids, lens, starts):
            at = jnp.arange(n)
            live = (at < lens[:, None]).reshape(-1)
            pos = (starts[:, None] + at).reshape(-1)
            arena = dict(arena)

            def mixer(kind, ki, lp, x):
                names = leaves_of[kind]
                *leaves, o = getattr(self, f"_piece_{kind}_layer")(
                    lp, *(arena[name] for name in names), ki, rows, starts,
                    lens, x, pos)
                arena.update(zip(names, leaves))
                return o

            x, routes = self._walk_kinds(
                p, p["embed"][ids.reshape(-1)].astype(jnp.float32), live,
                self._piece_tile(rows.shape[0] * n), mixer)
            return arena, x, routes

        return piece

    def prefill_fn(self):
        """``PREFILL_ARGS`` -> (arena, tokens[L]): one **piece** of each
        lane's prompt (``piece_hidden_fn``: (arena, x ``[L * piece, d]``,
        choices ``[expert layers, L * piece, top_k]``, lane after lane)); the
        token sampled after a lane's last valid position lands in its slot's
        device-side token, and means something for a prompt's last piece
        only.  With ``stream_record`` the pieces' rows of the record follow
        the tokens, ``[L + L x piece x stream_record]``."""
        piece = self.piece_hidden_fn()

        def prefill(p, arena, rows, ids, lens, seeds, temps, top_ks, top_ps,
                    sample, starts):
            import jax.numpy as jnp

            lanes = rows.shape[0]
            arena, x, routes = piece(p, arena, rows, ids, lens, starts)
            # Each lane's last valid row of x.
            at = lens - 1 + self.piece * np.arange(lanes, dtype=np.int32)
            logits = self._logits(p, x[at])
            arena, tokens = sample_into_slots(
                arena, rows, logits, seeds, starts + lens, temps, top_ks,
                top_ps, sample)
            if not self.stream_record:
                return arena, tokens
            last = (jnp.arange(lanes * self.piece)
                    == jnp.repeat(at, self.piece))
            words, last = self._piece_words(routes), last[:, None]
            bits = jnp.repeat(logit_bits(logits, tokens, RECORD_LOGITS),
                              self.piece, axis=0)
            rec = jnp.concatenate(words + [jnp.where(last, bits, 0)], axis=1)
            return arena, jnp.concatenate([tokens, rec.reshape(-1)])

        return prefill
