"""Sparse-expert decoder with a latent cache (`pangu_moe`): one chip's share
of an expert-parallel deployment, served through the generative path.

The architecture is the public ``openPangu-Ultra-MoE`` config's
(``model_type`` ``pangu_ultra_moe``): multi-head latent attention, a few
leading dense layers and then layers of many routed experts and a shared one,
four RMSNorms a layer (``sandwich_norm``), rotary positions on a slice of each
head, SwiGLU, no biases; a float32 residual stream and float32 logits over
bfloat16 matmuls (models/evabyte.py's arithmetic).  With x ``[n, d]``:

- *Block*: ``x += N2(MLA(N1(x)))``; ``x += N4(FFN(N3(x)))``; a final RMSNorm;
  logits ``x W_head``.  ``FFN`` is a SwiGLU in a leading dense layer, the
  expert layer after.
- *MLA*: ``c_q = RMSNorm(x W_qa)``; per head ``[q_nope | q_rope] = c_q
  W_qb``; ``[c_kv | k_r] = x W_kva``; ``c = RMSNorm(c_kv)``; rotary on
  ``q_rope`` and on ``k_r``, which every head shares; per head ``k_nope = c
  W_kb``, ``v = c W_vb``; one causal softmax over ``(q_nope . k_nope + q_rope
  . k_r) / sqrt(nope + rope)``; ``o = concat_h(p v) W_o``.
- *Expert layer*: ``s = sigmoid(x W_g)`` over **all** ``n_experts``, in
  float32; the ``top_k`` largest; weights ``s_i / sum s_i * routed_scale``;
  ``y = shared(x) + sum_i w_i E_i(x)``.

**The share.**  This backend holds ``experts_held`` of the routed experts,
``first_expert ..``, as one chip of an expert-parallel group does.  The router
keeps its width and its ``top_k``; the layer computes ``shared(x)`` and the
terms of the chosen experts it holds; what the absent experts would add is left
out, and that partial result goes on to the next layer.  Nothing stands in for
the other chips or their exchange.  The (token, expert) pairs held here are
sorted by expert and multiplied in groups (ops/grouped_matmul.py): no pair is
dropped, and an expert no token chose is not read.

**The cache** is one leaf ``c`` of ``[L, R, S, W]``: a position's row is ``[c
| k_r | 0]`` (``latent_row_width``: 512 + 64 -> 640 lanes), what every head
reads.  **Decode absorbs** the up-projection: ``q_lat = q_nope W_kb^T`` per
head, scores ``[q_lat | q_rope] . row``, ``o_lat = p c``, ``o_h = o_lat
W_vb`` (``latent_attention`` of models/decoder.py's contract; the kernel is
ops/decode_kernel.py ``latent_wave_attention``, which takes the query and
leaves ``o_lat`` with the heads along the minor axis: the two einsums write
and read that layout).  **Prefill does not**: a
piece of ``piece`` positions computes ``k_nope`` and ``v`` of its own rows and,
from the cache, of the rows before it, and attends with the flash kernel
(per head q and k ``nope + rope`` wide, padded to whole tiles, v ``v_dim``).
A prompt is consumed a piece at a time (``prefill_piece``), one lane a call;
piece i of a prompt has exactly ``i * piece`` rows before it, so the program
holds one branch a count (``lax.switch``) and computes nothing that is masked.

Layers are a Python loop over per-layer weights (five at the served depth):
a layer's matrices are operands as they are, with no slice of a stacked array
in front of a kernel.

What a wave routed is known on the device only: ``wave_stats`` names the three
counters the decode program returns behind its tokens (pairs held here, the
busiest held expert's, held experts touched, each summed over the expert
layers; padded lanes route nowhere).
"""

from __future__ import annotations

import concurrent.futures
import math
import os

import numpy as np

from client_tpu.models.decoder import DecoderBackend, sample_into_slots
from client_tpu.models.evabyte import rope

_NEG_INF = -1e30
_CHUNK = 1 << 24          # elements of a weight made by one task
_BLOCK = 1 << 17          # elements made at a time (cache-sized)
# Rows of a grouped matmul's tile: a wave's groups are a few rows (16 is
# bfloat16's sublane tile), a prefill piece's some dozens.
TILE_M_WAVE, TILE_M_PIECE = 16, 64


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


class PanguMoeBackend(DecoderBackend):
    """The decoder above (``models/decoder.py`` for what it is served
    through).  ``dtype="float32"`` makes weights, cache and matmuls float32
    (the tests' exact-routing comparison); the served form is bfloat16."""

    cache_leaves = ("c",)
    wave_stats = ("expert_pairs_local", "expert_pairs_busiest",
                  "experts_touched")

    def __init__(self, name: str = "pangu_moe", n_layers: int = 3,
                 n_dense: int = 1, d_model: int = 64, n_heads: int = 4,
                 q_rank: int = 48, kv_rank: int = 32, nope_dim: int = 16,
                 rope_dim: int = 8, v_dim: int = 16, d_ff: int = 128,
                 d_expert: int = 32, n_experts: int = 16,
                 experts_held: int = 4, first_expert: int = 0,
                 top_k: int = 4, n_shared: int = 1,
                 routed_scale: float = 2.5, vocab: int = 96,
                 max_seq_len: int = 64, piece: int = 16,
                 rope_theta: float = 25600000.0, rms_eps: float = 1e-5,
                 max_streams: int = 4, seed: int = 0,
                 attention_impl: str = "einsum",
                 attn_impl: str | None = None, dtype: str = "bfloat16"):
        super().__init__(name, vocab=vocab, max_seq_len=max_seq_len,
                         max_streams=max_streams,
                         attention_impl=attention_impl, attn_impl=attn_impl)
        if max_seq_len % piece or piece % 8:
            raise ValueError("max_seq_len must divide into prefill pieces "
                             "of a multiple of 8 positions")
        if not 0 < n_dense < n_layers:
            raise ValueError("leading dense layers and then expert layers")
        if first_expert + experts_held > n_experts or top_k > n_experts:
            raise ValueError(
                f"experts {first_expert}..{first_expert + experts_held} and "
                f"top {top_k} do not fit a router of {n_experts}")
        self.n_layers, self.n_dense = int(n_layers), int(n_dense)
        self.d_model, self.n_heads = int(d_model), int(n_heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim, self.d_ff = int(v_dim), int(d_ff)
        self.d_expert, self.n_experts = int(d_expert), int(n_experts)
        self.experts_held, self.first_expert = int(experts_held), int(
            first_expert)
        self.top_k, self.n_shared = int(top_k), int(n_shared)
        self.routed_scale = float(routed_scale)
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.piece = int(piece)
        self.dtype = str(dtype)
        self._seed = seed
        from client_tpu.ops.decode_kernel import latent_row_width

        self.row_width = latent_row_width(self.kv_rank, self.rope_dim)
        # Per head q and k are nope + rope wide; the flash kernel takes them
        # in whole 128-lane tiles (192 -> 256; narrower models as they are).
        qk = self.nope_dim + self.rope_dim
        self.qk_pad = -(-qk // 128) * 128 if qk > 128 else qk
        self.sm_scale = 1.0 / math.sqrt(qk)
        self.latent_attention = self.kv_rank
        self.prefill_piece = (self.piece, 1)

    # -- params --------------------------------------------------------------

    def _init_params(self):
        """Seeded weights as ``SeededWeight`` leaves (made, and rounded to
        bfloat16, when asked for).  Layers are a list: a leading dense layer
        has ``wgu``/``wd``, an expert layer the router (float32), the shared
        expert and the held experts' stacked ``egu [E, d, 2f]`` (gate | up)
        and ``ed [E, f, d]``."""
        d, h = self.d_model, self.n_heads
        count = iter(range(1 << 20))

        # A float32 model's weights are still rounded to bfloat16 values:
        # the same numbers in both forms of the program.
        def w(*shape, scale, offset=0.0, dtype=None, first=None):
            return SeededWeight((self._seed, next(count)), shape, scale,
                                offset, dtype or self.dtype, first)

        def mat(rows, cols):
            return w(rows, cols, scale=1.0 / math.sqrt(rows))

        def gain(n):
            return w(n, scale=0.1, offset=1.0)

        def layer(dense: bool):
            lp = {
                "ln1": gain(d), "ln2": gain(d), "ln3": gain(d),
                "ln4": gain(d),
                "wqa": mat(d, self.q_rank), "qln": gain(self.q_rank),
                # W_qb by its columns: every head's nope part, then every
                # head's rotary part (a matrix each, so no program splits
                # 192-wide heads out of one); W_kvb by head, as the absorbed
                # products contract it: k_nope_h = c wkb[h]^T, v_h = c wvb[h].
                "wqn": mat(self.q_rank, h * self.nope_dim),
                "wqr": mat(self.q_rank, h * self.rope_dim),
                "wkva": mat(d, self.kv_rank + self.rope_dim),
                "kvln": gain(self.kv_rank),
                "wkb": w(h, self.nope_dim, self.kv_rank,
                         scale=1.0 / math.sqrt(self.kv_rank)),
                "wvb": w(h, self.kv_rank, self.v_dim,
                         scale=1.0 / math.sqrt(self.kv_rank)),
                "wo": mat(h * self.v_dim, d),
            }
            if dense:
                lp["wgu"] = mat(d, 2 * self.d_ff)
                lp["wd"] = mat(self.d_ff, d)
                return lp
            f, fs = self.d_expert, self.d_expert * self.n_shared
            e = self.experts_held
            lp["router"] = w(d, self.n_experts, scale=1.0 / math.sqrt(d),
                             dtype="float32")
            lp["sgu"], lp["sd"] = mat(d, 2 * fs), mat(fs, d)
            lp["egu"] = w(e, d, 2 * f, scale=1.0 / math.sqrt(d),
                          first=self.first_expert)
            lp["ed"] = w(e, f, d, scale=1.0 / math.sqrt(f),
                         first=self.first_expert)
            return lp

        return {
            "embed": w(self.vocab, d, scale=1.0),
            "layers": [layer(i < self.n_dense)
                       for i in range(self.n_layers)],
            "lnf": gain(d),
            "head": mat(d, self.vocab),
        }

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

    def _queries_and_rows(self, lp, x, pos):
        """x ``[..., n, d]`` float32, pos ``[..., n]`` -> q_nope ``[..., n,
        H, nope]``, q_rope ``[..., n, H, rope]`` (rotated), c ``[..., n,
        kv_rank]`` (normed) and k_r ``[..., n, rope]`` (rotated), float32."""
        h = rms_norm(x, lp["ln1"], self.rms_eps)
        c_q = rms_norm(self._mm(h, lp["wqa"]), lp["qln"], self.rms_eps)
        heads = (*x.shape[:-1], self.n_heads, -1)
        q_nope = self._mm(c_q, lp["wqn"]).reshape(heads)
        q_rope = rope(self._mm(c_q, lp["wqr"]).reshape(heads), pos,
                      self.rope_theta)
        kv = self._mm(h, lp["wkva"])
        c = rms_norm(kv[..., :self.kv_rank], lp["kvln"], self.rms_eps)
        k_r = rope(kv[..., None, self.kv_rank:], pos, self.rope_theta)
        return q_nope, q_rope, c, k_r[..., 0, :]

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
        matmuls'."""
        import jax
        import jax.numpy as jnp

        logits = jnp.matmul(h, lp["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), self.top_k)
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

    def _after_rows(self, lp, h, o, live, tile_m):
        """The block behind its attention, for rows h ``[n, d]`` and their
        heads' outputs o ``[n, H * v_dim]`` -> (h, routing counts, choices):
        of a dense layer 0 and ``()``, of an expert layer its counts and
        ``(choices [n, k],)``."""
        import jax.numpy as jnp

        h = h + rms_norm(self._mm(o, lp["wo"]), lp["ln2"], self.rms_eps)
        f = rms_norm(h, lp["ln3"], self.rms_eps)
        if "wgu" in lp:
            y, counts, route = self._swiglu(f, lp["wgu"], lp["wd"]), 0, ()
        else:
            y, counts, top_i = self._experts(lp, f, live, tile_m)
            y, route = y + self._swiglu(f, lp["sgu"], lp["sd"]), (top_i,)
        return (h + rms_norm(y, lp["ln4"], self.rms_eps),
                jnp.asarray(counts, jnp.int32), route)

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

    def _walk_layers(self, p, body, carry):
        for li, lp in enumerate(p["layers"]):
            carry = body(carry, lp, li)
        return carry

    # -- full-context forward (no cache) ----------------------------------------

    def make_apply_params(self):
        """Full-context forward in the served precision, no cache, no
        pieces and nothing absorbed: logits of every position, and each
        expert layer's choices ``[layers, n, top_k]``.  Model-level entry for
        warm-up and diagnostics; serving goes through pieces and waves."""
        params = self.place_params(self.load_or_init_params(self._init_params))

        def apply(p, inputs):
            import jax
            import jax.numpy as jnp

            ids = inputs["INPUT_IDS"].astype("int32")
            n = ids.shape[0]
            pos = jnp.arange(n)
            live = jnp.ones(n, bool)
            causal = pos[None, :] <= pos[:, None]
            x = p["embed"][ids].astype(jnp.float32)
            routes = []
            for lp in p["layers"]:
                q_nope, q_rope, c, k_r = self._queries_and_rows(lp, x, pos)
                row = self._cache_rows_of(c, k_r, jnp.dtype(self.dtype))
                c_c = row[:, :self.kv_rank]
                k_r = row[:, self.kv_rank:self.kv_rank + self.rope_dim]
                k_nope, v = self._keys_values(lp, c_c)
                s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
                     + jnp.einsum("qhd,kd->hqk", q_rope,
                                  k_r.astype(jnp.float32))) * self.sm_scale
                s = jnp.where(causal[None], s, _NEG_INF)
                o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
                x, _, route = self._after_rows(lp, x, o.reshape(n, -1), live,
                                               TILE_M_PIECE)
                routes += route
            return {"logits": self._logits(p, x),
                    "routing": jnp.stack(routes)}

        return apply, params

    # -- generative interface (used by GenerativeScheduler) -------------------

    def init_arena(self, capacity: int):
        """``c [L, capacity + 1, max_seq_len, W]`` in the model's dtype (the
        +1 slot absorbs padded lanes) and ``tok [R]``, each slot's latest
        token on the device."""
        import jax.numpy as jnp

        shape = (self.n_layers, capacity + 1, self.max_seq_len,
                 self.row_width)
        return {"c": jnp.zeros(shape, jnp.dtype(self.dtype)),
                "tok": jnp.zeros(capacity + 1, jnp.int32)}

    def _piece_attention(self, lp, q_nope, q_rope, own, before):
        """One piece's attention, nothing absorbed: its queries against the
        keys and values of the ``before`` rows ahead of it ``[P, W]`` and,
        causally, of its ``own`` rows ``[n, W]`` (both as the cache holds
        them).  -> ``[n, H * v_dim]`` float32."""
        import jax
        import jax.numpy as jnp

        n, pre = own.shape[0], before.shape[0]
        rows = jnp.concatenate([before, own]) if pre else own
        c_c = rows[:, :self.kv_rank]
        k_r = rows[:, self.kv_rank:self.kv_rank + self.rope_dim]
        h, dq = self.n_heads, self.qk_pad
        k_nope, v = self._keys_values(lp, c_c)
        v = v.astype(rows.dtype)                              # [P+n, H, v]
        if self.attention_impl == "flash":
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

    def piece_hidden_fn(self):
        """(params, arena, rows[1], ids[1, piece], lens[1], starts[1]) ->
        (arena, x ``[piece, d]``, choices ``[expert layers, piece, top_k]``):
        one prefill piece, positions ``starts .. starts + lens`` of the
        lane's prompt (``starts`` a multiple of the piece)."""
        import jax
        import jax.numpy as jnp

        n, w = self.piece, self.row_width

        def piece(p, arena, rows, ids, lens, starts):
            row, start = rows[0], starts[0]
            pos = start + jnp.arange(n)
            live = jnp.arange(n) < lens[0]
            c_a = arena["c"]
            x = p["embed"][ids[0]].astype(jnp.float32)
            routes = []
            for li, lp in enumerate(p["layers"]):
                q_nope, q_rope, c, k_r = self._queries_and_rows(lp, x, pos)
                own = self._cache_rows_of(c, k_r, c_a.dtype)

                def attend(pre, lp=lp, li=li, q_nope=q_nope, q_rope=q_rope,
                           own=own, c_a=c_a):
                    before = jax.lax.dynamic_slice(
                        c_a, (li, row, 0, 0), (1, 1, pre, w))[0, 0]
                    return self._piece_attention(lp, q_nope, q_rope, own,
                                                 before)

                o = jax.lax.switch(
                    start // n,
                    [lambda pre=i * n: attend(pre)
                     for i in range(self.max_seq_len // n)])
                c_a = jax.lax.dynamic_update_slice(
                    c_a, own[None, None], (li, row, start, 0))
                x, _, route = self._after_rows(lp, x, o, live,
                                               TILE_M_PIECE)
                routes += route
            return {**arena, "c": c_a}, x, jnp.stack(routes)

        return piece

    def prefill_fn(self):
        """``PREFILL_ARGS`` -> (arena, tokens[1]): one **piece** of the
        lane's prompt; the token sampled after its last valid position lands
        in the slot's device-side token, and means something for a prompt's
        last piece only."""
        piece = self.piece_hidden_fn()

        def prefill(p, arena, rows, ids, lens, seeds, temps, top_ks, top_ps,
                    sample, starts):
            arena, x, _ = piece(p, arena, rows, ids, lens, starts)
            logits = self._logits(p, x[lens - 1])
            return sample_into_slots(arena, rows, logits, seeds,
                                     starts + lens, temps, top_ks, top_ps,
                                     sample)

        return prefill
