"""The served decoder: what the generative scheduler asks of a model, and the
one decode step every such model runs.

``engine/generative.py`` knows no model.  It builds its programs from a
:class:`DecoderBackend` and reads the contract below, every member of which has
a documented default here; a model file supplies its parts once and the rest
(the kernel-or-oracle choice, the decode-step frame, the piece frame, the pass
axis of both, the chunked step, the sampling tail, the decoupled
``ModelConfig``) is written in this module.

**The contract the scheduler reads**

- ``max_streams``, ``max_seq_len``, ``vocab``, ``default_max_tokens``.
- ``init_arena(capacity)``: the cache pytree ``{"k", "v": [L, R, S, H*D],
  "tok": [R]}``, donated into every program.  What a slot's ``S`` rows hold is
  the model's business, and so is which leaves there are: ``cache_leaves``
  names those a decode step carries through its layers (``("k", "v")``; a
  latent cache has one, ``("c",)``, ``[L, R, S, W]``).
- **Layer kinds.**  ``layer_kinds``: ``None`` (every layer reads rows: its
  leaves are ``cache_leaves``, indexed by the layer's number), or one entry a
  layer, ``"rows"`` or ``"state"``.  A ``"state"`` layer keeps no row a
  position: it owns a fixed-size state a slot, advanced in place by every
  step (``state_leaves``, e.g. ``("s", "conv")``: ``[layers of the kind, R,
  ...]``).  A leaf's leading axis counts **the layers of its kind**, and the
  frame hands a layer the leaves of its kind and its index among them
  (``_layer_kind``).  Rows left in a slot by its last stream are masked by
  ``lens``; a state is not, so the first piece of a prompt (``starts == 0``)
  starts from zeros, and padded lanes (on the junk slot) and padded positions
  leave every live slot's state as it was.  A ``"ring"`` layer reads rows
  from leaves of **another length** (``ring_leaves``, e.g. ``("kw", "vw")``:
  ``[layers of the kind, R, ring rows, Hkv*D]`` beside ``cache_leaves``' ``[..,
  R, S, Hkv*D]``: one arena, two row shapes): a slot's ``ring rows`` hold its
  last that many positions, position n at row ``n mod ring rows`` (a
  sliding-window layer).  A step at context length ``lens`` writes row
  ``lens mod ring rows`` and reads the ``min(lens, ring rows)`` live rows but
  the one it overwrites (ops/decode_kernel.py ``window_wave_attention``); the
  write position and the live rows are the kind's, ``_live_rows`` is the
  ``"rows"`` kind's alone.  ``ring_window``: ``None`` (a step sees as many
  keys as the ring has rows), or fewer.  The order of a ring's rows means
  nothing to the softmax: a model rotates a key before it is written
  (``_ring_qkv``).
- **Passes.**  ``passes``: ``1``, or how many times a step runs the layer
  stack over the **one** list of weights ``p["layers"]`` (models/ouro.py: a
  looped model), with ``_between_passes(p, x)`` -> x between two passes
  (default: nothing).  Every pass keeps a cache of its own: a leaf's leading
  axis counts ``passes x (layers of its kind)``, pass ``t``'s entries behind
  those of the passes before it, and ``_layer_kind`` gives the frames a
  layer's index at pass ``t`` as ``t x (layers of the kind) + its index among
  them``; ``init_arena`` sizes the leaves so, the weights stay one set.  The
  scheduler reads ``passes`` for its counter ``fetched_passes`` and learns
  nothing else of it.
- ``wave_stats``: ``()``, or names of ``spans.GEN_COUNTERS`` that only the
  device can count (what a wave's tokens were routed to): the decode program
  then returns that many int32 behind its ``B`` tokens (``_wave_stats(x)``),
  and the scheduler adds them to the counters when the wave's tokens arrive.
- ``stream_record``: ``0``, or how many int32 a position the programs leave
  behind their tokens for a stream that asks for its record (request
  parameter ``record``; needs ``prefill_piece``): the decode program returns
  ``[B tokens | B x stream_record | wave_stats]`` (``_record(x, logits,
  tokens)`` -> ``[B, stream_record]``) and a piece ``[lanes tokens | lanes x
  positions x stream_record]``.  What the ints say is the backend's
  (models/latent_moe.py: which held experts each expert layer chose, and a
  few of the logits the token was chosen from); the scheduler hands a stream
  its positions' rows with its final response (``RECORD``).
- ``arena_rows(capacity)`` -> (free rows, dummy row) and ``kv_shards`` (1).
- ``prefill_piece``: ``None`` (a whole prompt a lane, one program a prompt
  bucket) or ``(positions, lanes)`` (a prompt is consumed ``positions`` a
  piece; ``prefill_fn()`` is called with every power of two of lanes up to
  ``lanes``, a program each: the one that holds the prompts in line).  The
  piece program is written here too (``piece_hidden_fn``, ``prefill_fn``:
  **the piece's frame**, beside the wave's): a backend supplies a mixer a
  kind, ``_piece_rows_layer`` | ``_piece_ring_layer`` |
  ``_piece_state_layer``, and nothing else where its piece carries
  activations alone.
- ``piece_ends``: ``True`` (the piece program is the frame's: it takes the
  trailing ``ends`` and computes its head only where a lane ends its prompt)
  or ``False`` (a backend that writes a piece program of its own and runs
  its head in every piece: models/evabyte.py).  Read with ``prefill_piece``
  alone.
- ``piece_wave``: ``False``, or ``True`` where **every piece program carries
  a decode wave** of ``B = max_streams`` lanes (the top wave bucket).  The
  program then takes one more operand behind ``ends``, ``wave = (rows, lens,
  seeds, temps, top_ks, top_ps)``, each ``[B]`` as ``DECODE_ARGS`` has them
  (a lane that holds no stream on the junk slot at length 0; ``sample`` is
  the one flag of both), and returns ``[the piece's part | the wave's part]``,
  each as its own program leaves it (``[L | L x piece x stream_record]``,
  then ``[B | B x stream_record | wave_stats]``): where a token gap holds a
  piece, the scheduler dispatches this one program and the decoding lanes'
  next token comes out of the piece's pass over the weights.  The frame is
  written here (``piece_hidden_fn``, ``prefill_fn``) for the layer kinds
  ``"rows"`` (grouped-query rows in two leaves, or a latent cache's one:
  the wave's step is ``_decode_attend``'s either way), ``"ring"``,
  ``"state"`` and ``"none"``; a backend that declares it supplies mixers
  that take the wave's rows behind the piece's (``_piece_rows_layer(...,
  wave)``, models/grouped_query.py and, for a latent cache,
  models/latent_moe.py; ``_piece_state_layer(..., wave)``,
  models/state_layer.py) and a trail that counts the wave's rows apart
  (``_piece_end``, models/experts.py).
  Read with ``piece_ends`` alone; a scheduler dispatches such a backend's
  waves one at a time and refuses one that declares transitions too.
- ``attn_scale``: ``None`` (a ``"rows"`` layer's scores are scaled by ``1 /
  sqrt(head_dim)``) or the number a model scales them by (a published
  ``attention_multiplier``): the wave's kernel and its oracle take it
  (ops/decode_kernel.py ``sm_scale``), and so does a piece's attention
  (models/grouped_query.py).  One chip's whole-context rows alone: with a
  ring, a latent cache or ``kv_shards > 1`` the step is refused at build.
- ``cache_rows``: ``None`` (a step reads one row a position) or ``(n) ->
  (summary rows, exact rows)`` a step at context length ``n`` reads.
- ``cache_rows_by_kind``: ``None``, or ``(n) -> (ring rows, other rows, past)``:
  what a step at context length ``n`` reads from its ``"ring"`` layers and from
  its ``"rows"`` layers, each summed over the layers of the kind, and whether
  the context has outgrown the ring (1 | 0): the scheduler's counters
  ``fetched_rows_window``, ``fetched_rows_global``,
  ``fetched_lanes_past_window``.
- ``piece_pairs_by_kind``: ``None``, or ``(start, valid) -> (ring pairs, other
  pairs)``: the (query, key) pairs that a lane's piece of ``valid`` positions
  from ``start`` scores in its ``"ring"`` layers and in its ``"rows"`` layers,
  each summed over the layers of the kind: the scheduler's counters
  ``prefill_pairs_window``, ``prefill_pairs_global`` (what a piece's attention
  costs; read with ``prefill_piece`` alone).
- ``transition_due`` / ``transition_fn``: ``None``, or ``(n) -> bool`` and the
  builder of ``(params, arena, rows[T], lens[T]) -> arena``, ordered before the
  wave of a stream that decoded its way to a due length.
- The programs, by their positional arguments (``PREFILL_ARGS``,
  ``DECODE_ARGS``, ``DECODE_CHUNK_ARGS``): ``prefill_fn()`` -> (arena,
  tokens[B]) takes the trailing ``starts`` only with ``prefill_piece``, and
  behind it ``ends`` (``[L]`` int32, traced: 1 where the lane's piece is its
  prompt's last, 0 elsewhere and on padded lanes; the scheduler knows it and
  the program cannot, since a last piece may be full) only with
  ``piece_ends`` too, and behind that ``wave`` only with ``piece_wave``;
  ``decode_fn()`` -> (arena, tokens[B]); ``decode_chunk_fn()`` -> (arena,
  tokens[k, B]).  ``sample`` (and the chunk's ``k``) are static, the arena is
  donated: ``*_static_argnums`` and ``donate_argnums`` say so by position.

**The parts a model supplies** (``B`` lanes of a wave; ``lp`` one layer's
weights; ``li`` its index, a Python int; ``x`` the model's
own carry between layers: activations ``[B, d]``, or a pytree where a layer
hands on more than those, as models/pangu_moe.py's routing counts):
``_embed(p, tokens, pos)`` -> x; ``_qkv(lp, x, pos)`` -> q, k, v ``[B, H,
D]`` (k and v ``[B, Hkv, D]`` where the rows are grouped-query rows; a
``"ring"`` layer's come from ``_ring_qkv``, by default the same) or, with
``latent_attention = value lanes`` declared (one shared row a
position, ops/decode_kernel.py ``latent_wave_attention``), the absorbed
query as the kernel takes it, ``[B, W, H]`` in the cache's dtype with the
score scale in it, and the new row ``[B, W]``, and ``_attention_output(lp,
o)`` taking the rows' weighted sum as the kernel leaves it, ``[B, value
lanes, H]`` float32, to what ``_after_attention`` reads (heads along the
minor axis on both sides of the kernel: a model's einsums write and read
that layout);
``_after_attention(lp, x, o)`` -> x;
where ``layer_kinds`` names ``"state"`` layers, ``_advance(lp, x, *state
leaves, rows, lens, index)`` -> (*state leaves, o): the layer's mixer on the
slots' states (models/state_layer.py's, around the recurrence a model names;
it chooses kernel or oracle by ``_use_kernel()``), ``o`` what
``_after_attention`` reads; where it names ``"none"`` layers,
``_feed_forward(lp, x)`` -> x, the whole of such a layer;
``_logits(p, x)`` and,
where they are not ``[B, vocab]``, ``_served(logits)`` picking those tokens
are sampled from; ``_live_rows(lens)``, the rows of each slot a step at
context length ``lens`` may read (default: ``lens``).  The layers are walked
by ``_walk_layers(p, body, carry)``, written here: ``body(carry, lp, li)``
over ``passes`` x ``p["layers"]``, a list of one tree of leaves a layer, in a
Python loop (``li`` a Python int, the body's place in the walk: ``pass x
layers + the layer's number``).  A weight is then a parameter of the program, read by
its product where it lies; a ``lax.scan`` over stacked leaves made the
compiler write out and re-lay a layer's slice every iteration (PERF.md
section 6, PR 42), and no served model overrides the loop.
"""

from __future__ import annotations

import functools

import numpy as np

from client_tpu.engine.config import ModelConfig, TensorConfig
from client_tpu.engine.model import ModelBackend

PREFILL_ARGS = ("params", "arena", "rows", "ids", "lens", "seeds", "temps",
                "top_ks", "top_ps", "sample", "starts", "ends", "wave")
DECODE_ARGS = ("params", "arena", "rows", "lens", "seeds", "temps", "top_ks",
               "top_ps", "sample")
DECODE_CHUNK_ARGS = DECODE_ARGS + ("k",)


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


def choose_tokens(logits, seeds, ctx, temps, top_ks, top_ps, sample):
    """tokens[B]: each lane's token chosen from ``logits[b]`` at context
    length ``ctx[b]``.  ``sample`` is STATIC: an all-greedy program compiles
    without the sort/cumsum/PRNG pipeline (``jnp.where`` alone would keep
    both)."""
    import jax
    import jax.numpy as jnp

    if sample:
        return jax.vmap(_sample_token)(logits, seeds, ctx, temps, top_ks,
                                       top_ps)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def tokens_into_slots(arena, rows, tokens):
    """The arena with each lane's token left in its slot's device-side
    token, where the next wave finds it without the host."""
    return {**arena, "tok": arena["tok"].at[rows].set(tokens)}


def sample_into_slots(arena, rows, logits, seeds, ctx, temps, top_ks, top_ps,
                      sample):
    """The tail of every program that emits a token: (arena, tokens[B]),
    ``choose_tokens`` and ``tokens_into_slots`` as one (the piece's frame
    calls the pair apart: its choice stands under a conditional, the write
    does not)."""
    tokens = choose_tokens(logits, seeds, ctx, temps, top_ks, top_ps, sample)
    return tokens_into_slots(arena, rows, tokens), tokens


def slot_tails(leaf, ki, rows):
    """A wave's lanes' rows of a fixed-size leaf ``[layers of the kind, R,
    width]`` (a slot's convolution tail): -> (pick ``[B, R]``, the layer's
    slots ``[R, width]``, the lanes' rows ``[B, width]``).

    The rows leave and enter the leaf through a one-hot product, lanes by
    slots (exact: a row of it holds one 1, and a bfloat16 value times 1
    summed in float32 is the value): XLA lowers a gather and a scatter of 256
    rows of such a leaf to loops of 256 slices, 2.2 ms a layer on the v5e
    where the product and one pass over the layer's slots take a tenth of
    that (PERF.md section 6, PR 34)."""
    import jax
    import jax.numpy as jnp

    slots = leaf[ki]                                           # [R, width]
    pick = (rows[:, None] == jnp.arange(slots.shape[0])[None, :]
            ).astype(leaf.dtype)                               # [B, R]
    tail = jnp.matmul(pick, slots, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(leaf.dtype)
    return pick, slots, tail


def put_slot_tails(leaf, ki, pick, slots, ext):
    """``slot_tails``' way back.  ``ext [B, taps, channels]``: each lane's
    tail with this step's input behind it; all but the oldest go back into
    the lane's slot of the layer.  A slot that several lanes name (the junk
    slot) is left their sum."""
    import jax
    import jax.numpy as jnp

    put = jnp.matmul(pick.T, ext[:, 1:].reshape(ext.shape[0], -1),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32).astype(leaf.dtype)
    slots = jnp.where(pick.any(axis=0)[:, None], put, slots)
    return jax.lax.dynamic_update_slice(leaf, slots[None], (ki, 0, 0))


# Logits of a row's first ids in a stream's record, beside its token's.
RECORD_LOGITS = 8


def record_width(words: int = 0) -> int:
    """int32 a position of a stream's record: a model's own ``words`` (an
    expert model's routing), then the ``1 + RECORD_LOGITS`` logits."""
    return words + 1 + RECORD_LOGITS


def logit_bits(logits, tokens, samples: int):
    """``[B, 1 + samples]`` int32: the float32 bits of each lane's logit of
    its token and of the row's first ``samples`` ids (a fixed sample of the
    row: what a comparison weighs a program's precision by); the form that
    rides behind int32 tokens in a ``stream_record``."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    at = jnp.take_along_axis(logits, tokens[:, None], axis=-1)
    return jax.lax.bitcast_convert_type(
        jnp.concatenate([at, logits[:, :samples]], axis=-1), jnp.int32)


class DecoderBackend(ModelBackend):
    """A decoder served token by token: INPUT_IDS [-1] -> streamed (TOKEN,
    INDEX) responses, ended by an empty ``triton_final_response`` like every
    decoupled model here.  ``max_tokens`` bounds a request's generation."""

    generative = True

    prefill_piece: tuple[int, int] | None = None
    piece_ends = True
    piece_wave = False
    passes = 1
    cache_leaves: tuple[str, ...] = ("k", "v")
    layer_kinds: tuple[str, ...] | None = None
    state_leaves: tuple[str, ...] = ()
    ring_leaves: tuple[str, ...] = ()
    ring_window: int | None = None
    latent_attention: int | None = None
    attn_scale: float | None = None
    wave_stats: tuple[str, ...] = ()
    stream_record = 0
    cache_rows = None
    cache_rows_by_kind = None
    piece_pairs_by_kind = None
    transition_due = None
    transition_fn = None
    donate_argnums = (PREFILL_ARGS.index("arena"),)
    prefill_static_argnums = (PREFILL_ARGS.index("sample"),)
    decode_static_argnums = (DECODE_ARGS.index("sample"),)
    decode_chunk_static_argnums = (DECODE_CHUNK_ARGS.index("sample"),
                                   DECODE_CHUNK_ARGS.index("k"))

    def __init__(self, name: str, *, vocab: int, max_seq_len: int,
                 max_streams: int, attention_impl: str,
                 attn_impl: str | None, kv_shards: int = 1):
        # Prefill's attention.  "einsum": XLA-scheduled O(S^2) scores, right
        # for short prompts; "flash": the Pallas kernel (causal).
        if attention_impl not in ("einsum", "flash"):
            # A silent fallback would serve the quadratic path at 2048+ —
            # the cliff the option exists to avoid.
            raise ValueError(
                f"attention_impl must be 'einsum' or 'flash', got "
                f"{attention_impl!r}")
        self.attention_impl = attention_impl
        # The decode wave.  "fused": the Pallas kernel (ops/decode_kernel.py),
        # one row written in place and each live row read once.  "reference":
        # the XLA oracle next to it (scatter, gather, dense masked softmax) on
        # the same arena: same math, same sampling sequence, token-identical
        # streams; the parity tests and chip_smoke's phase B serve it, and so
        # do the GSPMD-sharded families (parallel/serving.py), whose programs
        # XLA has to partition.  Unset, the platform decides (`_use_kernel`).
        if attn_impl not in (None, "", "reference", "fused"):
            raise ValueError(
                f"attn_impl must be 'reference' or 'fused', got "
                f"{attn_impl!r}")
        self.attn_impl = attn_impl or ""
        # KV arena shards over a "kv" mesh axis (parallel/kv_shard.py); the
        # row-sharded layout and the shard_map'd kernel go together.
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
        # Kernel knobs: key-block tile (None = auto divisor of a slot's
        # rows) and the cross-shard combine ("ring" remote-DMA kernel |
        # "psum" XLA collective).
        self.decode_block_s: int | None = None
        self.kv_combine = "ring"
        self._kv_mesh = None
        self.vocab, self.max_seq_len = int(vocab), int(max_seq_len)
        self.max_streams = int(max_streams)
        self.default_max_tokens = 16
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

    def place_params(self, params):
        """Device placement hook; sharded variants override with
        per-tensor NamedShardings (parallel/serving.py)."""
        import jax

        return jax.device_put(params)

    # -- the arena's rows -----------------------------------------------------

    def arena_rows(self, capacity: int | None = None):
        """(free_rows, dummy_row) of the arena this backend builds: which
        rows the scheduler may hand to streams, and the junk row padded
        lanes point at.  Single-chip: rows 0..cap-1 plus the trailing
        dummy; sharded: one junk row per shard (parallel/kv_shard.py), so
        the free list is non-contiguous and the scheduler must not assume
        ``row == lane`` arithmetic."""
        cap = self.max_streams if capacity is None else int(capacity)
        if self.kv_shards == 1:
            return list(range(cap)), cap
        from client_tpu.parallel.kv_shard import arena_row_layout

        _total, free, dummy = arena_row_layout(cap, self.kv_shards)
        return free, dummy

    def _mesh(self):
        if self._kv_mesh is None:
            from client_tpu.parallel.kv_shard import kv_mesh

            self._kv_mesh = kv_mesh(self.kv_shards)
        return self._kv_mesh

    def _live_rows(self, lens):
        return lens

    def _served(self, logits):
        return logits

    def _between_passes(self, p, x):
        return x

    def _walk_layers(self, p, body, carry):
        """``body(carry, lp, li)`` over ``passes`` x ``p["layers"]``, ``li``
        the body's place in the walk (``pass x layers + the layer's
        number``: ``_layer_kind`` tells the pass from it).  Between two
        passes the head of a carry ``(x, ...)`` goes through
        ``_between_passes``."""
        layers = p["layers"]
        for t in range(self.passes):
            if t:
                carry = (self._between_passes(p, carry[0]), *carry[1:])
            for li, lp in enumerate(layers):
                carry = body(carry, lp, t * len(layers) + li)
        return carry

    # -- kernel or oracle -----------------------------------------------------

    def _use_kernel(self) -> bool:
        """Whether the arena is the Pallas kernels' (the decode wave and
        prefill's write): by ``attn_impl``, or unset wherever Mosaic
        compiles them (a TPU) and not where Pallas would only be
        interpreted."""
        from client_tpu.engine.backend_init import pallas_interpret

        return self.attn_impl == "fused" or (
            not self.attn_impl and not pallas_interpret())

    def _decode_attend(self, ring: bool = False):
        """``attend(k_arena, v_arena, q, k, v, rows, live, layer)`` ->
        (k_arena, v_arena, o): one layer of a wave.  Lane b's new ``k, v``
        ``[B, H, D]`` go to row ``live[b]`` of slot ``rows[b]`` and ``q``
        reads rows ``0 .. live[b]``.  The kernel is one Pallas grid over the
        donated arena (with ``kv_shards > 1`` its shard_map form over the
        row-sharded arena); ``layer`` may be traced except over shards (no
        served decoder hands one over since PR 42, when the last ``scan``
        over layers went: ROADMAP Queue C).  With ``latent_attention``
        declared: ``attend(c_arena, q, new_row, rows, live, layer)`` ->
        (c_arena, o), the one leaf's kernel or its oracle.  With ``ring``:
        the ``"ring"`` kind's, over ``ring_leaves`` (``live`` is then the
        context length: the write position and the live rows follow from
        it and the leaf's length)."""
        from client_tpu.engine.backend_init import pallas_interpret
        from client_tpu.ops.decode_kernel import (decode_wave_attention,
                                                  latent_wave_attention,
                                                  reference_decode_attention,
                                                  reference_latent_attention,
                                                  window_wave_attention)

        interpret, block_s = pallas_interpret(), self.decode_block_s
        if self.attn_scale is not None and (
                ring or self.latent_attention is not None
                or self.kv_shards > 1):
            raise NotImplementedError(
                "attn_scale is taken by one chip's whole-context rows (not "
                "by a ring, a latent cache or kv_shards > 1)")
        if ring:
            if self.kv_shards > 1:
                raise NotImplementedError(
                    "a ring of rows is one chip's (kv_shards > 1)")
            kernel, window = self._use_kernel(), self.ring_window

            def attend(k_a, v_a, q, k, v, rows, lens, layer):
                if not kernel:
                    return reference_decode_attention(
                        k_a, v_a, q, k, v, rows, lens, layer=layer,
                        ring=True, window=window)
                return window_wave_attention(
                    k_a, v_a, q, k, v, rows, lens, layer=layer,
                    block_s=block_s, interpret=interpret, window=window)
        elif self.latent_attention is not None:
            value_dim = self.latent_attention
            if self.kv_shards > 1:
                raise NotImplementedError(
                    "a latent cache has one key/value head: there is "
                    "nothing to shard by rows' heads (kv_shards > 1)")
            kernel = self._use_kernel()

            def attend(c_a, q, new, rows, live, layer):
                if not kernel:
                    return reference_latent_attention(
                        c_a, q, new, rows, live, layer=layer,
                        value_dim=value_dim)
                static = isinstance(layer, int)
                return latent_wave_attention(
                    c_a, q, new, rows, live, value_dim=value_dim,
                    layer=layer if static else None,
                    layer_index=None if static else layer,
                    block_s=block_s, interpret=interpret)
        elif not self._use_kernel():
            scale = self.attn_scale

            def attend(k_a, v_a, q, k, v, rows, live, layer):
                return reference_decode_attention(
                    k_a, v_a, q, k, v, rows, live, layer=layer,
                    sm_scale=scale)
        elif self.kv_shards > 1:
            from client_tpu.parallel.kv_shard import \
                sharded_decode_attention

            mesh, combine = self._mesh(), self.kv_combine

            def attend(k_a, v_a, q, k, v, rows, live, layer):
                return sharded_decode_attention(
                    mesh, k_a, v_a, q, k, v, rows, live, layer=layer,
                    block_s=block_s, interpret=interpret, combine=combine)
        else:
            scale = self.attn_scale

            def attend(k_a, v_a, q, k, v, rows, live, layer):
                static = isinstance(layer, int)
                return decode_wave_attention(
                    k_a, v_a, q, k, v, rows, live,
                    layer=layer if static else None,
                    layer_index=None if static else layer,
                    block_s=block_s, interpret=interpret, sm_scale=scale)

        return attend

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

    # -- the decode step ------------------------------------------------------

    def _layer_kind(self, li):
        """(kind, index into the kind's leaves) of the walk's body ``li``
        (``pass x layers + the layer's number``): pass ``t``'s entries lie
        behind those of the passes before it, ``t x (layers of the kind) +
        the layer's index among them``."""
        if self.layer_kinds is None:
            return "rows", li
        t, li = divmod(li, len(self.layer_kinds))
        kind = self.layer_kinds[li]
        return kind, (t * self.layer_kinds.count(kind)
                      + self.layer_kinds[:li].count(kind))

    def _attention_output(self, lp, o):
        return o

    def _ring_qkv(self, lp, x, pos):
        return self._qkv(lp, x, pos)

    def _feed_forward(self, lp, x):
        raise NotImplementedError

    def _wave_stats(self, x):
        raise NotImplementedError

    def _record(self, x, logits, tokens):
        return logit_bits(logits, tokens, RECORD_LOGITS)

    def _decode_hidden_fn(self):
        """(params, arena, rows[B], lens[B]) -> (arena, x after the last
        layer).  One decode step: each lane's input token is GATHERED from
        its slot's device-side token (written by prefill / the previous
        wave), its position is its context length ``lens[b]``; each layer
        writes the lane's new cache row behind the slot's live rows and
        reads them all (``_decode_attend``), whatever leaves the cache has
        (``cache_leaves``), does so in the slot's ring (``ring_leaves``),
        advances the slot's state in place (``_advance`` on
        ``state_leaves``), or touches no leaf at all (``_feed_forward``), by
        the layer's kind."""
        attend = self._decode_attend()
        around = self._decode_attend(ring=True) if self.ring_leaves else None
        held = len(self.cache_leaves)
        first_state = held + len(self.ring_leaves)
        names = self.cache_leaves + self.ring_leaves + self.state_leaves

        def step(p, arena, rows, lens):
            live = self._live_rows(lens)
            tokens = arena["tok"][rows]

            def layer(carry, lp, li):
                x, *leaves = carry
                cache, ring, state = (leaves[:held], leaves[held:first_state],
                                      leaves[first_state:])
                kind, ki = self._layer_kind(li)
                if kind == "none":
                    return (self._feed_forward(lp, x), *leaves)
                if kind == "rows":
                    *cache, o = attend(*cache, *self._qkv(lp, x, lens), rows,
                                       live, ki)
                    o = self._attention_output(lp, o)
                elif kind == "ring":
                    *ring, o = around(*ring, *self._ring_qkv(lp, x, lens),
                                      rows, lens, ki)
                    o = self._attention_output(lp, o)
                else:
                    *state, o = self._advance(lp, x, *state, rows, lens, ki)
                return (self._after_attention(lp, x, o), *cache, *ring,
                        *state)

            x, *leaves = self._walk_layers(
                p, layer, (self._embed(p, tokens, lens),
                           *(arena[name] for name in names)))
            return {**arena, **dict(zip(names, leaves))}, x

        return step

    def decode_logits_fn(self):
        """``_decode_hidden_fn`` and the logits as ``_logits`` leaves
        them: (params, arena, rows[B], lens[B]) -> (arena, logits)."""
        hidden = self._decode_hidden_fn()

        def step(p, arena, rows, lens):
            arena, x = hidden(p, arena, rows, lens)
            return arena, self._logits(p, x)

        return step

    def decode_fn(self):
        """``DECODE_ARGS`` -> (arena, next[B]): ``decode_logits_fn`` and the
        sampled (or greedy) next token, written back to the slots, so
        consecutive waves chain on the device with no host round trip — the
        scheduler dispatches waves ahead and fetches tokens asynchronously.
        The context at sampling is ``lens + 1`` (the token just written
        occupies position ``lens``): prefill's fold sequence, continued.
        A backend that declares ``stream_record`` or ``wave_stats`` returns
        them behind the tokens, ``[B | B x stream_record | wave_stats]``: one
        fetch brings all."""
        import jax.numpy as jnp

        hidden = self._decode_hidden_fn()

        def decode(p, arena, rows, lens, seeds, temps, top_ks, top_ps,
                   sample=True):
            arena, x = hidden(p, arena, rows, lens)
            logits = self._served(self._logits(p, x))
            arena, tokens = sample_into_slots(
                arena, rows, logits, seeds, lens + 1, temps, top_ks, top_ps,
                sample)
            behind = []
            if self.stream_record:
                behind.append(self._record(x, logits, tokens).reshape(-1))
            if self.wave_stats:
                behind.append(self._wave_stats(x).astype(tokens.dtype))
            if behind:
                tokens = jnp.concatenate([tokens, *behind])
            return arena, tokens

        return decode

    # -- the prefill piece ----------------------------------------------------

    def _piece_start(self, p, ids, pos, live, riders: int = 0):
        return self._embed(p, ids, pos), None

    def _piece_after(self, lp, x, o, trail):
        return self._after_attention(lp, x, o), trail

    def _piece_block(self, lp, x, trail):
        return self._feed_forward(lp, x), trail

    def _piece_end(self, trail):
        """(what the program hands on, the ``wave_stats`` of the wave that
        rode or ``None``)."""
        return trail, None

    def _piece_words(self, trail):
        return []

    def _walk_kinds(self, p, x, trail, mixer):
        """x ``[n, d]`` through ``passes`` x the layers by their kinds:
        ``mixer(kind, ki, lp, x)`` -> o for a layer that has one, then
        ``_piece_after``; a ``"none"`` layer is ``_piece_block``.  -> (x,
        what ``_piece_end`` makes of the trail, a riding wave's counts)."""
        def layer(carry, lp, li):
            x, trail = carry
            kind, ki = self._layer_kind(li)
            if kind == "none":
                return self._piece_block(lp, x, trail)
            return self._piece_after(lp, x, mixer(kind, ki, lp, x), trail)

        x, trail = self._walk_layers(p, layer, (x, trail))
        return (x, *self._piece_end(trail))

    def piece_hidden_fn(self):
        """(params, arena, rows[L], ids[L, piece], lens[L], starts[L]) ->
        (arena, x ``[L * piece, d]``, the piece's trail), lane after lane:
        one prefill piece of each of ``L`` prompts (any ``L`` up to what
        ``prefill_piece`` declares), positions ``starts .. starts + lens`` of
        a lane's prompt (``starts`` a multiple of the piece).  **The piece's
        frame**, as ``_decode_hidden_fn`` is the wave's: ``passes`` x the
        layers, a layer gets the leaves of its kind and its index into them
        (``_layer_kind``: pass ``t``'s piece reads and writes pass ``t``'s
        rows).  Whatever is a matrix product over positions sees all lanes'
        positions as one batch, so a weight is read once a program and pass;
        a mixer runs a lane at a time, each from its own slot.  A backend
        supplies a part for each kind it declares, ``_piece_rows_layer``,
        ``_piece_ring_layer``, ``_piece_state_layer`` ``(lp, *the kind's
        leaves, ki, rows, starts, lens, x, pos)`` -> (*leaves, o ``[L * piece,
        *]``).  What follows a mixer is the wave's ``_after_attention`` on
        the piece's rows, a ``"none"`` layer its ``_feed_forward``, the
        first x its ``_embed``; a backend whose piece carries more than
        activations (models/experts.py: which lanes are live, the routing's
        choices) says so through ``_piece_start(p, ids, pos, live)`` -> (x,
        trail), ``_piece_after(lp, x, o, trail)`` and ``_piece_block(lp, x,
        trail)`` -> (x, trail), ``_piece_end(trail)`` -> what the program
        hands on, and ``_piece_words(that)`` -> the record's leading
        columns.

        **With a wave** (``piece_wave``; ``wave = (rows[B], lens[B])`` behind
        ``starts``): the wave's ``B`` rows stand behind the piece's ``L *
        piece`` in x all the way, each lane's input token gathered from its
        slot and its position its length, as ``_decode_hidden_fn`` takes
        them.  A mixer projects all rows as one batch and gets, behind
        ``pos``, the wave's own step of its kind for the rows behind the
        piece's (``_decode_attend``, over two leaves or a latent cache's
        one, the wave's rows, its live rows or
        lengths; a ``"state"`` layer the wave's rows and lengths, its step
        being its own, models/state_layer.py ``_step_slots``); every other
        product sees the rows as one batch, and
        ``_piece_start`` is told how many of its rows are a wave's
        (``riders``).  The slots of the two are disjoint: a stream prefills
        or decodes."""
        import jax.numpy as jnp

        n = self.prefill_piece[0]
        leaves_of = {"rows": self.cache_leaves, "ring": self.ring_leaves,
                     "state": self.state_leaves}
        steps = {}
        if self.piece_wave:
            steps["rows"] = self._decode_attend()
            if self.ring_leaves:
                steps["ring"] = self._decode_attend(ring=True)

        def piece(p, arena, rows, ids, lens, starts, wave=None):
            at = jnp.arange(n)
            live = (at < lens[:, None]).reshape(-1)
            pos = (starts[:, None] + at).reshape(-1)
            ids, riding, start = ids.reshape(-1), {}, ()
            if wave is not None:
                w_rows, w_lens = wave
                ids = jnp.concatenate([ids, arena["tok"][w_rows]])
                live = jnp.concatenate([live, w_lens > 0])
                pos = jnp.concatenate([pos, w_lens])
                riding = {"rows": ((steps["rows"], w_rows,
                                    self._live_rows(w_lens)),),
                          "ring": ((steps.get("ring"), w_rows, w_lens),),
                          "state": ((w_rows, w_lens),)}
                start = (w_rows.shape[0],)
            arena = dict(arena)

            def mixer(kind, ki, lp, x):
                names = leaves_of[kind]
                *leaves, o = getattr(self, f"_piece_{kind}_layer")(
                    lp, *(arena[name] for name in names), ki, rows, starts,
                    lens, x, pos, *riding.get(kind, ()))
                arena.update(zip(names, leaves))
                return o

            x, trail, stats = self._walk_kinds(
                p, *self._piece_start(p, ids, pos, live, *start), mixer)
            return (arena, x, trail) + (() if wave is None else (stats,))

        return piece

    def prefill_fn(self):
        """``PREFILL_ARGS`` -> (arena, tokens[L]): one **piece** of each
        lane's prompt (``piece_hidden_fn``); the token sampled after a lane's
        last valid position lands in its slot's device-side token, and means
        something for a prompt's last piece only.  So **the head runs only
        where a prompt ends**: ``_logits``, ``_served``, the token choice and
        the record's logit bits stand under one ``lax.cond`` on "some lane's
        ``ends`` is set", and a program in which no lane ends reads no row of
        the vocabulary's matrix and leaves zeros for tokens and bits (which
        nothing reads: such a lane's fetch carries no stream).  A lane that
        goes on beside one that ends gets a token as it always did.  The
        arena does not pass through the conditional: the write of the tokens
        into the slots stands behind it (a donated leaf carried through a
        branch is a leaf the compiler may copy).  With ``stream_record`` the
        pieces' rows of the record follow the tokens, ``[L + L x piece x
        stream_record]``: a model's own words (``_piece_words``) in every
        piece, then the logits' bits in the row of a lane's last valid
        position.  (A backend that declares no ``prefill_piece`` writes its
        own ``prefill_fn``.)

        **With a wave** (``piece_wave``; ``wave = (rows, lens, seeds, temps,
        top_ks, top_ps)``, each ``[B]``): the wave's rows go through the
        layers behind the piece's (``piece_hidden_fn``) and out through the
        same head: the rows of x that the one product over the vocabulary's
        matrix takes are each piece lane's last and the wave's ``B``, under
        the one conditional, now on "a lane ends or a wave lane is live";
        a wave lane's token is chosen at context ``lens + 1``, as
        ``decode_fn`` chooses it.  -> (arena, ``[the piece's part | the
        wave's part]``), each as its own program leaves it: the wave's is
        ``[B | B x stream_record | wave_stats]``, the record's rows and the
        counts (``_piece_end``) of the wave's rows alone."""
        piece = self.piece_hidden_fn()
        n = self.prefill_piece[0]
        record = bool(self.stream_record)

        def prefill(p, arena, rows, ids, lens, seeds, temps, top_ks, top_ps,
                    sample, starts, ends, wave=None):
            import jax
            import jax.numpy as jnp

            lanes, counts = rows.shape[0], None
            if wave is None:
                arena, x, trail = piece(p, arena, rows, ids, lens, starts)
            else:
                w_rows, w_lens, *w_sampling = wave
                arena, x, trail, counts = piece(
                    p, arena, rows, ids, lens, starts, (w_rows, w_lens))
            # Each lane's last valid row of x.
            at = last_at = lens - 1 + n * np.arange(lanes, dtype=np.int32)
            heads = lanes
            if wave is not None:
                # The wave's rows behind the lanes' last, its columns behind
                # the lanes': one head for both.
                heads = lanes + w_rows.shape[0]
                at = jnp.concatenate([at, np.arange(lanes * n, x.shape[0],
                                                    dtype=np.int32)])
                seeds, temps, top_ks, top_ps = (
                    jnp.concatenate(pair) for pair in zip(
                        (seeds, temps, top_ks, top_ps), w_sampling))
                rows = jnp.concatenate([rows, w_rows])

            def head(x_at):
                logits = self._served(self._logits(p, x_at))
                ctx = starts + lens
                if wave is not None:
                    ctx = jnp.concatenate([ctx, w_lens + 1])
                tokens = choose_tokens(logits, seeds, ctx, temps, top_ks,
                                       top_ps, sample)
                if not record:
                    return (tokens,)
                return tokens, logit_bits(logits, tokens, RECORD_LOGITS)

            def skip(x_at):
                tokens = jnp.zeros(heads, jnp.int32)
                if not record:
                    return (tokens,)
                return tokens, jnp.zeros((heads, 1 + RECORD_LOGITS),
                                         jnp.int32)

            due = jnp.any(ends != 0)
            if wave is not None:
                due = due | jnp.any(w_lens > 0)
            # (The rows behind a barrier: the compiler otherwise sinks what
            # of the last layer only they read, an expert layer's gather
            # back from the sorted layout, into the branch, and every
            # operand of that then lives until the conditional: 48 MB more
            # temporaries at smallthinker_21b's widths, compiled for the
            # v5e, tests/test_tpu_compile.py.)
            tokens, *bits = jax.lax.cond(
                due, head, skip, jax.lax.optimization_barrier(x[at]))
            arena = tokens_into_slots(arena, rows, tokens)
            stats = [] if counts is None else [counts.astype(tokens.dtype)]
            if not record:
                return arena, (jnp.concatenate([tokens, *stats]) if stats
                               else tokens)
            last = (jnp.arange(lanes * n) == jnp.repeat(last_at, n))
            words, last = self._piece_words(trail), last[:, None]
            if wave is None:
                bits = jnp.repeat(bits[0], n, axis=0)
                rec = jnp.concatenate(words + [jnp.where(last, bits, 0)],
                                      axis=1)
                return arena, jnp.concatenate([tokens, rec.reshape(-1)])
            own = jnp.where(last, jnp.repeat(bits[0][:lanes], n, axis=0), 0)
            rec = jnp.concatenate(
                words + [jnp.concatenate([own, bits[0][lanes:]])], axis=1)
            return arena, jnp.concatenate(
                [tokens[:lanes], rec[:lanes * n].reshape(-1),
                 tokens[lanes:], rec[lanes * n:].reshape(-1), *stats])

        return prefill

    def decode_chunk_fn(self):
        """``DECODE_CHUNK_ARGS`` -> (arena, tokens[k, B]).

        K decode waves in ONE device execution via ``lax.scan`` over the
        single-wave body: each scanned step gathers its inputs from the
        token slots the previous step wrote, so the whole chunk chains on
        the device and one dispatch advances every live stream K tokens.
        ``k`` is static (one executable per (wave bucket, K)); the per-step
        math is ``decode_fn``'s, so sampling's fold_in(seed, ctx_len)
        sequence is that of K separate waves.  Not offered by a backend
        with a transition: one may fall between any two steps, and the
        scheduler orders it."""
        if self.transition_due is not None:
            raise NotImplementedError(
                f"{self.config.name} decodes one wave a dispatch (its cache "
                "transitions are ordered between waves)")
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
