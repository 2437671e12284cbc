"""Continuous (iteration-level) batching for generative models.

The decoupled scheduler streams one model's responses per request
(scheduler.py DecoupledScheduler); this scheduler goes further for
autoregressive backends: every *decode step* is shared across all live
generation streams. Design, TPU-first:

- The KV cache is a fixed-capacity HBM **arena** pytree owned by one worker
  (``backend.init_arena``; +1 dummy slot absorbs padded lanes), donated into
  every jitted call so updates are in-place. What a stream's slot holds is
  the backend's business: one row per position of ``max_seq_len`` for a
  full-attention decoder (float32 in ``TinyGptBackend``), the model's own
  state for another (``models/evabyte.py``: bfloat16 chunk summaries and a
  window of exact rows). The arena carries each slot's latest token ON DEVICE
  (``arena["tok"]``), so consecutive decode waves chain with no host round
  trip between them.
- **Prefill** (one jit per prompt bucket and lane count) writes a batch of
  prompts' K/V into their arena rows and emits each prompt's first token.
  An admit's lanes pad up to the smallest count of the bucket's ladder
  (``_lane_ladder``: 8 lanes always, fewer only where halving the program
  pays for compiling it), every count compiled before the bucket's first
  dispatch.
- **Prefill by pieces** (a backend that declares ``prefill_piece =
  (positions, lanes)``): a prompt is admitted once and consumed ``positions``
  at a time, the cache carrying the state between pieces; between two decode
  waves at most one piece is dispatched, so a token gap is bounded by a wave
  plus a piece, and the first token follows the last piece.  One compiled
  prefill program a lane count (every power of two up to the backend's
  ``lanes``, all warmed with the model), whatever the prompt's length; a
  piece runs with the smallest count that holds the prompts standing in
  line, and a lone prompt's piece goes at once, in the one-lane program.
  The worker knows, lane by lane, whether a piece is its prompt's last, and
  says so to a backend that declares ``piece_ends`` (the decoder's own piece
  frame) in one more operand behind ``starts``, ``ends [lanes]`` (0 on padded
  lanes): such a program computes its head, the vocabulary's matrix read for
  one row a lane, only where some lane ends, so a prompt of a dozen pieces
  pays for one head and not for twelve (counter ``prefill_heads``: the piece
  programs in which some lane ended).  A backend that declares
  ``piece_pairs_by_kind`` has the (query, key) pairs of every dispatched
  lane's piece added to ``prefill_pairs_window`` and ``prefill_pairs_global``,
  as ``cache_rows_by_kind`` feeds a wave's rows by kind.
- **A wave carried in the piece's program** (a backend that declares
  ``piece_wave``: the ring-and-expert decoders ``cohere_moe`` and
  ``smallthinker`` and, through its state layers, ``nemotron_h``; the
  scheduler reads the declaration and no name): every piece program of its
  ladder takes a wave's operands at the top bucket behind the piece's
  (``wave``, behind ``ends``) and returns the wave's part behind the
  piece's.  Where an iteration has a piece to dispatch and lanes to decode,
  the worker stages both and dispatches that one program (one
  ``gen.prefill_dispatch``); the one fetch is taken apart into the piece's
  and the wave's, and each is counted,
  kept and emitted as its own program's fetch is: the decoding lanes' next
  token comes out of the piece's pass over the weights.  A carried wave is a
  wave to every counter (``fetched_*``, the gaps', ``dispatches``; its
  ``wave_stats`` are of its own lanes), and ``fetched_waves_carried`` counts
  those that rode.  With no lane to decode the same program runs with every
  wave lane on the dummy row.  Such a backend's waves go one at a time
  (``CLIENT_TPU_GEN_CHUNK`` is not read, as with transitions), and one that
  declares transitions too is refused.  **What a carried wave is charged**:
  the program's interval is one clock's for both, so the wave's part is its
  rows' share of the frame's (its bucket's lanes over those and the piece's
  ``lanes x piece`` positions): that is the wave's ``decode_waves`` time and
  its lanes' bill; a piece's part stays unbilled, as a lone piece's is.
- **Transitions** (a backend that declares ``transition_due(n)`` and
  ``transition_fn()``): a stream whose dispatch-side length ``n`` is due has
  the jitted transition queued before its next wave (span
  ``gen.transition_dispatch``).  The worker knows every stream's
  dispatch-side length, so the order needs no fetch and no host sync.
  The scheduler learns all of this from the contract a served decoder
  declares (``models/decoder.py`` ``DecoderBackend``: every member has a
  default there), never from a model's name.
- **What only the device can count** (a backend that declares
  ``wave_stats``, names of ``spans.GEN_COUNTERS``): the decode program returns
  that many int32 behind a wave's tokens (what a sparse expert layer routed),
  one fetch brings both, and the counters move when the tokens arrive.
- **Passes** (a backend that declares ``passes`` > 1: its layer stack runs
  that many times a step over one set of weights, each pass on a cache of
  its own): the programs are the backend's and look like any other's; the
  worker adds ``passes`` a fetched wave to the counter ``fetched_passes``
  and the rows of every pass reach ``fetched_rows_global`` through
  ``cache_rows_by_kind``.
- **A stream's record** (a backend that declares ``stream_record``, int32 a
  position, and a request whose parameters say ``record``): the programs
  return every position's row behind their tokens (pieces and waves alike);
  the worker keeps the rows of the streams that asked and hands a stream its
  whole record, ``RECORD [positions, stream_record]``, with its final
  response.  No other stream's path moves: the rows of a fetch nobody asked
  for are dropped with a slice.
- **Decode waves** (one jit per stream-count bucket) advance every live
  stream one token in a single XLA execution: gather input tokens from the
  device-side slots, scatter new K/V at each stream's position, masked
  attention over the static sequence axis, sample/argmax, scatter the new
  tokens back into the slots.
- **Pipelined dispatch** (round-4): the worker dispatches prefills and
  waves WITHOUT waiting for their results — JAX async dispatch queues them
  on the device in order — and consumes the token fetches asynchronously
  (``copy_to_host_async`` + ``is_ready``), bounded by a pipeline depth
  (``_PIPELINE_DEPTH``). Emission,
  stop-token checks, and retirement happen at fetch time, a few waves
  behind dispatch; over-generated tokens past a stop are discarded (the
  lanes are independent, so junk in a retired lane cannot perturb live
  streams). This moves inter-token latency from one host↔device round
  trip per token to the device step time; how much that buys depends on
  the round trip, which on a chip-local host is not measured yet.
- Streams are admitted whenever a row is free — new requests join the next
  wave (iteration-level batching), they never wait for a running stream to
  finish (request-level batching would).

The worker's own clock is inside the program: every phase of a loop iteration
is a ``gen.*`` span and every dispatch, drain and first token bumps a counter
(:mod:`client_tpu.observability.spans`), served per model under
``GET /v2/profile`` ``generative`` and, while a device trace is active, written
into it as ``TraceAnnotation``s of the same names.

Tokens stream out through the ordinary decoupled response protocol
(``triton_final_response`` terminates), so the gRPC stream frontend and the
C API serve generative models without modification.  A frontend that can
take a whole wave at once says so on the request (``InferRequest.token_sink``):
its streams' tokens leave as ONE ``TokenWave`` per fetched wave (no response
object, no array and no queue operation per token on the worker; what the
writer does with the wave is the frontend's, ``server/sse.py``); only the
final response and errors of such a stream take the per-response path.
"""

from __future__ import annotations

import collections
import logging
import math
import os
from client_tpu import config as envcfg
import queue as _queue
import threading
import time

import numpy as np

from client_tpu.engine.scheduler import (
    Scheduler,
    _SHUTDOWN,
    _SHUTDOWN_LEVEL,
    _backpressured,
    power_buckets,
)
from client_tpu.engine.types import (
    EngineError,
    InferRequest,
    InferResponse,
    TokenWave,
    now_ns,
    token_response,
)
from client_tpu.observability import spans as _sp
from client_tpu.observability.costs import ledger
from client_tpu.observability.profiler import profiler
from client_tpu.observability.tracing import MAX_CHUNK_EVENTS

_log = logging.getLogger("client_tpu")

# Dispatch-ahead bound, in waves in flight before the worker blocks on the
# oldest fetch (each entry holds a bucket-sized token vector).  What is known:
# it bounds the junk dispatched behind a cancellation or a stop token; on the
# chip the runtime's own launch queue fills first and is what bounds the
# pipeline (PERF.md section 6), so nothing between that bound and 32 changes a
# token gap.  Whether 32 is the right value is ROADMAP Queue A item 5's
# question.
_PIPELINE_DEPTH = 32

# A lane count joins a prompt bucket's ladder only where the program it halves
# holds more tokens than this.  Device time alone would put the mark near 1024
# (on the v5e 480 tokens of float32 weights balance their own read; under that
# a prefill costs the same with one lane or eight), but every program is also
# 2.5 s of a launch (trace, lower and load, cache warm: PERF.md section 6,
# PR 31), so the ladder is kept to the halving that saves most: 8 x 1024
# positions (12.9 ms) against 4 x 1024 (7.1 ms).
_LANE_WORTH_TOKENS = 4096


class _Stream:
    __slots__ = ("req", "row", "disp_len", "disp_tokens", "f_len",
                 "emitted", "max_new", "seed", "temp", "top_k", "top_p",
                 "stop", "dead", "throttled_since", "ids", "consumed",
                 "transition", "sink", "record")

    def __init__(self, req, row, plen, max_new,
                 seed=0, temp=0.0, top_k=0, top_p=1.0, stop=frozenset()):
        self.req = req
        # Where its tokens go by the wave (None: one response per token).
        self.sink = req.token_sink if req is not None else None
        self.row = row
        self.disp_len = plen      # context length at the next dispatch
        self.disp_tokens = 1      # tokens whose generation is dispatched
        self.f_len = plen         # fetch-side context length mirror
        self.emitted = 0
        self.max_new = max_new
        self.seed = seed          # per-request PRNG seed
        self.temp = temp          # 0 = greedy
        self.top_k = top_k        # 0 = off
        self.top_p = top_p        # 1.0 = off
        self.stop = stop          # token ids terminating the stream
        self.dead = False         # retired/cancelled (skip pending lanes)
        self.throttled_since = None  # monotonic mark while backpressured
        # Prefill by pieces: the prompt still to consume (None once its last
        # piece is dispatched, and always for a one-shot prefill) and how
        # much of it is dispatched.
        self.ids = None
        self.consumed = 0
        self.transition = False   # a cache transition is due before a wave
        # The rows of its record fetched so far, piece by piece and wave by
        # wave (None: it did not ask for one).
        self.record = None


# The lane of a prefill piece whose prompt is not finished by it.
_NO_STREAM = _Stream(None, -1, 0, 0)
_NO_STREAM.dead = True


class _Inflight:
    """One dispatched execution whose token fetch is pending."""

    __slots__ = ("kind", "streams", "tokens", "waves", "t_disp", "bucket",
                 "depth", "positions", "rows", "pieces", "fresh", "by_kind",
                 "lanes", "rider")

    def __init__(self, kind, streams, tokens, waves=1, t_disp=0, bucket=0,
                 depth=0, positions=0, rows=(0, 0), pieces=(), fresh=(),
                 by_kind=(0, 0, 0), lanes=0, rider=None):
        self.kind = kind          # 'prefill' | 'piece' | 'wave' | 'chunk'
        self.streams = streams    # lane order, real lanes only
        self.tokens = tokens      # jax.Array future (copy_to_host_async'd)
        self.waves = waves        # logical waves this dispatch advances
        self.t_disp = t_disp      # monotonic ns at a wave's dispatch
        self.bucket = bucket      # wave bucket (0 for prefill)
        self.depth = depth        # waves in flight at a prefill's dispatch
        self.positions = positions  # valid context positions it reads
        self.rows = rows          # cache rows it reads: (summary, exact)
        self.pieces = pieces      # a piece's lanes: (stream, valid positions)
        self.fresh = fresh        # a wave's lanes that decode their first token
        self.by_kind = by_kind    # rows it reads: (ring, whole-context), and
        #                           its lanes past the ring
        self.lanes = lanes        # a piece call's compiled lanes
        # The wave a piece's program carried (an _Inflight of its own kind
        # with no tokens: its part lies behind the piece's in this fetch).
        self.rider = rider


class _WarmupReq:
    """Queue sentinel: precompile on the worker thread (serialized with
    live traffic — compiling from the caller's thread would race the
    arena)."""

    def __init__(self):
        self.done = threading.Event()
        self.error: Exception | None = None


def _parse_sampling(req: InferRequest, vocab: int):
    """(seed, temp, top_k, top_p, stop_set) from request parameters.

    Defaults are greedy (temperature 0), matching the pre-sampling engine
    bit for bit. ``stop_token_ids`` accepts an int or a comma-separated
    string (wire parameters are scalar); ``eos_id`` is its single-token
    alias."""
    p = req.parameters

    def num(key, default, cast, lo=None, hi=None):
        try:
            v = cast(p.get(key, default))
        except (TypeError, ValueError, OverflowError):
            # OverflowError: int(float('inf')) — json accepts Infinity.
            raise EngineError(
                f"{key} must be {cast.__name__}, got {p.get(key)!r}",
                400) from None
        if cast is float and not math.isfinite(v):
            # NaN passes every range comparison (nan<lo and nan>hi are both
            # False) and would silently poison the sampled logits.
            raise EngineError(f"{key} must be finite, got {v!r}", 400)
        if (lo is not None and v < lo) or (hi is not None and v > hi):
            raise EngineError(
                f"{key} must be in [{lo}, {hi}], got {v}", 400)
        return v

    # Unseeded sampling draws a fresh per-request seed (vLLM-style): retries
    # of the same prompt get different samples. An explicit seed keeps full
    # determinism, and batch invariance holds either way because the seed is
    # per-request (fold_in(seed, position) inside the kernels).
    if "seed" in p:
        seed = num("seed", 0, int)
    else:
        seed = int.from_bytes(os.urandom(4), "little")
    temp = num("temperature", 0.0, float, lo=0.0)
    top_k = num("top_k", 0, int, lo=0)
    top_p = num("top_p", 1.0, float, lo=0.0, hi=1.0)
    if top_p == 0.0:
        raise EngineError("top_p must be in (0, 1]", 400)
    stop: set[int] = set()
    raw_stop = p.get("stop_token_ids", None)
    if raw_stop is None:
        raw_stop = p.get("eos_id", None)
    if raw_stop is not None:
        parts = (str(raw_stop).split(",")
                 if isinstance(raw_stop, str) else [raw_stop])
        for part in parts:
            try:
                tok = int(part)
            except (TypeError, ValueError):
                raise EngineError(
                    f"stop_token_ids must be ints, got {part!r}",
                    400) from None
            if not 0 <= tok < vocab:
                raise EngineError(
                    f"stop token {tok} outside vocab [0, {vocab})", 400)
            stop.add(tok)
    return seed, temp, top_k, top_p, frozenset(stop)


def _census_arena(sched) -> tuple[int, int]:
    """HbmCensus dynamic-provider hook. The KV arena is donated into
    every jit call, so its buffers are replaced wave-to-wave — static
    tags would die within one step; the census instead reads the live
    pytree through this at walk time. Must stay a plain function (the
    census holds the scheduler weakly; a closure would pin it)."""
    from client_tpu.observability.memory import _buffer_nbytes

    leaves = sched._jax.tree_util.tree_leaves(sched._arena)
    total = 0
    for leaf in leaves:
        total += _buffer_nbytes(leaf)
    return total, len(leaves)


class GenerativeScheduler(Scheduler):
    """Arena-owned single worker; batching provides the parallelism."""

    single_instance = True
    # How long a stream may stay CONTINUOUSLY transport-throttled before
    # its arena slot is reclaimed (see the worker-loop flow control).
    BACKPRESSURE_TIMEOUT_S = 60.0

    def __init__(self, model, stats):
        import jax

        self._jax = jax
        backend = model.backend
        self._cap = int(backend.max_streams)
        self._max_seq = int(backend.max_seq_len)
        # Sharded KV arenas carry one junk row per shard, so free rows are
        # not 0..cap-1 and the dummy row is not `cap` (parallel/kv_shard.py).
        free_rows, dummy = backend.arena_rows(self._cap)
        self._rows_init = [int(r) for r in free_rows]
        self._dummy = int(dummy)
        self._arena = backend.init_arena(self._cap)
        from client_tpu.observability.memory import hbm_census

        hbm_census().register_provider(
            model.config.name, "kv_arena", self, _census_arena)
        # `sample` is static: all-greedy calls get an executable with no
        # sampling pipeline in it; the backend says where it stands in each
        # program's arguments.  The XLA modules are named here (jit_prefill,
        # jit_decode, jit_decode_chunk), not by what a backend calls its
        # functions: the device-trace reduction finds the steps by these
        # names.
        self._prefill = jax.jit(
            _sp.named_step(backend.prefill_fn(), _sp.STEP_PREFILL),
            donate_argnums=backend.donate_argnums,
            static_argnums=backend.prefill_static_argnums)
        self._decode = jax.jit(
            _sp.named_step(backend.decode_fn(), _sp.STEP_DECODE),
            donate_argnums=backend.donate_argnums,
            static_argnums=backend.decode_static_argnums)
        # Chunked decode (CLIENT_TPU_GEN_CHUNK > 1): K waves fused into one
        # scanned execution — one dispatch advances every stream K tokens,
        # dividing per-wave Python + transport-command overhead by K.
        # Token emission still happens per wave at fetch time; streams that
        # stop/retire mid-chunk have their surplus lanes discarded exactly
        # like any retired lane.  Admits join at chunk boundaries (<= K-1
        # waves of extra TTFT, ~K*step_ms).
        self._chunk = max(1, envcfg.env_int("CLIENT_TPU_GEN_CHUNK"))
        # What a backend declares about a cache that is not one slot per
        # position (module docstring), each None where it is: prefill by
        # pieces, the rows a step reads, and a transition ordered between
        # two waves.
        self._piece_len, self._piece_lanes = backend.prefill_piece or (0, 0)
        self._piece_ends = bool(self._piece_len and backend.piece_ends)
        # The lanes of the wave that every piece program carries (module
        # docstring: the top wave bucket; 0: the programs take no wave).
        self._piece_wave = self._cap if (
            self._piece_ends and backend.piece_wave) else 0
        self._cache_rows = backend.cache_rows
        self._rows_by_kind = backend.cache_rows_by_kind
        self._pairs_by_kind = backend.piece_pairs_by_kind
        self._passes = int(backend.passes)
        # Counters only the device can fill (``wave_stats``): that many
        # int32 ride behind a decode wave's tokens.
        self._wave_stats = [_sp.GEN_COUNTERS.index(name)
                            for name in backend.wave_stats]
        # int32 a position behind every program's tokens, for the streams
        # that ask for their record (module docstring).
        self._record = int(backend.stream_record)
        if self._record and not self._piece_len:
            raise ValueError(
                f"{model.config.name}: a stream's record is kept piece by "
                "piece; the backend declares no prefill_piece")
        self._transition_due = backend.transition_due
        self._transition = None
        if self._transition_due is not None:
            self._transition = jax.jit(
                _sp.named_step(backend.transition_fn(), _sp.STEP_TRANSITION),
                donate_argnums=backend.donate_argnums)
            # A transition may fall between any two steps of a stream, so
            # waves are dispatched one at a time.
            self._chunk = 1
            if self._piece_wave:
                raise ValueError(
                    f"{model.config.name}: a transition is ordered before "
                    "a stream's next wave and a piece's program carries "
                    "that wave; the backend declares both")
        if self._piece_wave:
            # A piece's program carries one wave, so waves are dispatched
            # one at a time.
            self._chunk = 1
        self._decode_chunk = None
        if self._chunk > 1:
            self._decode_chunk = jax.jit(
                _sp.named_step(backend.decode_chunk_fn(),
                               _sp.STEP_DECODE_CHUNK),
                donate_argnums=backend.donate_argnums,
                static_argnums=backend.decode_chunk_static_argnums)
        self._prompt_buckets = ([self._piece_len] if self._piece_len
                                else power_buckets(self._max_seq))
        self._wave_buckets = power_buckets(self._cap)
        # The most lanes a prefill holds, and per prompt bucket the ladder
        # of lane counts a chunk pads up to (``_lane_ladder``).  A bucket's
        # whole ladder runs once before its first real dispatch
        # (``_warm_ladder``): a lane size first seen under load would stall
        # every stream for a compile (round-3's ~1 s mid-measurement).
        self._admit_lane = self._piece_lanes or min(self._cap, 8)
        self._ladders = {pb: self._lane_ladder(pb)
                         for pb in self._prompt_buckets}
        self._ladders_warm: set[tuple[int, bool]] = set()
        self._depth = _PIPELINE_DEPTH   # a test sets a shallower one
        self._streams: list[_Stream] = []
        self._inflight: collections.deque[_Inflight] = collections.deque()
        # Depth accounting is in WAVES, not dispatches: a K-chunk counts K,
        # so the depth bounds the same amount of dispatched-ahead device
        # work (and cancellation junk) in either mode.
        self._inflight_waves = 0
        self._free = list(self._rows_init)
        # Fetch-side low-water mark for wave timing: the device is busy
        # from max(dispatch, previous fetch) to this fetch, so pipelined
        # waves are not double-counted (see _drain_fetches).
        self._last_fetch_ns = 0
        # The token gap as this worker produces it (the ``gap_*`` counters):
        # the previous decode fetch (0: none since the last ``gen.idle``, so
        # the next decode fetch closes no gap) and whether a prefill call's
        # fetch came since.  The fetch queue keeps dispatch order and
        # dispatch order is device order, so a prefill head popped between
        # two decode heads ran between those two waves on the chip.
        self._last_decode_fetch_ns = 0
        self._prefill_since_decode = False
        # (bucket, chunk) wave shapes whose static cost model has been
        # captured — decode waves never pass Model.execute_timed, so the
        # roofline numerator is pulled here, once per shape.
        self._wave_cost_captured: set[tuple[int, int]] = set()
        # Per-row arena bytes for the cost ledger's HBM-byte-second
        # charges, cached on first use (one pytree walk, static shapes).
        self._row_bytes = 0.0
        # Loop-phase spans and lane counters of this worker; an iteration
        # commits into whichever profiler is the global one when it ends.
        name, version = model.config.name, model.config.version
        self._rec = _sp.GenRecorder(
            lambda rec: profiler().commit_generative(name, version, rec))
        super().__init__(model, stats)

    def arena_shards(self) -> int:
        """KV arena shard count (1 = single-chip): the autotuner divides
        the arena reservation by this so the planning arena charges the
        PER-DEVICE share, not the global pytree bytes."""
        return int(self.model.backend.kv_shards)

    def arena_nbytes(self) -> int:
        """Total bytes of the KV arena pytree — the engine's HBM planner
        (``client_tpu.engine.arena``) reserves this against the device
        budget when the autotuner is enabled, so co-resident models see
        the generative arena as committed memory, not free space."""
        leaves = self._jax.tree_util.tree_leaves(self._arena)
        total = 0
        for leaf in leaves:
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is None:
                size = getattr(leaf, "size", 0)
                itemsize = getattr(getattr(leaf, "dtype", None),
                                   "itemsize", 0)
                nbytes = size * itemsize
            total += int(nbytes)
        return total

    # -- warmup ---------------------------------------------------------------

    def warmup(self) -> None:
        """Precompile the greedy prefill executable for every prompt bucket
        and the greedy decode executable for every wave bucket, on the
        worker thread. Without this, the first burst that exercises a new
        bucket pays a ~1s XLA compile mid-stream (measured as the round-3
        TTFT p99)."""
        req = _WarmupReq()
        self.queue.put(req)
        if not req.done.wait(600):
            raise EngineError(
                "generative warmup timed out (worker busy for 600s)", 500)
        if req.error is not None:
            raise EngineError(f"generative warmup failed: {req.error}", 500)

    def _lane_ladder(self, bucket: int) -> list[int]:
        """The lane counts a prefill of prompt bucket ``bucket`` may be
        dispatched with: powers of two up to ``_admit_lane``, a smaller one
        only where the next one's program holds more tokens than
        ``_LANE_WORTH_TOKENS``, so where dropping its padded lanes saves the
        device more than the program costs a launch.  A backend that
        prefills by pieces has every power of two up to its own lanes: a
        piece runs with the smallest that holds the prompts in line, since a
        lane with no prompt costs a piece's whole mixers for nothing."""
        lanes = power_buckets(self._admit_lane)
        if self._piece_len:
            return lanes
        return [lane for lane, above in zip(lanes, lanes[1:])
                if above * bucket > _LANE_WORTH_TOKENS] + lanes[-1:]

    def _warm_ladder(self, bucket: int, sample: bool, but: int = 0) -> None:
        """Run every lane count of a prompt bucket's ladder (``but`` the one
        about to run anyway) once with every lane padded onto the dummy row,
        so that no admit size compiles under load."""
        if (bucket, sample) in self._ladders_warm:
            return
        for lane in self._ladders[bucket]:
            if lane == but:
                continue
            self.model._set_state(
                f"warmup: prefill prompt bucket={bucket} lanes={lane}",
                _sp.STEP_PREFILL, bucket)
            rows, *sampling = self._stage_lanes([], lane)
            # A piece's `starts`, the argument only prefill by pieces has,
            # and behind it the `ends` of a backend that takes them (no lane
            # ends here: the executable is the one of a piece where one does).
            zeros = np.zeros(lane, np.int32)
            self._arena, _ = self._prefill(
                self.model._params, self._arena, rows,
                np.zeros((lane, bucket), np.int32), np.ones(lane, np.int32),
                *sampling, sample,
                *((zeros, zeros) if self._piece_ends
                  else (zeros,) if self._piece_len else ()),
                *((self._stage_wave([], self._piece_wave),)
                  if self._piece_wave else ()))
        self._ladders_warm.add((bucket, sample))

    def _precompile(self) -> None:
        for pb in self._prompt_buckets:
            self._warm_ladder(pb, False)
        if self._transition is not None:
            self.model._set_state("warmup: cache transition",
                                  _sp.STEP_TRANSITION, 1)
            self._arena = self._transition(
                self.model._params, self._arena,
                np.asarray([self._dummy], np.int32), np.zeros(1, np.int32))
        for wb in self._wave_buckets:
            self.model._set_state(f"warmup: decode wave bucket={wb}",
                                  _sp.STEP_DECODE, wb)
            rows, lens, *sampling = self._stage_wave([], wb)
            self._arena, tokens = self._decode(
                self.model._params, self._arena, rows, lens, *sampling,
                False)
            if self._decode_chunk is not None:
                self.model._set_state(
                    f"warmup: chunked decode bucket={wb} k={self._chunk}",
                    _sp.STEP_DECODE_CHUNK, wb)
                self._arena, tokens = self._decode_chunk(
                    self.model._params, self._arena, rows, lens, *sampling,
                    False, self._chunk)
                tokens = tokens[-1]
        self._jax.block_until_ready(tokens)
        self.model._clear_state()

    # -- worker ---------------------------------------------------------------

    def _worker_loop(self) -> None:
        rec = self._rec
        while True:
            rec.begin_loop()
            try:
                if self._loop_once(rec):
                    return
            finally:
                rec.end_loop()

    def _loop_once(self, rec) -> bool:
        """One scheduler iteration (one ``gen.loop`` span); True stops the
        worker."""
        span = rec.span
        pending = []
        shutdown = False
        # Blocking admit only when fully idle; otherwise opportunistic —
        # a new request joins the *next* wave, never waits for a stream
        # to finish.
        if not self._streams and not self._inflight:
            with self._idle():
                item = self.queue.get()
            if item is _SHUTDOWN:
                return True
            if isinstance(item, _WarmupReq):
                self._run_warmup(item)
                return False
            pending.append(item)
        while len(self._free) > len(pending):
            try:
                item = self.queue.get(timeout=0)
            except _queue.Empty:
                break
            if item is _SHUTDOWN:
                shutdown = True
                break
            if isinstance(item, _WarmupReq):
                self._run_warmup(item)
                continue
            pending.append(item)
        if pending:
            with span[_sp.S_ADMIT]:
                try:
                    self._admit_batch(pending)
                except Exception as exc:  # noqa: BLE001 — sole worker:
                    # an escape here would kill the scheduler thread and
                    # hang the model permanently.
                    self._reset_arena(exc)
        if shutdown:
            self._abort_streams("server shutting down")
            return True
        with span[_sp.S_SWEEP]:
            live = self._sweep()
        pieced = carried = False
        try:
            if self._piece_len:
                pieced, carried = self._dispatch_piece(live)
            if live and not carried:
                if self._transition is not None:
                    self._dispatch_transitions(live)
                self._dispatch_wave(live)
        except Exception as exc:  # noqa: BLE001
            self._reset_arena(exc)
        # Consume fetches: non-blocking while results are ready or the
        # pipeline is over depth; forced (blocking on the oldest) when
        # nothing was dispatched — every budget-exhausted stream has
        # its final wave in flight, so this always makes progress.
        self._drain_fetches(
            force_one=not live and not pending and not pieced)
        if (not live and not pending and not pieced and not self._inflight
                and self._streams):
            # Every stream is throttled by transport backpressure:
            # nothing to dispatch, nothing to fetch.  Park briefly so
            # the writer can drain (it advances ~10 rows/ms) — via a
            # timed queue poll, not a bare sleep: _SHUTDOWN must not
            # be starved for the whole backpressure timeout
            # (engine.shutdown joins this thread), and a warmup
            # sentinel must not rot behind throttled streams.
            try:
                with self._idle():
                    item = self.queue.get(timeout=0.001)
            except _queue.Empty:
                return False
            if item is _SHUTDOWN:
                self._abort_streams("server shutting down")
                return True
            if isinstance(item, _WarmupReq):
                self._run_warmup(item)
            else:
                # A new admit while the arena is throttle-parked: put
                # it back at the FRONT (no reordering) and yield the
                # core — the loop-top opportunistic admit takes it the
                # moment a slot frees.
                self.queue.put_front(item)
                with self._idle():
                    time.sleep(0.001)
        return False

    def _idle(self):
        """``gen.idle``.  The device drains while the worker waits for work,
        so a token gap across it is no wave-to-wave interval: the next decode
        fetch closes none (the ``gap_*`` counters)."""
        self._last_decode_fetch_ns = 0
        return self._rec.span[_sp.S_IDLE]

    def _sweep(self) -> list:
        """Drop cancelled streams and return the lanes of the next wave."""
        # Client-abandoned streams stop consuming decode slots at the
        # next wave boundary (frontends set `cancelled` on disconnect).
        for s in list(self._streams):
            if s.req.cancelled:
                self._drop(s)
                self._fail(s.req, EngineError("request cancelled", 499))
        # Transport flow control: streams whose frontend reports a
        # backlogged response path sit out this wave (production is
        # writer-paced) instead of flooding the stream queue until the
        # slow-consumer shed kills them.  They stay live and rejoin
        # the moment the writer drains — but a stream CONTINUOUSLY
        # throttled past the timeout is holding an arena slot for a
        # consumer that stopped reading; drop it (bounds slot
        # occupancy the way the shed bounds queue memory).
        live = []
        now_mono = time.monotonic()
        for s in list(self._streams):
            if not self._has_budget(s):
                continue
            if _backpressured(s.req):
                if s.throttled_since is None:
                    s.throttled_since = now_mono
                elif (now_mono - s.throttled_since
                      > self.BACKPRESSURE_TIMEOUT_S):
                    self._drop(s)
                    self._fail(s.req, EngineError(
                        "request cancelled (stream backpressured "
                        f"beyond {self.BACKPRESSURE_TIMEOUT_S:.0f}s)",
                        499))
                continue
            s.throttled_since = None
            live.append(s)
        return live

    def _run_warmup(self, req: _WarmupReq) -> None:
        t0 = time.monotonic_ns()
        try:
            self._precompile()
        except Exception as exc:  # noqa: BLE001 — surface to the caller
            req.error = exc
        finally:
            req.done.set()
            # Set-up, not serving: out of this iteration's gen.loop.
            self._rec.exclude(time.monotonic_ns() - t0)

    def _has_budget(self, s: _Stream) -> bool:
        return (not s.dead and s.ids is None and s.disp_tokens < s.max_new
                and s.disp_len + 1 < self._max_seq)

    def _validate(self, req: InferRequest):
        """Parse + validate one admit; returns (ids, max_new, sampling)."""
        ids = np.ravel(np.asarray(req.inputs["INPUT_IDS"])).astype(np.int32)
        try:
            max_new = int(req.parameters.get(
                "max_tokens", self.model.backend.default_max_tokens))
        except (TypeError, ValueError, OverflowError):
            raise EngineError(
                f"max_tokens must be an integer, got "
                f"{req.parameters.get('max_tokens')!r}", 400) from None
        if max_new < 1:
            raise EngineError("max_tokens must be >= 1", 400)
        if len(ids) < 1:
            raise EngineError("INPUT_IDS must contain at least one id", 400)
        if len(ids) + max_new > self._max_seq:
            raise EngineError(
                f"prompt ({len(ids)}) + max_tokens ({max_new}) exceeds "
                f"max_seq_len ({self._max_seq})"
                + (f"; the prompt itself may be any length up to that, "
                   f"prefilled {self._piece_len} positions a piece"
                   if self._piece_len else ""), 400)
        vocab = self.model.backend.vocab
        if (ids < 0).any() or (ids >= vocab).any():
            raise EngineError(f"token ids must be in [0, {vocab})", 400)
        return ids, max_new, _parse_sampling(req, vocab)

    def _admit_batch(self, items: list) -> None:
        """Validate, group by prompt bucket, one batched prefill per chunk;
        prefills are dispatched without waiting (tokens fetch async)."""
        ready = []  # (req, ids, max_new, sampling)
        for req in items:
            if self._check_timeout(req) or self._check_cancelled(req):
                continue
            try:
                ids, max_new, sampling = self._validate(req)
            except EngineError as exc:
                self._fail(req, exc)
                continue
            except Exception as exc:  # noqa: BLE001 — malformed request
                # reaching the scheduler must fail that request, not the
                # admit batch (let alone the worker).
                self._fail(req, EngineError(f"invalid request: {exc}", 400))
                continue
            req.times.compute_start = now_ns()
            ledger().charge_queue(
                self.model.config.name, str(self.model.config.version),
                req.tenant, req.times.queue_ns / 1e9,
                trace_id=self._trace_id(req))
            ready.append((req, ids, max_new, sampling))
        if self._piece_len:
            # Prefill by pieces: a prompt takes its slot now and is consumed
            # by ``_dispatch_piece``, one piece between two waves.
            for req, ids, max_new, (seed, temp, top_k, top_p, stop) in ready:
                stream = _Stream(req, self._free.pop(), len(ids), max_new,
                                 seed=seed, temp=temp, top_k=top_k,
                                 top_p=top_p, stop=stop)
                stream.ids = ids
                if self._record and req.parameters.get("record"):
                    stream.record = []
                self._streams.append(stream)
                self._rec.c[_sp.C_PROMPTS_ADMITTED] += 1
            return
        by_bucket: dict[int, list] = {}
        for entry in ready:
            bucket = next(b for b in self._prompt_buckets
                          if b >= len(entry[1]))
            by_bucket.setdefault(bucket, []).append(entry)
        chunks = []
        for bucket, entries in sorted(by_bucket.items()):
            cap = self._admit_lane
            chunks += [(bucket, entries[i:i + cap])
                       for i in range(0, len(entries), cap)]
        for ci, (bucket, chunk) in enumerate(chunks):
            try:
                with self._rec.span[_sp.S_PREFILL_DISPATCH]:
                    self._prefill_chunk(bucket, chunk)
            except EngineError as exc:
                for req, *_ in chunk:
                    self._fail(req, exc)
            except Exception as exc:  # noqa: BLE001
                # Donated-arena failure: everything queued behind this
                # chunk fails too (the arena is being rebuilt).
                for _, later in chunks[ci + 1:]:
                    for req, *_ in later:
                        self._fail(req, EngineError(
                            f"generation aborted: {exc}", 500))
                for req, *_ in chunk[1:]:
                    self._fail(req, EngineError(
                        f"generation aborted: {exc}", 500))
                self._reset_arena(exc, failing=chunk[0][0])
                return

    def _count_started(self, req: InferRequest, now: int) -> None:
        """A prompt's first prefill call (its first piece, or its one-shot
        program) returned at ``now``: its two waits up to here.  The engine's
        queue, the line for a prefill call and ``first_token_wait_ns`` (from
        ``now`` on) partition ``first_token - queue_start`` of the request."""
        times = req.times
        times.prefill_start = now
        c = self._rec.c
        c[_sp.C_PROMPTS_STARTED] += 1
        c[_sp.C_ADMIT_WAIT_NS] += times.queue_ns
        c[_sp.C_PREFILL_LINE_WAIT_NS] += now - times.compute_start

    def _stage_lanes(self, lanes: list, width: int):
        """(rows, seeds, temps, top_ks, top_ps), each ``[width]``, as every
        program takes them: the lanes' streams first, the rest padded onto
        the dummy row as greedy lanes."""
        pad = width - len(lanes)

        def column(values, fill, dtype):
            return np.asarray(values + [fill] * pad, dtype)

        return (column([s.row for s in lanes], self._dummy, np.int32),
                column([s.seed & 0xFFFFFFFF for s in lanes], 0,
                       np.uint32).astype(np.int32),
                column([s.temp for s in lanes], 0.0, np.float32),
                column([s.top_k for s in lanes], 0, np.int32),
                column([s.top_p for s in lanes], 1.0, np.float32))

    def _stage_wave(self, live: list, bucket: int):
        """(rows, lens, seeds, temps, top_ks, top_ps) of a wave of ``live``
        at ``bucket`` lanes, as a decode program takes them: a padded lane
        on the dummy row at length 0."""
        rows, *sampling = self._stage_lanes(live, bucket)
        lens = np.asarray([s.disp_len for s in live]
                          + [0] * (bucket - len(live)), np.int32)
        return (rows, lens, *sampling)

    def _prefill_chunk(self, prompt_bucket: int, chunk: list) -> None:
        """One batched prefill dispatch: B admits -> ONE device execution,
        no host sync (the first tokens arrive through the fetch queue)."""
        n = len(chunk)
        lane = next(b for b in self._ladders[prompt_bucket] if b >= n)
        streams = [
            _Stream(req, self._free.pop(), len(ids), max_new, seed=seed,
                    temp=temp, top_k=top_k, top_p=top_p, stop=stop)
            for req, ids, max_new, (seed, temp, top_k, top_p, stop) in chunk]
        try:
            ids_mat = np.zeros((lane, prompt_bucket), np.int32)
            lens = np.ones(lane, np.int32)
            for i, (_req, ids, *_) in enumerate(chunk):
                ids_mat[i, :len(ids)] = ids
                lens[i] = len(ids)
            rows, seeds, temps, top_ks, top_ps = self._stage_lanes(
                streams, lane)
            sample = bool((temps > 0.0).any())
            try:
                self._warm_ladder(prompt_bucket, sample, but=lane)
                self.model._set_state(
                    f"generative prefill ({n} streams, prompt "
                    f"bucket={prompt_bucket})", _sp.STEP_PREFILL,
                    prompt_bucket)
                self._arena, tokens = self._prefill(
                    self.model._params, self._arena, rows, ids_mat,
                    lens, seeds, temps, top_ks, top_ps, sample)
                tokens.copy_to_host_async()
            finally:
                self.model._clear_state()
        except Exception:
            self._free.extend(s.row for s in streams)
            raise
        self._streams.extend(streams)
        # Executions are counted at dispatch (round-3 semantics): fetch-time
        # counting would drop waves whose lanes all retired before the
        # fetch, and everything discarded by an arena reset.
        self.stats.record_execution(n)
        self._rec.c[_sp.C_PREFILL_LANES_LIVE] += n
        self._rec.c[_sp.C_PREFILL_LANES_PADDED] += lane - n
        now = time.monotonic_ns()
        for s in streams:
            self._count_started(s.req, now)
        self._inflight.append(_Inflight("prefill", streams, tokens,
                                        depth=self._inflight_waves))
        self._inflight_waves += 1

    def _dispatch_piece(self, live: list) -> tuple[bool, bool]:
        """The next piece of the oldest prompts still prefilling (up to the
        backend's lanes), as ONE device execution with no host sync.  A
        prompt's last piece leaves its first token in the slot's device-side
        token and in the fetch queue, and the stream joins the next wave.
        Where the piece program carries a wave, the wave of ``live`` goes in
        it.  -> (a piece was dispatched, it carried the wave)."""
        todo = [s for s in self._streams
                if s.ids is not None][:self._piece_lanes]
        if not todo:
            return False, False
        riders = live if self._piece_wave else []
        with self._rec.span[_sp.S_PREFILL_STAGE]:
            self._stage_and_dispatch_piece(todo, riders)
        return True, bool(riders)

    def _stage_and_dispatch_piece(self, todo: list, riders: list) -> None:
        width = self._piece_len
        lane = next(b for b in self._ladders[width] if b >= len(todo))
        ids_mat = np.zeros((lane, width), np.int32)
        lens = np.ones(lane, np.int32)
        starts = np.zeros(lane, np.int32)
        ends = np.zeros(lane, np.int32)
        for i, s in enumerate(todo):
            part = s.ids[s.consumed:s.consumed + width]
            ids_mat[i, :len(part)] = part
            lens[i], starts[i] = len(part), s.consumed
            ends[i] = s.consumed + len(part) >= len(s.ids)
        rows, seeds, temps, top_ks, top_ps = self._stage_lanes(todo, lane)
        sample = bool((temps > 0.0).any())
        wave = ()
        if self._piece_wave:
            wave = (self._stage_wave(riders, self._piece_wave),)
            sample = sample or bool((wave[0][3] > 0.0).any())
        self._warm_ladder(width, sample, but=lane)
        self.model._set_state(
            f"generative prefill piece ({len(todo)} streams, from "
            f"{[int(x) for x in starts[:len(todo)]]}"
            + (f", a wave of {len(riders)}" if riders else "") + ")",
            _sp.STEP_PREFILL, width)
        try:
            with self._rec.span[_sp.S_PREFILL_DISPATCH]:
                self._arena, tokens = self._prefill(
                    self.model._params, self._arena, rows, ids_mat, lens,
                    seeds, temps, top_ks, top_ps, sample, starts,
                    *((ends,) if self._piece_ends else ()), *wave)
            tokens.copy_to_host_async()
        finally:
            self.model._clear_state()
        now = time.monotonic_ns()
        self._rec.c[_sp.C_PREFILL_PIECES] += len(todo)
        self._rec.c[_sp.C_PREFILL_HEADS] += int(ends.any())
        held = int(lens[:len(todo)].sum())
        self._rec.c[_sp.C_PREFILL_POSITIONS_VALID] += held
        self._rec.c[_sp.C_PREFILL_POSITIONS_PADDED] += \
            len(todo) * width - held
        if self._pairs_by_kind is not None:
            for i in range(len(todo)):
                ring, whole = self._pairs_by_kind(int(starts[i]),
                                                  int(lens[i]))
                self._rec.c[_sp.C_PREFILL_PAIRS_WINDOW] += ring
                self._rec.c[_sp.C_PREFILL_PAIRS_GLOBAL] += whole
        self.stats.record_execution(len(todo))
        done = []                     # by lane: the stream, if it ended
        for i, s in enumerate(todo):
            if not s.consumed:
                self._count_started(s.req, now)
            s.consumed += int(lens[i])
            if ends[i]:
                s.ids = None          # prefilled: live from the next wave
            done.append(_NO_STREAM if s.ids is not None else s)
        # The fetch queue keeps dispatch order and the pipeline's depth; a
        # lane whose prompt goes on carries no stream (its token is junk).
        depth = self._inflight_waves
        self._inflight_waves += 1
        # (A carried wave stands in the queue's depth for the dispatch it
        # replaces; a program whose wave held no lane leaves a part that
        # nothing reads.)
        rider = self._wave_dispatched(
            riders, self._piece_wave, 1, None, wave[0][1]) if riders else None
        self._inflight.append(_Inflight(
            "prefill" if any(s is not _NO_STREAM for s in done) else "piece",
            done, tokens, depth=depth, lanes=lane, rider=rider,
            pieces=list(zip(todo, lens.tolist())) if self._record else ()))

    def _dispatch_transitions(self, live: list) -> None:
        """Queue the cache transition of every live stream that is due one,
        before the wave that needs it (device order is dispatch order)."""
        for s in live:
            if not s.transition:
                continue
            with self._rec.span[_sp.S_TRANSITION_DISPATCH]:
                self._arena = self._transition(
                    self.model._params, self._arena,
                    np.asarray([s.row], np.int32),
                    np.asarray([s.disp_len], np.int32))
            s.transition = False
            self._rec.c[_sp.C_TRANSITIONS] += 1

    def _dispatch_wave(self, live: list) -> None:
        """Dispatch decode wave(s) for the live lanes.  Live lanes can
        exceed the largest wave bucket (a ladder edit, a tuner-retired
        bucket, or a subclass shrinking the ladder): clamp to the max
        bucket and split into several dispatches instead of letting the
        bucket pick in :meth:`_dispatch_one_wave` raise StopIteration and
        reset the arena under full load."""
        max_bucket = self._wave_buckets[-1] if self._wave_buckets \
            else len(live)
        for i in range(0, len(live), max_bucket):
            self._dispatch_one_wave(live[i:i + max_bucket])

    def _dispatch_one_wave(self, live: list) -> None:
        """Dispatch one decode wave; input tokens come from the arena's
        device-side slots, so no host value is needed."""
        with self._rec.span[_sp.S_WAVE_STAGE]:
            self._stage_and_dispatch(live)

    def _stage_and_dispatch(self, live: list) -> None:
        rec = self._rec
        bucket = next(b for b in self._wave_buckets if b >= len(live))
        rows, lens, seeds, temps, top_ks, top_ps = self._stage_wave(
            live, bucket)
        # Chunk only when every live lane has K steps of sequence headroom:
        # a scanned step past max_seq would CLIP its k/v scatter onto the
        # last position (jax .at[] semantics) and corrupt it.  Budget
        # overshoot is safe (surplus tokens discard at fetch) but wasteful,
        # so chunking also waits until every lane wants >= K more tokens.
        k = self._chunk
        if k > 1 and not all(
                s.disp_len + k < self._max_seq
                and s.max_new - s.disp_tokens >= k for s in live):
            k = 1
        self.model._set_state(
            f"generative decode wave ({len(live)} streams, bucket={bucket}"
            + (f", chunk={k}" if k > 1 else "") + ")",
            _sp.STEP_DECODE_CHUNK if k > 1 else _sp.STEP_DECODE, bucket)
        try:
            sample = bool((temps > 0.0).any())
            # The enqueue alone: where a full runtime queue blocks.
            with rec.span[_sp.S_WAVE_DISPATCH]:
                if k > 1:
                    self._arena, nxt = self._decode_chunk(
                        self.model._params, self._arena, rows, lens,
                        seeds, temps, top_ks, top_ps, sample, k)
                else:
                    self._arena, nxt = self._decode(
                        self.model._params, self._arena, rows, lens,
                        seeds, temps, top_ks, top_ps, sample)
            nxt.copy_to_host_async()
        finally:
            self.model._clear_state()
        self._inflight.append(
            self._wave_dispatched(live, bucket, k, nxt, lens))
        if (bucket, k) not in self._wave_cost_captured:
            # Once per wave shape: static roofline numerator for this
            # decode executable. The jit call above just traced this
            # exact signature, so .lower() is a cache hit (no compile);
            # donation is not executed by lowering, and self._arena is
            # the live post-dispatch arena with identical avals.
            self._wave_cost_captured.add((bucket, k))
            from client_tpu.observability import roofline

            args = (self.model._params, self._arena, rows, lens,
                    seeds, temps, top_ks, top_ps, sample)
            cost = roofline.capture_cost_model(
                self._decode_chunk if k > 1 else self._decode,
                args + ((k,) if k > 1 else ()))
            profiler().record_wave_cost_model(
                self.model.config.name, self.model.config.version,
                bucket, k, cost)

    def _wave_dispatched(self, live: list, bucket: int, k: int, nxt,
                         lens) -> _Inflight:
        """A wave of ``live`` is on the device, in a decode program (``nxt``
        its result) or in a piece's (``None``: its part lies in the piece's
        fetch): the counters of a dispatch, the streams' dispatch-side
        lengths, and what its fetch will count."""
        rec = self._rec
        # How deep the pipeline already was, and the valid context the
        # live lanes read (each its length before this wave, one more for
        # each scanned step): counted when the wave's tokens arrive.
        rec.c[_sp.C_DISPATCHES] += 1
        rec.c[_sp.C_INFLIGHT_WAVES] += self._inflight_waves
        positions = k * int(lens.sum()) + len(live) * (k * (k - 1) // 2)
        n_sum = n_exact = 0
        by_kind = [0, 0, 0]
        fresh = []                    # lanes whose first decode token this is
        for s in live:
            if self._cache_rows is not None:
                a, b = self._cache_rows(s.disp_len)
                n_sum, n_exact = n_sum + a, n_exact + b
            if self._rows_by_kind is not None:
                for step in range(k):
                    for i, n in enumerate(
                            self._rows_by_kind(s.disp_len + step)):
                        by_kind[i] += n
            if s.disp_tokens == 1:
                fresh.append(s)
            s.disp_len += k
            s.disp_tokens += k
            if self._transition is not None and self._transition_due(
                    s.disp_len):
                s.transition = True
        # One device dispatch = one execution in the public stats, chunked
        # or not — execution_count means device executions, and fewer
        # executions per token IS the chunking win the stat should show.
        # (A carried wave's execution is its piece's.)
        if nxt is not None:
            self.stats.record_execution(len(live))
        self._inflight_waves += k
        return _Inflight("chunk" if k > 1 else "wave", live, nxt, waves=k,
                         t_disp=time.monotonic_ns(), bucket=bucket,
                         positions=positions, rows=(n_sum, n_exact),
                         fresh=fresh, by_kind=by_kind)

    def _drain_fetches(self, force_one: bool = False) -> None:
        """Consume completed token fetches in dispatch order; emission,
        stop-token checks, and retirement happen here (a few waves behind
        dispatch)."""
        rec = self._rec
        c = rec.c
        drained = False
        decode_fetches = 0
        while self._inflight:
            head = self._inflight[0]
            if not head.tokens.is_ready():
                if not (force_one or self._inflight_waves > self._depth):
                    break
                c[_sp.C_FETCHES_FORCED] += 1  # taken to wait, not ready
            force_one = False
            self._inflight.popleft()
            self._inflight_waves -= head.waves
            try:
                with rec.span[_sp.S_FETCH_WAIT]:
                    toks = np.asarray(head.tokens)
            except Exception as exc:  # noqa: BLE001 — execution failed
                self._reset_arena(exc)
                break
            drained = True
            if head.rider is not None:
                # The piece's program carried a wave: the fetch is the
                # piece's part and then the wave's, each taken as its own
                # program's fetch.  No clock splits the program's interval
                # (from the fetch before it), so the wave's time is its
                # rows' share of the frame's (module docstring).
                self._inflight_waves -= head.rider.waves
                c[_sp.C_FETCHED_WAVES_CARRIED] += 1
                since, cut = self._last_fetch_ns, self._piece_part(head)
                wave_rows = head.rider.bucket
                self._take_fetch(head, toks[:cut], since)
                self._take_fetch(
                    head.rider, toks[cut:], since,
                    wave_rows / (wave_rows + head.lanes * self._piece_len))
                decode_fetches += 1
            else:
                if self._piece_wave and not head.bucket:
                    toks = toks[:self._piece_part(head)]  # no lane rode
                self._take_fetch(head, toks, self._last_fetch_ns)
                decode_fetches += bool(head.bucket)
        if drained:
            c[_sp.C_DRAINS] += 1
            if decode_fetches >= 2:
                # Two waves' tokens leave back to back: the pairs a
                # client sees as one long gap and one of nothing.
                c[_sp.C_DRAINS_MULTI] += 1

    def _piece_part(self, head: _Inflight) -> int:
        """How much of a piece program's fetch is the piece's: its lanes'
        tokens and their positions' rows of the record (the rest is the part
        of the wave it carried)."""
        return head.lanes * (1 + self._piece_len * self._record)

    def _take_fetch(self, head: _Inflight, toks, since: int,
                    share: float = 1.0) -> None:
        """One program's fetched result (a carrying piece's comes apart into
        two): what only the device counted, the records, the wave's timing
        and counters, then emission.  ``since``: the fetch before it;
        ``share``: how much of the device's interval is this wave's (a
        carried wave's rows over its program's)."""
        rec = self._rec
        c = rec.c
        if self._wave_stats and head.kind in ("wave", "chunk"):
            n = len(self._wave_stats)
            stats = toks[..., -n:].reshape(-1, n).sum(axis=0)
            toks = toks[..., :-n]
            for i, v in zip(self._wave_stats, stats.tolist()):
                c[i] += v
        if self._record:
            toks = self._keep_records(head, toks)
        # Wave timing: the device ran this dispatch from max(its dispatch,
        # the previous fetch) until now — pipelined waves complete back to
        # back, so the deltas between consecutive fetches ARE the
        # per-dispatch device occupancy (the first fetch after an idle gap
        # also carries host staging; steady-state waves dominate the
        # histogram).
        t_done = time.monotonic_ns()
        if not head.bucket:
            self._prefill_since_decode = True
        else:
            self._count_gap(head, t_done)
            busy_ns = int(share * max(0, t_done - max(head.t_disp, since)))
            # The device has run the wave: its lanes, padding and valid
            # context are what decode_waves and the clients' token gaps of
            # this moment are about.
            lanes = len(head.streams) * head.waves
            c[_sp.C_FETCHED_WAVES] += head.waves
            c[_sp.C_FETCHED_LANES_LIVE] += lanes
            c[_sp.C_FETCHED_LANES_PADDED] += \
                head.bucket * head.waves - lanes
            c[_sp.C_FETCHED_POSITIONS_VALID] += head.positions
            c[_sp.C_FETCHED_ROWS_SUMMARY] += head.rows[0]
            c[_sp.C_FETCHED_ROWS_EXACT] += head.rows[1]
            c[_sp.C_FETCHED_ROWS_WINDOW] += head.by_kind[0]
            c[_sp.C_FETCHED_ROWS_GLOBAL] += head.by_kind[1]
            c[_sp.C_FETCHED_LANES_PAST_WINDOW] += head.by_kind[2]
            c[_sp.C_FETCHED_PASSES] += head.waves * self._passes
            profiler().record_wave(
                self.model.config.name, self.model.config.version,
                bucket=head.bucket, chunk=head.waves,
                duration_ns=busy_ns, waves=head.waves)
            # Cost ledger: the wave's device occupancy splits evenly across
            # live lanes (every stream advances one token per wave
            # regardless of context length); padded lanes charge the wave's
            # dominant tenant as padding waste. A junk wave (every lane
            # retired while it was in flight) bills its dispatch-time
            # streams instead — they caused the speculative dispatch, and
            # conservation against the profiler requires every recorded
            # wave to be charged.
            live = [s for s in head.streams if not s.dead] \
                or list(head.streams)
            if live:
                ledger().charge_batch(
                    self.model.config.name,
                    str(self.model.config.version),
                    [(s.req.tenant, 1, None) for s in live],
                    busy_ns / 1e9,
                    padded=max(0, head.bucket - len(live)),
                    component="wave")
        self._last_fetch_ns = t_done
        with rec.span[_sp.S_EMIT]:
            self._emit_fetched(head, toks)

    def _count_gap(self, head: _Inflight, t_done: int) -> None:
        """The token gap a decode fetch closes, for each of its lanes: the
        time since the previous decode fetch (a K-chunk: K gaps of a Kth),
        and apart the gaps that held a prefill call, once however many it
        held.  Never a prefill head's own interval: where the worker blocks
        in the dispatch it pops a piece and a wave microseconds apart, and
        only their sum is the device's.  A lane's first gap runs from its
        token 0, which its prefill's fetch emitted inside this interval or
        before it, and waited for no prefill: it is counted as it was, among
        the plain gaps."""
        c = self._rec.c
        last, behind = self._last_decode_fetch_ns, self._prefill_since_decode
        self._last_decode_fetch_ns = t_done
        self._prefill_since_decode = False
        if not last:
            return
        lanes = len(head.streams)
        fresh = [s.req.times.first_token for s in head.fresh if not s.dead]
        c[_sp.C_GAP_LANES] += lanes * head.waves
        c[_sp.C_GAP_LANE_NS] += (t_done - last) * lanes \
            + len(fresh) * last - sum(fresh)
        if behind:
            waited = lanes - len(fresh)
            c[_sp.C_GAP_LANES_BEHIND_PREFILL] += waited * head.waves
            c[_sp.C_GAP_LANE_BEHIND_PREFILL_NS] += (t_done - last) * waited

    def _keep_records(self, head: _Inflight, toks):
        """A fetch's tokens, its positions' rows of the streams' records cut
        off behind them (``[lanes | lanes x positions x stream_record]``, a
        chunk's one such row a wave); the streams that asked keep theirs."""
        lanes = head.bucket or head.lanes
        rows = toks[..., lanes:]
        if head.pieces:
            rows = rows.reshape(lanes, -1, self._record)
            for i, (s, valid) in enumerate(head.pieces):
                if s.record is not None:
                    s.record.append(rows[i, :valid])
        else:
            rows = rows.reshape(-1, lanes, self._record)
            for i, s in enumerate(head.streams):
                if s.record is not None:
                    s.record.append(rows[:, i])
        return toks[..., :lanes]

    def _emit_fetched(self, head: _Inflight, toks) -> None:
        """Emit one fetch's tokens.  A chunked fetch is K stacked waves
        [K, B]; emit them in wave order so stop/budget retirement lands
        mid-chunk exactly where a per-wave dispatch would have retired
        (surplus lanes past a retirement are junk and are discarded like
        any dead lane).

        What only the worker can do happens here, lane by lane: lengths,
        stop tokens, budgets, first-token clocks, retirement.  The tokens
        themselves leave once per fetch: the lanes whose frontend declared a
        ``token_sink`` as ONE ``TokenWave`` to their writer, the others as
        one ``InferResponse`` each.  Final responses follow the record, so
        a stream's last token is never behind its end."""
        c = self._rec.c
        prefill = head.kind == "prefill"
        waves = toks if head.kind == "chunk" else toks[None]
        t = now_ns()                  # one clock read a fetch
        version = str(self.model.config.version)
        records: dict = {}            # writer -> TokenWave
        ended = []
        for row in waves.tolist():
            for s, tok in zip(head.streams, row):
                if s.dead:
                    continue  # retired/cancelled lanes: discard junk
                if prefill:
                    # TTFT from inside: prefill dispatch to token 0.
                    s.req.times.first_token = t
                    c[_sp.C_FIRST_TOKENS] += 1
                    c[_sp.C_FIRST_TOKEN_WAIT_NS] += \
                        t - s.req.times.prefill_start
                    c[_sp.C_FIRST_TOKEN_INFLIGHT_WAVES] += head.depth
                else:
                    s.f_len += 1
                if tok in s.stop:
                    # Stop tokens terminate without being emitted.
                    self._retire(s)
                    ended.append(s)
                    continue
                sink = s.sink
                if sink is None:
                    self._respond(s.req, token_response(
                        s.req, version, tok, s.emitted))
                    c[_sp.C_EMITTED_TOKENS_CALLBACK] += 1
                else:
                    wave = records.get(sink.writer)
                    if wave is None:
                        wave = records[sink.writer] = TokenWave(version)
                    wave.sinks.append(sink)
                    wave.tokens.append(tok)
                    wave.indices.append(s.emitted)
                    stamps = sink.chunk_ts_ns
                    if stamps is not None and len(stamps) < MAX_CHUNK_EVENTS:
                        stamps.append(t)
                s.emitted += 1
                if (s.emitted >= s.max_new
                        or s.f_len + 1 >= self._max_seq):
                    self._retire(s)
                    ended.append(s)
        for writer, wave in records.items():
            c[_sp.C_EMIT_HANDOFFS] += 1
            c[_sp.C_EMITTED_TOKENS] += len(wave.tokens)
            try:
                writer.post(wave)
            except Exception:  # noqa: BLE001 — a frontend's fault must not
                # kill the sole worker; its streams end by their own
                # back-pressure or cancel.
                _log.exception(
                    "stream writer refused a wave (model '%s')",
                    self.model.config.name)
        for s in ended:
            outputs = {}
            if s.record is not None:
                # A row a position consumed: the prompt's, and one a wave
                # it took part in (a chunk's surplus rows go).
                outputs["RECORD"] = np.concatenate(s.record)[:s.f_len]
            self._respond(s.req, InferResponse(
                model_name=s.req.model_name,
                model_version=s.req.model_version or version,
                request_id=s.req.request_id,
                outputs=outputs,
                parameters={"triton_final_response": True},
                final=True,
                times=s.req.times,
            ))

    # -- stream lifecycle ------------------------------------------------------

    def _drop(self, s: _Stream) -> None:
        """Remove from the active set and release the row. The row is safe
        to reuse immediately: executions already dispatched with it run
        BEFORE any later prefill into the same row (single device stream,
        dispatch order), and their lanes are discarded at fetch."""
        s.dead = True
        if s in self._streams:
            self._streams.remove(s)
        self._free.append(s.row)
        # Cost ledger: KV-arena residency — this stream held one arena row
        # from admission until now, excluding nothing (a row blocked for
        # the whole generation is the scarce resource being attributed).
        held_ns = now_ns() - s.req.times.compute_start
        if held_ns > 0 and s.req.times.compute_start:
            ledger().charge_hbm(
                self.model.config.name, str(self.model.config.version),
                s.req.tenant, held_ns / 1e9 * self._row_nbytes(),
                trace_id=self._trace_id(s.req))

    def _row_nbytes(self) -> float:
        """Per-row KV arena bytes, cached (the arena is static-shaped, so
        one pytree walk amortises over every stream release)."""
        if not self._row_bytes:
            rows = len(self._rows_init) + 1  # usable rows + dummy lane
            self._row_bytes = self.arena_nbytes() / max(1, rows)
        return self._row_bytes

    def _retire(self, s: _Stream) -> None:
        """A stream's last token is fetched: free its row and close its
        account.  Its final response is the caller's to send, behind the
        fetch's tokens (``_emit_fetched``)."""
        self._drop(s)
        s.req.times.compute_input_end = s.req.times.compute_start
        s.req.times.compute_infer_end = now_ns()
        s.req.times.compute_output_end = s.req.times.compute_infer_end
        self.stats.record_request(s.req.times, success=True,
                                  tenant=s.req.tenant)

    def _all_tracked_streams(self) -> list:
        """Active streams plus any stream referenced only by in-flight
        fetches (deduped)."""
        seen: dict[int, _Stream] = {id(s): s for s in self._streams}
        for inf in self._inflight:
            for s in inf.streams:
                if not s.dead:
                    seen.setdefault(id(s), s)
        return list(seen.values())

    def _abort_streams(self, why: str) -> None:
        for s in self._all_tracked_streams():
            s.dead = True
            self._fail(s.req, EngineError(why, 503))
        self._streams.clear()
        self._inflight.clear()
        self._inflight_waves = 0
        self._free = list(self._rows_init)
        self.queue.put(_SHUTDOWN, _SHUTDOWN_LEVEL)  # other sentinels may wait

    def _reset_arena(self, exc: Exception, failing=None) -> None:
        """A failed donated call may have invalidated the arena buffers —
        and every in-flight execution behind it: rebuild and drop every
        live stream (mirrors the oldest-sequence batcher's recovery)."""
        _log.exception(
            "model '%s': generative step failed; resetting KV arena "
            "(%d live streams dropped)", self.model.config.name,
            len(self._streams))
        if failing is not None:
            self._fail(failing, exc)
        for s in self._all_tracked_streams():
            s.dead = True
            self._fail(s.req, EngineError(
                f"generation aborted: {exc}", 500))
        self._streams.clear()
        self._inflight.clear()
        self._inflight_waves = 0
        self._free = list(self._rows_init)
        self._arena = self.model.backend.init_arena(self._cap)
