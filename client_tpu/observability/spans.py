"""The program's own span vocabulary, and the recorder a worker thread
writes it with.

Four families of names, constants here and nowhere else:

- ``gen.*`` — the phases of one ``GenerativeScheduler._worker_loop``
  iteration.  Always on: every span is (count, total ns, max ns) per model,
  taken with ``time.monotonic_ns()`` on the worker thread, committed into the
  :class:`~client_tpu.observability.profiler.EfficiencyProfiler` when the
  iteration ends and served under each generative model's entry of
  ``GET /v2/profile`` (``"generative": {"spans", "counters"}``).  ``gen.loop``
  is the whole iteration (inclusive); every other span is exclusive of the
  spans opened inside it (``gen.admit`` is ``_admit_batch`` less its
  ``gen.prefill_dispatch``, ``gen.wave_stage`` is ``_dispatch_one_wave`` less
  its ``gen.wave_dispatch``, ``gen.prefill_stage`` is ``_dispatch_piece`` less
  its ``gen.prefill_dispatch``), so the children partition the iteration and
  ``gen.loop`` less their sum is the loop's own bookkeeping.
- ``exec.*`` — the batcher's three phases inside ``Model.execute_timed``.
  Their aggregate times already live in the profiler's bucket table
  (``host_s``/``device_s``); the names exist for the device trace.
- ``startup.*`` — the launcher's set-up phases (``/v2/profile`` ``startup``),
  from the operating system's start of the process to "serving".
- ``compile.*`` — the three phases of one compilation (trace, lower, backend),
  one span each on the same list whenever JAX reports one, with the compiling
  thread's scope and the program's name.

While a device trace is active (``TraceManager`` flips :func:`set_trace_active`)
each ``gen.*``/``exec.*`` span is also a ``jax.profiler.TraceAnnotation`` of
the same name, so the ``.xplane.pb`` holds host spans and device operations on
one clock.  Off the trace no annotation object is built: the helper reads one
module-level boolean.

The jitted steps' names are here too: ``jax.jit`` names an XLA module
``jit_<fn.__name__>``, and the benchmark's trace reduction keys on
``jit_decode``/``jit_prefill``/``jit_transition``/``jit_apply`` —
:func:`named_step` makes that a
contract instead of an accident of what a backend calls its inner function.
"""

from __future__ import annotations

import time

# -- the generative worker loop ------------------------------------------------

GEN_LOOP = "gen.loop"
GEN_IDLE = "gen.idle"
GEN_ADMIT = "gen.admit"
GEN_PREFILL_DISPATCH = "gen.prefill_dispatch"
GEN_SWEEP = "gen.sweep"
GEN_WAVE_STAGE = "gen.wave_stage"
GEN_WAVE_DISPATCH = "gen.wave_dispatch"
GEN_FETCH_WAIT = "gen.fetch_wait"
GEN_EMIT = "gen.emit"
# A cache state transition ordered between two decode waves (a backend that
# declares ``transition_fn``: EvaByte's window dump); count = transitions'
# dispatches, never opened for a backend without the hook.
GEN_TRANSITION_DISPATCH = "gen.transition_dispatch"
# ``_dispatch_piece`` less its ``gen.prefill_dispatch``: the staging and the
# bookkeeping of a piece, as ``gen.wave_stage`` is of a wave.  Only a backend
# that prefills by pieces opens it.
GEN_PREFILL_STAGE = "gen.prefill_stage"

# Positional (the index constants, the snapshot's order): new names go last.
GEN_SPANS = (GEN_LOOP, GEN_IDLE, GEN_ADMIT, GEN_PREFILL_DISPATCH, GEN_SWEEP,
             GEN_WAVE_STAGE, GEN_WAVE_DISPATCH, GEN_FETCH_WAIT, GEN_EMIT,
             GEN_TRANSITION_DISPATCH, GEN_PREFILL_STAGE)
(S_LOOP, S_IDLE, S_ADMIT, S_PREFILL_DISPATCH, S_SWEEP, S_WAVE_STAGE,
 S_WAVE_DISPATCH, S_FETCH_WAIT, S_EMIT, S_TRANSITION_DISPATCH,
 S_PREFILL_STAGE) = range(len(GEN_SPANS))

# Cumulative, monotone: two snapshots difference exactly.  Every counter has
# a reader (docs/OBSERVABILITY.md, the inventory): a per-layer metric of
# BENCHMARK.json or a documented operator's use.  Dispatch runs up to a
# pipeline's depth ahead of the device, so lanes and positions are counted
# when a wave's tokens arrive (``fetched_*``): the moment ``decode_waves`` and
# the clients' token gaps are about, not what the device will run many waves
# later.  What a span's count already says has no counter (prefill dispatches
# = ``gen.prefill_dispatch`` count, fetches = ``gen.fetch_wait`` count).
GEN_COUNTERS = (
    # per decode dispatch: how many, and the waves already in flight at each
    "dispatches", "inflight_waves",
    # per decode fetch (a K-chunk fetch counts K waves)
    "fetched_waves", "fetched_lanes_live", "fetched_lanes_padded",
    "fetched_positions_valid",
    # per drain (a ``_drain_fetches`` call that took at least one fetch)
    "drains", "drains_multi", "fetches_forced",
    # per first token: prefill dispatch to the emit of token 0, and the
    # waves in flight when that prefill was dispatched
    "first_tokens", "first_token_wait_ns", "first_token_inflight_waves",
    # A backend whose cache is not one slot per position (``cache_rows``,
    # ``prefill_piece``, ``transition_fn``; 0 for every other backend): the
    # cache rows a fetched wave read, by kind (``fetched_positions_valid``
    # keeps meaning context positions); prompts admitted, the pieces their
    # prefill was dispatched in (a lane's piece counts one), and the cache
    # transitions dispatched.
    "fetched_rows_exact", "fetched_rows_summary",
    "prompts_admitted", "prefill_pieces", "transitions",
    # How tokens leave the worker: ``emit_handoffs`` wave records posted to
    # the stream writers (one per fetch and writer) carrying
    # ``emitted_tokens``; ``emitted_tokens_callback`` tokens that left as one
    # ``InferResponse`` each (a stream whose frontend declared no sink).
    "emit_handoffs", "emitted_tokens", "emitted_tokens_callback",
    # per one-shot prefill dispatch: lanes holding a prompt, and lanes padded
    # up to the program's lane count
    "prefill_lanes_live", "prefill_lanes_padded",
    # What only the device can count, for a backend that declares
    # ``wave_stats`` (0 for every other): a sparse expert layer's routing,
    # summed over the layers and the fetched waves: the (token, expert) pairs
    # whose expert is held here, the busiest held expert's pairs (a layer's
    # largest group), and the held experts that got at least one token.
    "expert_pairs_local", "expert_pairs_busiest", "experts_touched",
    # per dispatched prefill piece: its positions that held a prompt token,
    # and those that were padding up to the piece (which a layer with a
    # recurrent state has to step over without moving the state).
    "prefill_positions_valid", "prefill_positions_padded",
    # per prompt, at its first prefill dispatch (its first piece, or its
    # one-shot program): how many, the engine's queue before the admit
    # (``RequestTimes.queue_ns``) and the slot taken to that dispatch.  With
    # ``first_token_wait_ns`` the three partition ``first_token -
    # queue_start`` of every request.
    "prompts_started", "admit_wait_ns", "prefill_line_wait_ns",
    # per decode fetch that follows a decode fetch with no ``gen.idle``
    # between them: the token gap as the worker produces it, weighted by the
    # wave's live lanes (a K-chunk: K gaps of a Kth), and the part of both
    # whose gap held at least one prefill call (counted once a gap).
    "gap_lanes", "gap_lane_ns", "gap_lanes_behind_prefill",
    "gap_lane_behind_prefill_ns",
    # A backend with two kinds of row cache (``cache_rows_by_kind``; 0 for
    # every other), per decode fetch: the rows the live lanes read from
    # their rings (sliding-window layers) and from their whole-context
    # leaves, each summed over the layers of the kind, and the live lanes
    # whose context has outgrown the ring (those the ring saves reads for).
    "fetched_rows_window", "fetched_rows_global", "fetched_lanes_past_window",
    # per decode fetch: the passes over its layers that the wave's program
    # ran, by what the backend declares (``passes`` of models/decoder.py's
    # contract: 1 a wave for every backend but one whose layer stack runs
    # several times over one set of weights).
    "fetched_passes",
    # per dispatched piece program in which some lane's piece was its
    # prompt's last (a program each, as ``gen.prefill_dispatch`` counts; the
    # worker knows it when it stages the piece).  A backend that declares
    # ``piece_ends`` computes the head of exactly these programs.
    "prefill_heads",
    # per dispatched prefill piece, for a backend that declares
    # ``piece_pairs_by_kind`` (0 for every other): the (query, key) pairs the
    # live lanes' pieces score in their ring (sliding-window) layers and in
    # their whole-context layers, each summed over the layers of the kind:
    # what a piece's attention costs, as the positions say what its products
    # cost.
    "prefill_pairs_window", "prefill_pairs_global",
    # per decode fetch whose tokens came out of a piece's program (a backend
    # that declares ``piece_wave``: one program for the piece and the wave of
    # a token gap; 0 for every other).  Such a wave is a wave: every
    # ``fetched_*`` and ``gap_*`` counter counts it as a lone one.
    "fetched_waves_carried",
)
(C_DISPATCHES, C_INFLIGHT_WAVES, C_FETCHED_WAVES, C_FETCHED_LANES_LIVE,
 C_FETCHED_LANES_PADDED, C_FETCHED_POSITIONS_VALID, C_DRAINS, C_DRAINS_MULTI,
 C_FETCHES_FORCED, C_FIRST_TOKENS, C_FIRST_TOKEN_WAIT_NS,
 C_FIRST_TOKEN_INFLIGHT_WAVES, C_FETCHED_ROWS_EXACT, C_FETCHED_ROWS_SUMMARY,
 C_PROMPTS_ADMITTED, C_PREFILL_PIECES, C_TRANSITIONS, C_EMIT_HANDOFFS,
 C_EMITTED_TOKENS, C_EMITTED_TOKENS_CALLBACK, C_PREFILL_LANES_LIVE,
 C_PREFILL_LANES_PADDED, C_EXPERT_PAIRS_LOCAL, C_EXPERT_PAIRS_BUSIEST,
 C_EXPERTS_TOUCHED, C_PREFILL_POSITIONS_VALID, C_PREFILL_POSITIONS_PADDED,
 C_PROMPTS_STARTED, C_ADMIT_WAIT_NS, C_PREFILL_LINE_WAIT_NS, C_GAP_LANES,
 C_GAP_LANE_NS, C_GAP_LANES_BEHIND_PREFILL,
 C_GAP_LANE_BEHIND_PREFILL_NS, C_FETCHED_ROWS_WINDOW, C_FETCHED_ROWS_GLOBAL,
 C_FETCHED_LANES_PAST_WINDOW, C_FETCHED_PASSES,
 C_PREFILL_HEADS, C_PREFILL_PAIRS_WINDOW,
 C_PREFILL_PAIRS_GLOBAL,
 C_FETCHED_WAVES_CARRIED) = range(len(GEN_COUNTERS))

# -- Model.execute_timed (trace annotations only) --------------------------------

EXEC_STAGE = "exec.stage"
EXEC_RUN = "exec.run"
EXEC_FETCH = "exec.fetch"

# -- set-up phases ------------------------------------------------------------

STARTUP_PROCESS = "startup.process"          # the OS's start to the first
STARTUP_BACKEND_INIT = "startup.backend_init"
STARTUP_IMPORTS = "startup.imports"          # the last span's end to the engine
STARTUP_MODEL_LOAD = "startup.model_load:"   # + model name
STARTUP_WARMUP = "startup.warmup:"           # + model name
STARTUP_FIRST_RUN = "startup.first_run:"     # + model name: warm-up less compiles
STARTUP_FRONTENDS = "startup.frontends"

# One compilation's phases as JAX reports them (``jax.monitoring`` time
# spans), on the set-up timeline whenever they happen.
COMPILE_TRACE = "compile.trace"      # Python function -> jaxpr
COMPILE_LOWER = "compile.lower"      # jaxpr -> MLIR module
COMPILE_BACKEND = "compile.backend"  # XLA, or the persistent cache's load

# -- jitted steps ---------------------------------------------------------------

STEP_PREFILL = "prefill"
STEP_DECODE = "decode"
STEP_DECODE_CHUNK = "decode_chunk"
STEP_TRANSITION = "transition"
STEP_APPLY = "apply"


def named_step(fn, name: str):
    """``fn`` under the stable name ``name``: what ``jax.jit`` sees, so the
    XLA module is ``jit_<name>`` whatever the backend called its function."""

    def step(*args):
        return fn(*args)

    step.__name__ = step.__qualname__ = name
    step.__doc__ = getattr(fn, "__doc__", None)
    return step


# -- the device trace's clock ----------------------------------------------------

_trace_active = False


def set_trace_active(on: bool) -> None:
    """Flipped by ``TraceManager`` around ``jax.profiler`` start/stop."""
    global _trace_active
    _trace_active = bool(on)


def trace_active() -> bool:
    return _trace_active


def _annotate(name: str):
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


def begin(name: str):
    """Open a trace-only span: the entered annotation while a device trace
    is active, ``None`` (nothing built) otherwise.  Close with :func:`end`."""
    return _annotate(name) if _trace_active else None


def end(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


class _Span:
    """One name of a recorder, reusable as a context manager (a worker
    thread opens a given name at most once at a time)."""

    __slots__ = ("rec", "idx", "name", "inclusive", "t0", "child", "parent",
                 "ann")

    def __init__(self, rec, idx: int, name: str, inclusive: bool):
        self.rec, self.idx, self.name = rec, idx, name
        self.inclusive = inclusive
        self.t0 = self.child = 0
        self.parent = self.ann = None

    def __enter__(self):
        rec = self.rec
        self.parent, rec.open = rec.open, self
        self.child = 0
        if _trace_active:
            self.ann = _annotate(self.name)
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        dt = time.monotonic_ns() - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        rec, i = self.rec, self.idx
        own = dt if self.inclusive else dt - self.child
        rec.ns[i] += own
        rec.n[i] += 1
        if own > rec.max[i]:
            rec.max[i] = own
        parent = rec.open = self.parent
        if parent is not None:
            parent.child += dt
        return False


class GenRecorder:
    """One generative worker's spans and counters for the iteration in
    progress.  Single writer (the worker thread), no lock on the hot path;
    :meth:`end_loop` hands the iteration to ``commit`` (the profiler adds it
    to the model's totals under its lock and this buffer is zeroed), so a
    snapshot only ever sees whole iterations: the children's totals never
    exceed ``gen.loop``'s, at any instant."""

    def __init__(self, commit):
        self._commit = commit
        self.ns = [0] * len(GEN_SPANS)
        self.n = [0] * len(GEN_SPANS)
        self.max = [0] * len(GEN_SPANS)
        self.c = [0] * len(GEN_COUNTERS)   # rec.c[C_DISPATCHES] += 1
        self.open = None
        self.span = tuple(_Span(self, i, name, inclusive=(i == S_LOOP))
                          for i, name in enumerate(GEN_SPANS))

    def begin_loop(self) -> None:
        self.open = None
        self.span[S_LOOP].__enter__()

    def exclude(self, ns: int) -> None:
        """Take ``ns`` just spent (a warm-up run on the worker thread) out
        of the iteration: it is set-up, not serving."""
        self.span[S_LOOP].t0 += ns

    def end_loop(self) -> None:
        self.span[S_LOOP].__exit__(None, None, None)
        self._commit(self)
