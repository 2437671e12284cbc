"""Engine-internal request/response types and timing.

The timing mirrors the reference's server-side phase breakdown that
perf_analyzer pulls and differences per window (queue / compute_input /
compute_infer / compute_output, /root/reference/src/c++/perf_analyzer/
inference_profiler.cc:836-908).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np


class EngineError(Exception):
    """Engine-level failure; carries an HTTP-ish status code for frontends."""

    def __init__(self, message: str, status: int = 400):
        super().__init__(message)
        self.status = status


class DeadlineExpired(EngineError):
    """The request's end-to-end deadline passed before the work ran.

    Carried as 504 so the HTTP frontend answers Gateway Timeout and the
    gRPC frontend maps to DEADLINE_EXCEEDED. A distinct type (rather than
    a bare 504 EngineError) lets the scheduler attribute the expiry stage
    on tpu_deadline_expirations_total without string matching."""

    def __init__(self, message: str):
        super().__init__(message, 504)


def now_ns() -> int:
    return time.monotonic_ns()


@dataclass
class RequestTimes:
    """Nanosecond timestamps of the server-side request lifecycle."""

    received: int = 0
    queue_start: int = 0
    compute_start: int = 0        # batch assembled; input staging begins
    compute_input_end: int = 0    # inputs on device
    compute_infer_end: int = 0    # executable done
    compute_output_end: int = 0   # outputs staged for the frontend
    # XLA compile time paid inside compute_infer (first call of this
    # request's bucket signature; 0 on warm requests). Lets frontends mark
    # the response cold (Server-Timing `compile` entry / server_compile_us
    # parameter) so clients can tell compile-hit outliers from queueing.
    compile_ns: int = 0
    # Generative streams only: token 0 reached the worker (prefill fetched).
    # TTFT inside the server = queue + (first_token - compute_start); the
    # four phases above keep their meaning (compute_infer is the whole
    # stream).
    first_token: int = 0
    # Generative streams only: the prompt's first prefill call returned (its
    # first piece, or its one-shot program).  compute_start -> here is its
    # wait in the line for that call; here -> first_token the prefill itself
    # and the pipeline ahead of it.
    prefill_start: int = 0

    @property
    def queue_ns(self) -> int:
        return max(0, self.compute_start - self.queue_start)

    @property
    def compute_input_ns(self) -> int:
        return max(0, self.compute_input_end - self.compute_start)

    @property
    def compute_infer_ns(self) -> int:
        return max(0, self.compute_infer_end - self.compute_input_end)

    @property
    def compute_output_ns(self) -> int:
        return max(0, self.compute_output_end - self.compute_infer_end)


@dataclass
class OutputRequest:
    """What the client asked for per output (classification, shm placement)."""

    name: str
    classification_count: int = 0
    shm_region: str | None = None
    shm_offset: int = 0
    shm_byte_size: int = 0
    binary: bool = True
    parameters: dict[str, Any] = field(default_factory=dict)


@dataclass
class InferRequest:
    model_name: str
    inputs: dict[str, np.ndarray]
    model_version: str = ""
    request_id: str = ""
    outputs: list[OutputRequest] = field(default_factory=list)
    parameters: dict[str, Any] = field(default_factory=dict)
    # Stateful-model sequence routing (reference common.h:173-184).
    sequence_id: int = 0
    sequence_start: bool = False
    sequence_end: bool = False
    priority: int = 0
    # Cost-ledger tenant tag (observability.costs): set by frontends from
    # the `X-Tpu-Tenant` HTTP header / `tenant` request parameter / shm
    # slot header. Empty means untagged — the engine resolves it to
    # "shadow" (admission shadow class) or "default" at submit.
    tenant: str = ""
    # QoS class name (client_tpu.admission.qos): stamped by the engine
    # at admission from the tenant/priority via QosController.classify;
    # the scheduler's WFQ queue lanes requests by it. Empty = QoS off.
    qos_class: str = ""
    # Assigned by the scheduler under preserve_ordering (arrival index).
    arrival_seq: int | None = None
    timeout_us: int = 0
    # End-to-end deadline (absolute time.monotonic_ns(); 0 = none).
    # Frontends derive it from the client's budget — the `timeout-ms` HTTP
    # header / `timeout_ms` request parameter, or the gRPC RPC deadline —
    # and the scheduler dequeue path plus the model-execute pre-check fail
    # expired requests fast (504/DEADLINE_EXCEEDED) instead of burning
    # device time on work whose caller already gave up. Distinct from
    # `timeout_us`, which is the queue policy's queue-WAIT bound.
    deadline_ns: int = 0
    times: RequestTimes = field(default_factory=RequestTimes)
    # Decoupled models invoke this once per streamed response; the final
    # response (or the only one, for non-decoupled) resolves the future too.
    response_callback: Callable[["InferResponse"], None] | None = None
    # Cooperative cancellation: frontends set this when the client goes
    # away (gRPC context termination); schedulers poll it before queueing
    # work and between generation waves, failing the request with 499.
    # Plain bool — writes are GIL-atomic and stale reads only delay the
    # cancel by one wave.
    cancelled: bool = False
    # Set by in-process callers whose every requested output is placed into
    # a device-resident tpu-shm region: the batch executor then skips the
    # D2H fetch entirely and responses carry HBM-resident jax.Arrays (the
    # shm write stores them as-is — zero host bytes end to end).
    keep_outputs_on_device: bool = False
    # Distributed-trace context (observability.tracing.TraceContext), set
    # by frontends from the W3C `traceparent` header / gRPC metadata, or
    # left None for untraced in-process callers (bench fast path).  Typed
    # Any to keep engine types free of observability imports.
    trace: Any = None
    # Streaming flow control (round 5): frontends with a bounded response
    # path (the gRPC stream writer) set this to a zero-arg callable that
    # returns True while the transport is backlogged.  Decoupled producers
    # (generative decode waves, repeat emit loops) then PAUSE production
    # for this request instead of flooding the queue — the slow-consumer
    # shed becomes the stalled-consumer last resort, not the first line.
    backpressure: Callable[[], bool] | None = None
    # Wave hand-off: a stream frontend that can take a whole decode wave's
    # tokens in one call declares its stream's :class:`TokenSink` here, at
    # submit.  The generative scheduler then posts ONE :class:`TokenWave`
    # per fetched wave to ``token_sink.writer`` and no per-token
    # ``InferResponse``; the stream's final response (and every error) still
    # arrives on ``response_callback``, after the wave that held its last
    # token.  None (every other frontend and scheduler): one
    # ``InferResponse`` per token on ``response_callback``, as ever.
    token_sink: "TokenSink | None" = None

    def cancel(self) -> None:
        self.cancelled = True

    def set_deadline_from_timeout_ms(self, timeout_ms: float) -> None:
        """Arm the end-to-end deadline from a client budget in ms
        (non-positive budgets leave the request deadline-free)."""
        if timeout_ms > 0:
            self.deadline_ns = now_ns() + int(timeout_ms * 1_000_000)

    def deadline_expired(self, now: int | None = None) -> bool:
        return self.deadline_ns > 0 and \
            (now if now is not None else now_ns()) >= self.deadline_ns

    def deadline_remaining_s(self) -> float | None:
        """Seconds until the deadline (None when no deadline is set)."""
        if self.deadline_ns <= 0:
            return None
        return (self.deadline_ns - now_ns()) / 1e9

    def requested_output_names(self) -> list[str]:
        return [o.name for o in self.outputs]


class TokenSink:
    """One stream's end of the wave hand-off (``InferRequest.token_sink``).

    ``writer`` takes the records: ``writer.post(wave)`` is called once per
    fetched wave, on the scheduler's worker thread, with every lane of the
    wave that names this writer; it must not block.  ``chunk_ts_ns`` is the
    engine's: where a traced request keeps the clock of its first streamed
    tokens (``observability.tracing``), None for an untraced one."""

    __slots__ = ("writer", "chunk_ts_ns")

    def __init__(self, writer):
        self.writer = writer
        self.chunk_ts_ns: list[int] | None = None


class TokenWave:
    """What one fetched wave hands a stream writer: lane by lane the sink,
    its token and the token's index in its stream (parallel lists, emission
    order: a K-chunk fetch holds a stream K times).  ``model_version`` is
    the serving model's, for a request that named none."""

    __slots__ = ("model_version", "sinks", "tokens", "indices")

    def __init__(self, model_version: str):
        self.model_version = model_version
        self.sinks: list[TokenSink] = []
        self.tokens: list[int] = []
        self.indices: list[int] = []


@dataclass
class InferResponse:
    model_name: str
    model_version: str
    request_id: str = ""
    outputs: dict[str, np.ndarray] = field(default_factory=dict)
    parameters: dict[str, Any] = field(default_factory=dict)
    error: EngineError | None = None
    final: bool = True            # False for non-terminal decoupled responses
    times: RequestTimes | None = None

    @classmethod
    def make_error(cls, req: InferRequest, exc: Exception) -> "InferResponse":
        err = exc if isinstance(exc, EngineError) else EngineError(str(exc), 500)
        return cls(
            model_name=req.model_name,
            model_version=req.model_version or "1",
            request_id=req.request_id,
            error=err,
            times=req.times,
        )


def token_response(req: InferRequest, model_version: str, token: int,
                   index: int) -> InferResponse:
    """A generation stream's response for one token: what a stream without a
    ``token_sink`` receives per token, and what a sink's writer renders its
    wire template from."""
    return InferResponse(
        model_name=req.model_name,
        model_version=req.model_version or model_version,
        request_id=req.request_id,
        outputs={"TOKEN": np.array([token], np.int32),
                 "INDEX": np.array([index], np.uint32)},
        parameters={"triton_final_response": False},
        final=False,
        times=req.times,
    )
