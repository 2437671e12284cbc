"""Server-sent events for ``/generate_stream``: the wire format, and the one
thread that writes every stream of an HTTP server.

A generation stream used to be a thread of its own: the scheduler built one
``InferResponse`` per token and put it on that thread's queue, and the thread
woke, rendered the JSON and wrote the chunk.  With 33 streams in a decode wave
that is 33 wake-ups and 33 hand-offs of the interpreter lock per device step,
paid by the scheduler's worker (PERF.md section 5).  Here the hand-off is one
per wave: a stream declares itself on its request (``InferRequest.token_sink``
is its :class:`SseStream`), the generative scheduler posts one ``TokenWave`` a
fetched wave to :meth:`StreamWriter.post`, and each stream's event is filled
from a byte template made at the stream's first token and sent as one HTTP
chunk per token.  The connection's handler thread parks in
:meth:`SseStream.wait` until its stream's last byte has left.

**Who sends.**  A stream that is keeping up (nothing buffered, no test delay,
no coalescing) is written *through*: ``post`` sends its chunk on the caller's
thread, non-blocking, under the stream's lock; so is the terminal chunk of its
final response.  No thread is woken and the interpreter lock changes hands
for nobody: on the chip a writer thread that made the 33 sends of a wave
itself traded the lock with the worker at every send, and the hand-offs, not
the sends, set the pace (PERF.md section 6, PR 31).  The first send a socket
does not take whole, and everything that is not a wave's token on a stream
that keeps up (an error, a response of another scheduler, a delayed or
coalescing stream), moves the stream to the writer thread for the rest of its
life: the thread owns the buffers, waits for writability, and applies the
flow control below.

**The wire is what it was**, byte for byte: an event is ``data: `` + the v2
response head with its tensors as JSON (:func:`json_response_dict`, compact
separators) + a blank line, one chunk per response; the final empty response
is not sent; the terminal chunk is last; errors stay inside the chunked body.
Responses that reach the stream as ``InferResponse`` (every scheduler but the
generative one, and every stream's final response and errors) are rendered by
the same functions, so the template is an optimisation of one shape, never a
second format.

Sends never block: a socket that would block keeps the rest of its chunk and
the events behind it in the stream's own buffer (``SseStream.out``,
``SseStream.pending``) and is written again when it is writable; the other
streams go on.  Flow control reads that buffer: the request is back-pressured
at half the pending limit and cancelled as a slow consumer at the limit, by
the marks the per-stream threads had (``STREAM_PENDING_LIMIT``, a grace of
0.25 s without progress, eight times the limit whatever the progress).
"""

from __future__ import annotations

import collections
import json
import logging
import selectors
import socket
import ssl
import threading
import time
import uuid

from client_tpu.engine.types import (
    EngineError,
    InferRequest,
    InferResponse,
    TokenSink,
    TokenWave,
    token_response,
)
from client_tpu.protocol import rest
from client_tpu.protocol.dtypes import np_to_wire_dtype
from client_tpu.server.coalesce import drain_run

_log = logging.getLogger("client_tpu")

LAST_CHUNK = b"0\r\n\r\n"
# How long a stalled reader may make no progress past the pending limit
# before its request is cancelled.
CHOKE_GRACE_S = 0.25
_WOULD_BLOCK = (BlockingIOError, ssl.SSLWantWriteError, ssl.SSLWantReadError)
_ABANDON = object()
# Stand-ins for a token and its index while a stream's template is cut; no
# request can name them (they are this process's own).
_MARKS = tuple(f"@{name}-{uuid.uuid4().hex}@" for name in ("TOKEN", "INDEX"))


def json_response_dict(resp: InferResponse) -> dict:
    """v2 response head with all tensors as JSON data (no binary tails: SSE
    events and collected arrays are text)."""
    head: dict = {"model_name": resp.model_name,
                  "model_version": str(resp.model_version)}
    if resp.request_id:
        head["id"] = resp.request_id
    if resp.parameters:
        head["parameters"] = dict(resp.parameters)
    head["outputs"] = [
        rest.build_tensor_json(out_name, arr, np_to_wire_dtype(arr.dtype),
                               arr.shape, binary=False)[0]
        for out_name, arr in resp.outputs.items()
    ]
    return head


def _event(head: dict) -> bytes:
    return b"data: " + json.dumps(
        head, separators=(",", ":")).encode() + b"\n\n"


def response_event(resp: InferResponse) -> bytes:
    return _event(json_response_dict(resp))


def error_event(exc: Exception) -> bytes:
    return b"data: " + json.dumps({"error": str(exc)}).encode() + b"\n\n"


def chunk(payload: bytes) -> bytes:
    """One HTTP chunk (``Transfer-Encoding: chunked``)."""
    return b"%X\r\n%b\r\n" % (len(payload), payload)


def token_template(proto: InferResponse):
    """``(head, mid, tail)`` such that ``head + token + mid + index + tail``
    (the numbers in decimal) is :func:`response_event` of ``proto`` with that
    token and index, or None where ``proto`` is not TOKEN then INDEX, one
    value each."""
    head = json_response_dict(proto)
    outs = head["outputs"]
    if [o["name"] for o in outs] != ["TOKEN", "INDEX"] \
            or any(len(o["data"]) != 1 for o in outs):
        return None
    for out, mark in zip(outs, _MARKS):
        out["data"] = [mark]
    before, _, rest_ = _event(head).partition(b'"%s"' % _MARKS[0].encode())
    mid, _, tail = rest_.partition(b'"%s"' % _MARKS[1].encode())
    return before, mid, tail


def _token_chunk(s: "SseStream", version: str, token: int,
                 index: int) -> bytes:
    """The chunk of one token of ``s``, from its template (made here, at the
    stream's first token) where the response has the template's shape."""
    t = s.template
    if t is None:
        t = s.template = token_template(
            token_response(s.req, version, 0, 0)) or False
    if not t:
        return chunk(response_event(
            token_response(s.req, version, token, index)))
    return chunk(b"%b%d%b%d%b" % (t[0], token, t[1], index, t[2]))


class SseStream(TokenSink):
    """One ``/generate_stream`` response in the writer's hands, and its
    request's token sink.  Made by :meth:`StreamWriter.open`; everything but
    ``pending``'s length, ``received`` and ``done`` is the writer thread's."""

    __slots__ = ("req", "sock", "limit", "delay_s", "coalesce", "lock",
                 "inline", "lost", "pending", "out", "template", "received",
                 "progress", "armed", "choked", "not_before", "waiting",
                 "closing", "closed", "broken", "stalled", "done")

    def __init__(self, writer, req: InferRequest, sock, limit: int,
                 delay_s: float):
        super().__init__(writer)
        self.req = req
        self.sock = sock
        self.limit = limit            # slow-consumer mark, in events
        self.delay_s = delay_s        # test knob: pause after each event
        self.coalesce = bool(req.parameters.get("response_coalesce"))
        # Written through on the poster's thread (under ``lock``) while
        # True; the writer thread's once False, and never True again.
        self.lock = threading.Lock()
        self.inline = not (delay_s or self.coalesce)
        self.lost = False             # a written-through send found it dead
        # Events not yet on the wire, in order: ``(token, index)`` from a
        # wave, an ``InferResponse`` or an exception from the callback.
        self.pending: collections.deque = collections.deque()
        self.out = b""                # the rest of a chunk the socket refused
        self.template = None          # (head, mid, tail); False: none fits
        self.received = 0             # items the engine delivered
        self.progress = 0             # events written whole (the choke gate)
        self.armed = None             # (progress, monotonic) at the mark
        self.choked = False
        self.not_before = 0.0
        self.waiting = False          # registered for writability
        self.closing = False          # ``out`` ends with the terminal chunk
        self.closed = False
        self.broken = False           # the client is gone: do not reuse
        self.stalled = False
        self.done = threading.Event()

    def respond(self, resp: InferResponse) -> None:
        """The request's ``response_callback``: everything that is not a
        wave's token (a final response, an error, the responses of a
        scheduler that knows no waves)."""
        self.writer.post((self, resp))

    def fail(self, exc: Exception) -> None:
        """End the stream with an error event (the headers are out, so it
        stays inside the chunked body)."""
        self.req.cancel()
        self.writer.post((self, exc))

    def wait(self, stall_s: float) -> None:
        """Park the connection's thread until the stream's last byte left or
        its client died.  A stream the engine sends nothing for during
        ``stall_s`` is cancelled with an error event; one that then cannot
        even take that (its reader stopped for good) is abandoned."""
        seen = self.received
        while not self.done.wait(stall_s):
            if self.received != seen:
                seen = self.received
            elif not self.stalled:
                self.stalled = True
                self.fail(EngineError("generation stalled", 504))
            else:
                self.writer.post((self, _ABANDON))
                self.done.wait(stall_s)
                return


class StreamWriter:
    """An HTTP server's SSE streams: written through on the poster's thread
    while they keep up, by this writer's one thread once they do not."""

    def __init__(self):
        self._inbox: collections.deque = collections.deque()
        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, None)
        self._asleep = False
        self._stopping = False
        self._lock = threading.Lock()
        self._open: set[SseStream] = set()
        self._thread: threading.Thread | None = None
        # the writer thread's own
        self._dirty: dict[SseStream, None] = {}
        self._delayed: set[SseStream] = set()

    # -- any thread -------------------------------------------------------------

    def open(self, req: InferRequest, sock, limit: int,
             delay_s: float = 0.0) -> SseStream:
        """Take over ``sock`` (non-blocking, headers already sent) for the
        stream of ``req`` and declare the stream on the request."""
        stream = SseStream(self, req, sock, limit, delay_s)
        mark = max(1, limit // 2)
        req.token_sink = stream
        # Decode waves pause for this stream at HALF the cancel mark, so a
        # slow but alive reader is writer-paced and never reaches the cancel.
        req.backpressure = lambda: len(stream.pending) >= mark
        with self._lock:
            if not self._stopping:
                self._open.add(stream)
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="sse-writer", daemon=True)
                    self._thread.start()
                return stream
        # A server that is stopping writes nothing more.
        req.cancel()
        stream.inline = False
        stream.broken = stream.closed = True
        stream.done.set()
        return stream

    def post(self, item) -> None:
        """A ``TokenWave`` from a scheduler's worker, or ``(stream, what)``
        from a stream.  Never blocks."""
        if type(item) is TokenWave:
            item = self._write_through(item)
        elif item[0].inline:
            item = self._end_through(*item)
        if item is not None:
            self._inbox.append(item)
            self._wake()

    def _wake(self) -> None:
        if self._asleep:
            try:
                self._wake_w.send(b"\0")
            except BlockingIOError:
                pass  # the pipe is full of wake-ups already

    def stop(self) -> None:
        """End every open stream (their requests are cancelled) and the
        thread."""
        with self._lock:
            self._stopping = True
            thread = self._thread
        self._wake()
        if thread is not None:
            thread.join(timeout=5)

    def _write_through(self, wave: TokenWave):
        """Send, on the caller's thread, the chunk of every lane whose stream
        is keeping up; returns the wave of the lanes that are the writer
        thread's (a token of None: nothing new, look at the stream), or
        None."""
        version = wave.model_version
        rest = None
        for s, tok, idx in zip(wave.sinks, wave.tokens, wave.indices):
            if s.inline:
                with s.lock:
                    if s.inline:
                        s.received += 1
                        if self._send_through(
                                s, _token_chunk(s, version, tok, idx)):
                            continue
                        tok = None
            if rest is None:
                rest = TokenWave(version)
            rest.sinks.append(s)
            rest.tokens.append(tok)
            rest.indices.append(idx)
        return rest

    def _end_through(self, s: SseStream, what):
        """The final response of a stream that kept up: its terminal chunk
        goes the way its tokens went.  Returns what is left for the writer
        thread (``(s, None)``: look at the stream), or None."""
        if not (isinstance(what, InferResponse) and what.final
                and what.error is None and not what.outputs):
            return s, what
        with s.lock:
            if not s.inline:
                return s, what
            if self._send_through(s, LAST_CHUNK):
                self._finish(s)
                return None
            s.closing = True          # the rest of it is in ``s.out``
            return s, None

    @staticmethod
    def _send_through(s: SseStream, data: bytes) -> bool:
        """One non-blocking send under ``s.lock``; False hands the stream to
        the writer thread with the unsent rest in ``s.out``."""
        try:
            n = s.sock.send(data)
        except _WOULD_BLOCK:
            n = 0
        except OSError:
            s.lost, n, data = True, 0, b""
        if n == len(data) and not s.lost:
            s.progress += 1
            return True
        s.inline = False
        s.out = data[n:]
        return False

    def _finish(self, s: SseStream) -> None:
        s.inline = False
        s.closed = True
        with self._lock:
            self._open.discard(s)
        s.done.set()

    # -- the writer thread --------------------------------------------------------

    def _run(self) -> None:
        inbox, dirty = self._inbox, self._dirty
        while True:
            try:
                while inbox:
                    self._deliver(inbox.popleft())
                for s in list(dirty):
                    self._flush(s)
                dirty.clear()
                if self._stopping:
                    with self._lock:
                        left = list(self._open)
                    for s in left:
                        self._abandon(s)
                    self._sel.close()
                    self._wake_r.close()
                    self._wake_w.close()
                    return
                self._asleep = True
                if inbox or self._stopping:   # posted since the look above
                    self._asleep = False
                    continue
                timeout = None
                if self._delayed:
                    timeout = max(0.0, min(s.not_before for s in self._delayed)
                                  - time.monotonic())
                events = self._sel.select(timeout)
                self._asleep = False
                for key, _ in events:
                    if key.data is None:
                        try:
                            self._wake_r.recv(4096)
                        except BlockingIOError:
                            pass
                    else:
                        dirty[key.data] = None
                if self._delayed:
                    now = time.monotonic()
                    for s in [s for s in self._delayed
                              if s.not_before <= now]:
                        self._delayed.discard(s)
                        dirty[s] = None
            except Exception:  # noqa: BLE001 — the streams of a server hang on
                # this thread: log and go on.
                self._asleep = False
                _log.exception("SSE writer: iteration failed")

    def _deliver(self, item) -> None:
        dirty = self._dirty
        if type(item) is TokenWave:
            version = item.model_version
            for s, tok, idx in zip(item.sinks, item.tokens, item.indices):
                if s.closed:
                    continue
                self._take(s)
                if tok is not None:
                    s.pending.append((version, tok, idx))
                    s.received += 1
                dirty[s] = None
        else:
            s, what = item
            if s.closed:
                return
            self._take(s)
            if what is _ABANDON:
                self._abandon(s)
                return
            if what is not None:
                s.pending.append(what)
                s.received += 1
            dirty[s] = None

    @staticmethod
    def _take(s: SseStream) -> None:
        """From here on ``s`` is this thread's alone."""
        if s.inline:
            with s.lock:
                s.inline = False

    def _flush(self, s: SseStream) -> None:
        """Write what ``s`` holds, as far as its socket takes it."""
        if s.closed:
            return
        if s.lost:
            self._abandon(s)
            return
        try:
            try:
                self._write(s)
            except OSError:
                raise
            except Exception as exc:  # noqa: BLE001 — mid-stream failure:
                # tell the client inside the body, and stop generating.
                _log.exception("SSE writer: stream failed")
                s.req.cancel()
                s.pending.clear()
                s.out = chunk(error_event(exc)) + LAST_CHUNK
                s.closing = True
                self._write(s)
        except OSError:
            self._abandon(s)  # dead client: stop generating for it
        if not s.closed:
            self._check_backlog(s)

    def _write(self, s: SseStream) -> None:
        if s.delay_s and time.monotonic() < s.not_before:
            return
        while True:
            if s.out:
                try:
                    n = s.sock.send(s.out)
                except _WOULD_BLOCK as exc:
                    if isinstance(exc, ssl.SSLWantReadError):
                        # not a matter of writability: look again shortly
                        s.not_before = time.monotonic() + 0.001
                        self._delayed.add(s)
                        return
                    n = 0
                if n < len(s.out):
                    s.out = s.out[n:]
                    if not s.waiting:
                        s.waiting = True
                        self._sel.register(s.sock, selectors.EVENT_WRITE, s)
                    return
                s.out = b""
                s.progress += 1
                if s.closing:
                    self._close(s)
                    return
                if s.delay_s:
                    s.not_before = time.monotonic() + s.delay_s
                    self._delayed.add(s)
                    break
            if not s.pending:
                break
            s.out = self._next_chunk(s)
        if s.waiting:
            s.waiting = False
            self._sel.unregister(s.sock)

    def _next_chunk(self, s: SseStream) -> bytes:
        """The bytes of ``s``'s next event, taken off ``pending``: one
        response's, or with ``response_coalesce`` one for the rows already
        backlogged behind it."""
        item = s.pending.popleft()
        if type(item) is tuple:
            if not (s.coalesce and s.pending):
                return _token_chunk(s, *item)
            item = token_response(s.req, *item)
        if not isinstance(item, InferResponse):
            s.closing = True
            return chunk(error_event(item)) + LAST_CHUNK
        if item.error is not None:
            s.closing = True
            return chunk(error_event(item.error)) + LAST_CHUNK

        def get_nowait():
            if not s.pending:
                return None
            nxt = s.pending.popleft()
            if type(nxt) is tuple:
                return token_response(s.req, *nxt)
            return nxt if isinstance(nxt, InferResponse) else \
                InferResponse.make_error(s.req, nxt)

        merged, leftover = drain_run(item, get_nowait, s.req)
        if leftover is not None:
            s.pending.appendleft(leftover)
        out = b""
        if merged.outputs or not merged.final:
            out = chunk(response_event(merged))
        if merged.final:
            # The final empty response is not sent; the terminal chunk is.
            s.closing = True
            out += LAST_CHUNK
        return out

    def _check_backlog(self, s: SseStream) -> None:
        """Slow-consumer cancel.  The pipelined decoder rightly delivers
        depth x chunk rows that were in flight when back-pressure paused it,
        so crossing the mark only ARMS the cancel; it fires when a later
        delivery finds nothing written for the grace window (a reader that
        stopped), or at eight times the mark (a producer that ignores the
        probe)."""
        if s.choked:
            return
        size = len(s.pending)
        if size < s.limit:
            s.armed = None
            return
        if size < 8 * s.limit:
            now = time.monotonic()
            if s.armed is None or s.armed[0] != s.progress:
                s.armed = (s.progress, now)
                return
            if now - s.armed[1] < CHOKE_GRACE_S:
                return
        s.choked = True
        _log.warning(
            "generate stream backlog at %d pending responses (mark %d) with "
            "a stalled reader; cancelling request (slow consumer)", size,
            s.limit)
        s.req.cancel()

    def _abandon(self, s: SseStream) -> None:
        self._take(s)
        s.broken = True
        s.req.cancel()
        s.pending.clear()
        self._close(s)

    def _close(self, s: SseStream) -> None:
        if s.waiting:
            s.waiting = False
            try:
                self._sel.unregister(s.sock)
            except (KeyError, ValueError):
                pass
        self._delayed.discard(s)
        self._finish(s)
