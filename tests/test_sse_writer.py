"""The HTTP server's one SSE writer (``client_tpu/server/sse.py``): the wire
is byte for byte what the per-stream threads wrote, one chunk per token; a
reader that stops reading holds up nobody else, is throttled at the
back-pressure mark and cancelled at the limit; a dead client frees its slot.

The writer is driven directly over socket pairs (a wave's record in, bytes
out) and end to end through the server with the tiny generative model."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from client_tpu.engine import InferRequest, InferResponse, TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.types import (
    EngineError,
    TokenWave,
    token_response,
)
from client_tpu.models.generate import TinyGptBackend
from client_tpu.server import HttpInferenceServer
from client_tpu.server.http_server import _Handler
from client_tpu.server.sse import StreamWriter

MODEL = "sse_gpt"


def old_event(resp) -> bytes:
    """An event as ``h_generate_stream`` wrote it before the writer."""
    return b"data: " + json.dumps(
        _Handler._json_response_dict(resp),
        separators=(",", ":")).encode() + b"\n\n"


def old_error_event(exc) -> bytes:
    return b"data: " + json.dumps({"error": str(exc)}).encode() + b"\n\n"


def dechunk(raw: bytes):
    """(payloads of the chunks before the terminal one, saw the terminal
    chunk last)."""
    out, i = [], 0
    while i < len(raw):
        j = raw.index(b"\r\n", i)
        size = int(raw[i:j], 16)
        assert raw[i:j] == b"%X" % size      # upper-case hex, as before
        if size == 0:
            assert raw[j:] == b"\r\n\r\n"
            return out, True
        out.append(raw[j + 2:j + 2 + size])
        assert raw[j + 2 + size:j + 4 + size] == b"\r\n"
        i = j + 4 + size
    return out, False


def read_all(sock, timeout=10.0) -> bytes:
    sock.settimeout(timeout)
    out = b""
    while not out.endswith(b"0\r\n\r\n"):
        data = sock.recv(65536)
        if not data:
            break
        out += data
    return out


@pytest.fixture
def writer():
    w = StreamWriter()
    yield w
    w.stop()


def open_stream(writer, request_id="", limit=1024, sndbuf=None, **params):
    ours, theirs = socket.socketpair()
    if sndbuf:
        ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
    ours.setblocking(False)
    req = InferRequest(model_name="m", inputs={}, request_id=request_id,
                       parameters=params)
    stream = writer.open(req, ours, limit)
    return stream, req, ours, theirs


def wave(version, *lanes):
    w = TokenWave(version)
    for sink, tok, idx in lanes:
        w.sinks.append(sink)
        w.tokens.append(tok)
        w.indices.append(idx)
    return w


def final(req):
    return InferResponse(model_name=req.model_name, model_version="3",
                         request_id=req.request_id, outputs={},
                         parameters={"triton_final_response": True},
                         final=True)


# -- the wire -------------------------------------------------------------------

WIRE_CASES = {
    # name -> (request id, tokens handed over wave by wave, how it ends)
    "no_id_first_token": ("", [7], "final"),
    "with_id": ("req-9/a\"b", [0, 50256, 13], "final"),
    "last_token_of_many": ("", list(range(1000, 1012)), "final"),
    # the stop token itself never reaches the writer: the stream just ends
    "stop_token_ends_it_unsent": ("s", [5, 6], "final"),
    "no_token_at_all": ("", [], "final"),
    "error_mid_stream": ("e1", [11, 12], "error"),
    "refused_at_submit": ("", [], "exception"),
}


@pytest.mark.parametrize("case", WIRE_CASES)
def test_events_are_byte_for_byte_what_the_stream_threads_wrote(writer, case):
    request_id, tokens, ending = WIRE_CASES[case]
    stream, req, ours, theirs = open_stream(writer, request_id)
    for idx, tok in enumerate(tokens):
        writer.post(wave("3", (stream, tok, idx)))
    err = EngineError("request cancelled", 499)
    if ending == "final":
        stream.respond(final(req))
    elif ending == "error":
        stream.respond(InferResponse.make_error(req, err))
    else:
        stream.fail(err)
    stream.wait(10)
    assert stream.done.is_set() and not stream.broken
    events, terminated = dechunk(read_all(theirs))
    expected = [old_event(token_response(req, "3", tok, idx))
                for idx, tok in enumerate(tokens)]
    if ending != "final":
        expected.append(old_error_event(err))
        assert req.cancelled == (ending == "exception")
    # N tokens are N chunks, each the old bytes; the final empty response
    # is not sent; the terminal chunk is last.
    assert events == expected and terminated
    ours.close()
    theirs.close()


def test_a_chunked_fetch_is_one_chunk_per_token(writer):
    """A K-chunk fetch holds a stream K times in one record: still K
    events, in order."""
    a, req_a, sock_a, peer_a = open_stream(writer, "a")
    b, req_b, sock_b, peer_b = open_stream(writer)
    writer.post(wave("1", (a, 1, 0), (b, 9, 0), (a, 2, 1), (b, 8, 1),
                     (a, 3, 2)))
    a.respond(final(req_a))
    b.respond(final(req_b))
    got_a, _ = dechunk(read_all(peer_a))
    got_b, _ = dechunk(read_all(peer_b))
    assert got_a == [old_event(token_response(req_a, "1", t, i))
                     for i, t in enumerate([1, 2, 3])]
    assert got_b == [old_event(token_response(req_b, "1", t, i))
                     for i, t in enumerate([9, 8])]


def test_a_scheduler_that_knows_no_waves_streams_as_before(writer):
    """Per-response ``InferResponse``s through the callback (every
    scheduler but the generative one) render by the same functions,
    ``response_coalesce`` included: only rows backlogged together merge."""
    stream, req, ours, theirs = open_stream(writer, response_coalesce=True)
    stream.delay_s = 0.05            # the test knob: a slow writer
    resps = [token_response(req, "1", 40 + i, i) for i in range(6)]
    for r in resps:
        stream.respond(r)
    last = InferResponse(model_name="m", model_version="1",
                         outputs={"OUT": np.arange(3, dtype=np.float32)},
                         final=True)
    stream.respond(last)
    stream.wait(10)
    events, terminated = dechunk(read_all(theirs))
    assert terminated and events[-1] == old_event(last)
    rows = [json.loads(e[len(b"data: "):]) for e in events[:-1]]
    toks = [t for r in rows for t in r["outputs"][0]["data"]]
    assert toks == [40 + i for i in range(6)]
    assert len(rows) < 6             # the backlog merged


# -- isolation and flow control ---------------------------------------------------

def test_a_reader_that_stops_holds_up_nobody_else(writer):
    """One stream's reader never reads: its socket fills, its events wait in
    its own buffer, it is back-pressured at half the limit and cancelled at
    the limit after the grace; the other stream's tokens all arrive
    meanwhile, none delayed behind the blocked socket."""
    limit = 64
    stuck, req_stuck, sock_s, peer_s = open_stream(
        writer, "stuck", limit=limit, sndbuf=4096)
    peer_s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    good, req_good, sock_g, peer_g = open_stream(writer, "good", limit=limit)
    got = []
    reader = threading.Thread(target=lambda: got.append(read_all(peer_g)))
    reader.start()
    n = 400
    t0 = time.monotonic()
    for i in range(n):
        lanes = [(good, i, i)]
        if not req_stuck.cancelled:
            lanes.append((stuck, i, i))
        writer.post(wave("1", *lanes))
        if i == n // 2:
            # the blocked socket has long taken what it can: its stream is
            # throttled, the other is not
            time.sleep(0.3)
            assert req_stuck.backpressure() and not req_good.backpressure()
            assert len(stuck.pending) >= limit // 2
    good.respond(final(req_good))
    good.wait(10)
    took = time.monotonic() - t0
    reader.join(10)
    assert not reader.is_alive()
    events, terminated = dechunk(got[0])
    assert terminated and len(events) == n
    assert events[-1] == old_event(token_response(req_good, "1", n - 1, n - 1))
    assert took < 5.0, took          # never waited on the stuck socket
    # more deliveries past the limit, nothing written for the grace: the
    # slow-consumer cancel fires, as the per-stream choke did
    deadline = time.monotonic() + 5
    while not req_stuck.cancelled and time.monotonic() < deadline:
        time.sleep(0.1)
        writer.post(wave("1", (stuck, 0, 0)))
    assert req_stuck.cancelled and not req_good.cancelled
    # the scheduler answers a cancel with an error response; the stream ends
    # once its reader takes what is buffered
    stuck.respond(InferResponse.make_error(
        req_stuck, EngineError("request cancelled", 499)))
    raw = read_all(peer_s)
    stuck.wait(10)
    assert stuck.done.is_set() and not stuck.broken
    events, terminated = dechunk(raw)
    assert terminated and events[-1] == old_error_event("request cancelled")
    for s in (sock_s, peer_s, sock_g, peer_g):
        s.close()


def test_a_dead_client_cancels_its_request_and_wakes_its_thread(writer):
    stream, req, ours, theirs = open_stream(writer)
    theirs.close()
    deadline = time.monotonic() + 5
    i = 0
    while not stream.done.is_set() and time.monotonic() < deadline:
        writer.post(wave("1", (stream, i, i)))
        i += 1
        time.sleep(0.01)
    assert stream.done.is_set() and stream.broken and req.cancelled
    writer.post(wave("1", (stream, 1, 1)))     # late waves fall on the floor
    stream.respond(final(req))
    ours.close()


def test_a_stream_the_engine_forgets_is_cancelled_with_an_error(writer):
    """The stall guard: nothing from the engine for the timeout ends the
    stream with an error event inside the body."""
    stream, req, ours, theirs = open_stream(writer)
    writer.post(wave("1", (stream, 4, 0)))
    stream.wait(0.2)
    assert stream.done.is_set() and req.cancelled
    events, terminated = dechunk(read_all(theirs))
    assert terminated and events == [
        old_event(token_response(req, "1", 4, 0)),
        old_error_event("generation stalled")]


def test_stopping_the_writer_ends_open_streams(writer):
    stream, req, ours, theirs = open_stream(writer)
    writer.post(wave("1", (stream, 4, 0)))
    writer.stop()
    assert stream.done.wait(5) and stream.broken and req.cancelled
    late, late_req, *_ = open_stream(writer)   # opened after the stop
    assert late.done.is_set() and late_req.cancelled


# -- end to end -------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    repo = ModelRepository()
    repo.register_backend(TinyGptBackend(
        name=MODEL, max_streams=4, n_layers=2, max_seq_len=64))
    eng = TpuEngine(repo)
    srv = HttpInferenceServer(eng, port=0).start()
    yield eng, srv
    srv.stop()
    eng.shutdown()


def post_stream(srv, body: dict) -> socket.socket:
    host, port = srv.url.split(":")
    sock = socket.create_connection((host, int(port)), timeout=60)
    data = json.dumps(body).encode()
    sock.sendall(b"POST /v2/models/%s/generate_stream HTTP/1.1\r\n"
                 b"Host: x\r\nContent-Length: %d\r\n\r\n"
                 % (MODEL.encode(), len(data)) + data)
    return sock


def sse_body(sock) -> bytes:
    raw = b""
    while b"\r\n\r\n" not in raw:
        raw += sock.recv(65536)
    head, _, rest = raw.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200")
    while not rest.endswith(b"0\r\n\r\n"):
        data = sock.recv(65536)
        assert data, "the stream ended without its terminal chunk"
        rest += data
    return rest


def gen_counters(eng):
    """The model's committed counters, once the worker is back in its
    blocking wait: a stream's last chunk is written inside the iteration
    that commits its counters, so a client can be ahead of the profile."""
    from client_tpu.observability import spans

    sched = eng._schedulers[MODEL]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and (
            sched._streams or sched._inflight
            or sched._rec.open is not sched._rec.span[spans.S_IDLE]):
        time.sleep(0.005)
    g = eng.profile_snapshot(model=MODEL)["models"][f"{MODEL}:1"]["generative"]
    return g["counters"]


@pytest.mark.parametrize("request_id", ["", "turn-7"])
def test_n_tokens_arrive_as_n_chunks_of_the_old_bytes(served, request_id):
    eng, srv = served
    body = {"inputs": [{"name": "INPUT_IDS", "datatype": "INT32",
                        "shape": [3], "data": [1, 2, 3]}],
            "parameters": {"max_tokens": 9}}
    if request_id:
        body["id"] = request_id
    before = gen_counters(eng) if eng.profile_snapshot(model=MODEL)[
        "models"].get(f"{MODEL}:1", {}).get("generative") else None
    sock = post_stream(srv, body)
    events, terminated = dechunk(sse_body(sock))
    assert terminated and len(events) == 9
    req = InferRequest(model_name=MODEL, inputs={}, request_id=request_id)
    tokens = []
    for idx, ev in enumerate(events):
        tok = json.loads(ev[len(b"data: "):])["outputs"][0]["data"][0]
        assert ev == old_event(token_response(req, "1", tok, idx))
        tokens.append(tok)
    # a stop token ends the stream unsent, the terminal chunk still last
    body["parameters"]["stop_token_ids"] = tokens[4]
    sock2 = post_stream(srv, body)
    events2, terminated = dechunk(sse_body(sock2))
    first = tokens.index(tokens[4])
    assert terminated and events2 == events[:first]
    # the same connection serves the next request (keep-alive survived the
    # writer's non-blocking hands)
    data = json.dumps(body).encode()
    sock2.sendall(b"POST /v2/models/%s/generate_stream HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Length: %d\r\n\r\n"
                  % (MODEL.encode(), len(data)) + data)
    assert dechunk(sse_body(sock2))[0] == events2
    sock.close()
    sock2.close()
    # every token left through a wave's record, none as a response of its own
    after = gen_counters(eng)
    sent = 9 + 2 * first
    base = before or dict.fromkeys(after, 0)
    assert after["emitted_tokens"] - base["emitted_tokens"] == sent
    assert after["emitted_tokens_callback"] == base["emitted_tokens_callback"]
    assert 0 < after["emit_handoffs"] - base["emit_handoffs"] <= sent


def test_an_error_after_the_headers_stays_inside_the_body(served):
    eng, srv = served
    sock = post_stream(srv, {
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32", "shape": [3],
                    "data": [1, 2, 3]}],
        "parameters": {"max_tokens": 4000}})
    events, terminated = dechunk(sse_body(sock))
    assert terminated and len(events) == 1
    assert "max_seq_len" in json.loads(events[0][len(b"data: "):])["error"]
    sock.close()


def test_a_dead_client_frees_its_slot(served):
    eng, srv = served
    sched = eng._schedulers[MODEL]
    sock = post_stream(srv, {
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32", "shape": [2],
                    "data": [5, 6]}],
        "parameters": {"max_tokens": 60, "seed": 1, "temperature": 1.0}})
    assert sock.recv(64)                      # it streams
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    b"\x01\x00\x00\x00\x00\x00\x00\x00")   # RST on close
    sock.close()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and (
            sched._streams or len(sched._free) < 4):
        time.sleep(0.02)
    assert not sched._streams and len(sched._free) == 4
