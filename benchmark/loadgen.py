#!/usr/bin/env python3
"""The benchmark's own load generator (a worker process; never imports JAX).

    python3 benchmark/loadgen.py <spec.json>

KServe v2 HTTP binary (and SSE for ``generate_stream``) over raw keep-alive
sockets, one single-threaded event loop per worker: no lock and no thread
hand-off between a request's due time and its send.  Request bodies are
encoded and connections opened before the clock starts; the worker prints
``READY`` and waits for ``GO <t_zero>`` on stdin, where ``t_zero`` is the
window's start on ``time.monotonic()`` (one clock for every process on the
machine).

- Open loop: request ``k`` is sent at ``t_zero + due[k]`` on a free
  connection (sleep, then spin for the last millisecond and a half) and **timed
  from its due time**, so a stall counts against every request it delays.
  ``late_s`` is send time less due time.
- Closed loop: each of ``clients`` connections sends its next request the
  moment the previous one completes, walking the plan's cycle of requests
  round and round from before the window to after it.

The worker pins itself to ``cores``, and freezes and disables the garbage
collector before the clock starts.  It writes one JSON object of parallel
arrays to ``out``.
"""

from __future__ import annotations

import gc
import json
import os
import selectors
import socket
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import traffic as traffic_mod  # noqa: E402

HEAD, BODY, CHUNK = 0, 1, 2
SPIN_S = 0.0015  # epoll sleeps in whole milliseconds: wake early, spin


class Conn:
    __slots__ = ("sock", "buf", "req", "slot", "state", "need", "status",
                 "engine_ms", "n_events", "t_first")

    def __init__(self, sock):
        self.sock = sock
        self.buf = bytearray()
        self.req = -1
        self.state = HEAD
        self.need = 0
        self.status = 0
        self.engine_ms = -1.0
        self.n_events = 0
        self.t_first = 0.0


def connect(host: str, port: int) -> socket.socket:
    s = socket.create_connection((host, port), timeout=30)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    s.settimeout(30)
    return s


def header_value(head: bytes, name: bytes) -> bytes | None:
    """The value of header ``name`` (given with its colon) in a response
    head, or None."""
    i = head.find(b"\r\n" + name)   # a whole header name, not a suffix
    if i < 0:
        return None
    i += 2 + len(name)
    j = head.find(b"\r\n", i)
    return head[i:j if j > 0 else len(head)].strip()


def engine_ms_of(head: bytes) -> float:
    """Sum of the ``Server-Timing`` durations (queue + compute phases): the
    server's own account of the time the request spent inside the engine."""
    line = header_value(head, b"Server-Timing:")
    if line is None:
        return -1.0
    total = 0.0
    for part in line.split(b","):
        name, _, rest = part.strip().partition(b";dur=")
        if rest and name != b"compile":
            total += float(rest)
    return total


class Worker:
    def __init__(self, spec: dict):
        self.spec = spec
        self.host, self.port = spec["host"], int(spec["port"])
        self.sel = selectors.DefaultSelector()
        self.free: list[Conn] = []
        self.conns: list[Conn] = []
        # results, one entry per request sent
        self.r_idx: list[int] = []
        self.r_due: list[float] = []
        self.r_sent: list[float] = []
        self.r_first: list[float] = []
        self.r_done: list[float] = []
        self.r_status: list[int] = []
        self.r_engine_ms: list[float] = []
        self.r_events: list[int] = []
        self.ev_slot: list[int] = []
        self.ev_t: list[float] = []
        self.closed_loop = False
        self.next_closed = 0
        self.bodies: list[bytes] = []
        self.body_index = []
        self.t_stop = float("inf")
        self.send_cap = float("inf")
        self.n_done = 0
        self.reconnects = 0

    def open_conns(self, n: int) -> None:
        for _ in range(n):
            c = Conn(connect(self.host, self.port))
            self.sel.register(c.sock, selectors.EVENT_READ, c)
            self.conns.append(c)
            self.free.append(c)

    def send(self, c: Conn, k: int, t_due: float) -> None:
        c.req = k
        c.slot = len(self.r_idx)
        c.state, c.status, c.engine_ms, c.n_events = HEAD, 0, -1.0, 0
        c.t_first = 0.0
        self.r_idx.append(k)
        self.r_due.append(t_due)
        self.r_first.append(0.0)
        self.r_done.append(0.0)
        self.r_status.append(0)
        self.r_engine_ms.append(-1.0)
        self.r_events.append(0)
        c.sock.sendall(self.bodies[self.body_index[k]])
        self.r_sent.append(time.monotonic())

    def complete(self, c: Conn, now: float) -> None:
        s = c.slot
        self.r_first[s] = c.t_first or now
        self.r_done[s] = now
        self.r_status[s] = c.status
        self.r_engine_ms[s] = c.engine_ms
        self.r_events[s] = c.n_events
        c.req = -1
        c.state = HEAD
        self.n_done += 1
        if (self.closed_loop and now < self.t_stop
                and self.next_closed < self.send_cap):
            k = self.next_closed
            self.next_closed += 1
            self.send(c, k % len(self.body_index), now)
        else:
            self.free.append(c)

    def fail(self, c: Conn, now: float) -> None:
        """The peer closed or reset: the request failed (status 0 unless a
        status line arrived); the connection is replaced."""
        self.sel.unregister(c.sock)
        c.sock.close()
        busy = c.req >= 0
        c.buf.clear()
        c.sock = connect(self.host, self.port)
        self.sel.register(c.sock, selectors.EVENT_READ, c)
        self.reconnects += 1
        if busy:
            c.status = c.status if c.status >= 400 else 0
            self.complete(c, now)

    def on_readable(self, c: Conn) -> None:
        try:
            data = c.sock.recv(262144)
        except (ConnectionResetError, socket.timeout):
            data = b""
        now = time.monotonic()
        if not data:
            self.fail(c, now)
            return
        buf = c.buf
        buf += data
        while True:
            if c.state == HEAD:
                i = buf.find(b"\r\n\r\n")
                if i < 0:
                    return
                head = bytes(buf[:i])
                del buf[:i + 4]
                c.status = int(head[9:12])
                if b"chunked" in head:
                    c.state = CHUNK
                else:
                    c.need = int(header_value(head, b"Content-Length:"))
                    c.engine_ms = engine_ms_of(head)
                    c.state = BODY
            if c.state == BODY:
                if len(buf) < c.need:
                    return
                del buf[:c.need]
                self.complete(c, now)
                return
            if c.state == CHUNK:
                j = buf.find(b"\r\n")
                if j < 0:
                    return
                size = int(bytes(buf[:j]), 16)
                if len(buf) < j + size + 4:
                    return
                if size == 0:
                    del buf[:j + 4]
                    self.complete(c, now)
                    return
                del buf[:j + size + 4]
                c.n_events += 1
                if not c.t_first:
                    c.t_first = now
                self.ev_slot.append(c.slot)
                self.ev_t.append(now)

    def poll(self, timeout: float) -> None:
        for key, _ in self.sel.select(timeout):
            self.on_readable(key.data)

    # -- phases ---------------------------------------------------------------

    def run_open(self, due: list[float], t_end: float) -> None:
        """Arrivals on schedule (``due`` on the monotonic clock); stops at
        ``t_end`` (requests still in flight are left to the drain)."""
        n, k = len(due), 0
        while True:
            now = time.monotonic()
            while k < n and due[k] <= now and self.free:
                self.send(self.free.pop(), k, due[k])
                k += 1
            if now >= t_end:
                return
            nxt = due[k] if k < n and self.free else t_end
            wait = min(nxt, t_end) - now
            self.poll(wait - SPIN_S if wait > SPIN_S else 0.0)

    def run_closed(self, clients: int, t_end: float | None,
                   total: int | None, stagger_s: float = 0.0) -> None:
        """``clients`` connections each keep one request in flight until
        ``t_end``; with ``total`` (warm-up) exactly that many requests are
        sent and the run ends when all have completed.  With ``stagger_s``
        the clients start evenly spread over that many seconds, so that
        long requests are in every phase of their life from the start."""
        self.closed_loop = True
        self.t_stop = t_end if t_end is not None else float("inf")
        self.send_cap = total if total is not None else float("inf")
        t_begin = time.monotonic()
        n = min(clients, len(self.free))
        starts = [t_begin + i * stagger_s / n for i in range(n)]
        deadline = t_begin + float(self.spec.get("deadline_s", 600))
        started = 0
        while True:
            now = time.monotonic()
            while (started < n and starts[started] <= now
                   and self.next_closed < self.send_cap):
                k = self.next_closed
                self.next_closed += 1
                started += 1
                self.send(self.free.pop(), k % len(self.body_index), now)
            if total is None:
                if now >= t_end:
                    return
                horizon = t_end
            else:
                if self.n_done >= total or now >= deadline:
                    return
                horizon = deadline
            if started < n:
                horizon = min(horizon, starts[started])
            self.poll(min(0.05, max(0.0, horizon - now)))

    def results(self) -> dict:
        return {"idx": self.r_idx, "due": self.r_due, "sent": self.r_sent,
                "first": self.r_first, "done": self.r_done,
                "status": self.r_status, "engine_ms": self.r_engine_ms,
                "events": self.r_events, "ev_slot": self.ev_slot,
                "ev_t": self.ev_t, "reconnects": self.reconnects}


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    if spec.get("cores"):
        os.sched_setaffinity(0, set(spec["cores"]))
    cfg, tr = spec["config"], spec["traffic"]
    seed, seconds = int(spec["seed"]), float(spec["seconds"])
    w, nw = int(spec.get("worker", 0)), int(spec.get("workers", 1))
    worker = Worker(spec)
    phase = spec["phase"]            # "warmup" | "window"
    if phase == "warmup":
        phases = ["warmup"]
    elif tr["loop"] == "open":
        phases = ["preroll", "window"]
    else:
        phases = ["window"]
    # One schedule: the phases one after another; this worker takes every
    # nw-th request of it.
    bodies: list[bytes] = []
    due: list[float] = []
    for name in phases:
        plan = traffic_mod.build_plan(cfg, tr, seed, seconds, name)
        bodies += plan.bodies
        due += plan.due.tolist()
    mine = range(w, len(bodies), nw)
    worker.bodies = bodies
    worker.body_index = list(mine)

    if phase == "warmup":
        n_conn = len(worker.body_index)
    elif tr["loop"] == "open":
        n_conn = -(-int(tr["connections"]) // nw)
    else:
        n_conn = -(-int(tr["clients"]) // nw)
    worker.open_conns(n_conn)
    gc.collect()
    gc.freeze()
    gc.disable()
    print("READY", flush=True)
    go = sys.stdin.readline().split()
    if not go or go[0] != "GO":
        return 2
    t_zero = float(go[1])
    t_end = t_zero + seconds
    if phase == "warmup":
        worker.run_closed(n_conn, None, len(worker.body_index))
    elif tr["loop"] == "open":
        worker.run_open([t_zero + due[k] for k in mine], t_end)
        # No new arrivals after the window; give what is in flight
        # ``drain_s`` to finish (a request still open then has failed).
        t_drain = t_end + float(tr.get("drain_s", 0))
        while (worker.n_done < len(worker.r_idx)
               and time.monotonic() < t_drain):
            worker.poll(0.01)
    else:
        # closed loop starts preroll_s before the window, at full depth
        while time.monotonic() < t_zero - float(tr.get("preroll_s", 0)):
            time.sleep(0.001)
        worker.run_closed(n_conn, t_end, None,
                          float(tr.get("stagger_s", 0)))
    out = worker.results()
    out["t_zero"], out["t_end"] = t_zero, t_end
    out["gidx"] = [int(worker.body_index[k]) for k in out["idx"]]
    with open(spec["out"], "w") as f:
        json.dump(out, f)
    for c in worker.conns:
        c.sock.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
