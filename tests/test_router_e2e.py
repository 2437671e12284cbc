"""Router end-to-end against real server subprocesses.

The two acceptance scenarios of the router PR, run against genuine
``python -m client_tpu.server`` processes (not in-process servers — the
chaos here is process death and SIGTERM, which only means something
across a process boundary):

* **failover**: SIGKILL one of two replicas mid-burst; the client sees
  zero errors (the router replays in-flight transport failures onto the
  survivor), the killed replica's breaker opens within one breaker
  window, and all subsequent traffic lands on the survivor;
* **rolling drain**: with client traffic flowing, walk one replica
  through the coordinated drain (readiness gate -> quiesce -> SIGTERM ->
  observe) — zero dropped in-flight requests, the process exits 0, and
  the fleet keeps serving.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import client_tpu.http as httpclient
from client_tpu.resilience import CircuitBreaker
from client_tpu.router import Replica, Router, RouterHttpServer, rolling_drain

pytestmark = pytest.mark.chaos

BOOT_TIMEOUT_S = 90.0


class _ReplicaProc:
    """One `python -m client_tpu.server` subprocess and its parsed URL."""

    def __init__(self, drain_deadline=10.0):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "client_tpu.server", "--zoo", "simple",
             "--http-port", "0", "--no-grpc",
             "--drain-deadline", str(drain_deadline)],
            stderr=subprocess.PIPE, text=True)
        self.url = None
        self.stderr_lines = []
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while self.url is None and time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    "replica died at boot:\n" + "".join(self.stderr_lines))
            time.sleep(0.05)
        if self.url is None:
            self.kill()
            raise RuntimeError(
                "replica never announced its URL:\n"
                + "".join(self.stderr_lines))

    def _read(self):
        for line in self.proc.stderr:
            self.stderr_lines.append(line)
            if line.startswith("serving http at "):
                self.url = line.split("serving http at ", 1)[1].strip()

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)


@pytest.fixture
def fleet():
    """Two subprocess replicas fronted by a standalone router."""
    procs = [_ReplicaProc(), _ReplicaProc()]
    router = Router(
        [Replica(p.url, pid=p.proc.pid) for p in procs],
        breaker=CircuitBreaker(failure_threshold=3, cooldown_s=1.0),
        poll_interval_s=0.5, seed=42)
    srv = RouterHttpServer(router, port=0).start()
    yield {"procs": procs, "router": router, "srv": srv,
           "url": srv.url}
    srv.stop()
    for p in procs:
        p.kill()


def _inputs():
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    i0 = httpclient.InferInput("INPUT0", a.shape, "INT32")
    i0.set_data_from_numpy(a)
    i1 = httpclient.InferInput("INPUT1", b.shape, "INT32")
    i1.set_data_from_numpy(b)
    return a + b, [i0, i1]


def _status(url):
    return json.loads(urllib.request.urlopen(
        f"http://{url}/v2/router/status", timeout=5).read())


# How long a state the test waits for may take to come about on a host that
# five other test workers load: the test asserts the state, not the time.
STATE_TIMEOUT_S = 60.0


def _wait_for(state):
    """Poll ``state()`` until it is true (a scrape that times out on a loaded
    host is polled again)."""
    deadline = time.monotonic() + STATE_TIMEOUT_S
    while time.monotonic() < deadline:
        try:
            if state():
                return True
        except OSError:
            pass
        time.sleep(0.1)
    return False


def test_failover_zero_client_errors(fleet):
    """Kill one of two replicas mid-burst: the burst completes with zero
    client-visible errors, the breaker opens on the corpse, and traffic
    rebalances onto the survivor."""
    expect, inputs = _inputs()
    client = httpclient.InferenceServerClient(fleet["url"], concurrency=4)
    errors = []
    served = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            try:
                result = client.infer("simple", inputs)
                assert (result.as_numpy("OUTPUT0") == expect).all()
                with lock:
                    served[0] += 1
            except Exception as exc:  # noqa: BLE001
                with lock:
                    errors.append(repr(exc))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    victim_id, survivor = (r.id for r in fleet["router"].replicas)
    # A warm burst against both replicas: each has answered.
    assert _wait_for(lambda: len(_count_ok(fleet["url"])) == 2), \
        "a replica never answered"

    victim_proc = fleet["procs"][0]
    os.kill(victim_proc.proc.pid, signal.SIGKILL)
    victim_proc.proc.wait(timeout=10)

    # The killed replica is circuit-broken (3 consecutive transport
    # failures): its breaker has left "closed" (open, or half-open between
    # a cooldown and the probe that fails).
    opened = _wait_for(
        lambda: _status(fleet["url"])["replicas"][victim_id]["breaker"]
        != "closed")
    # Keep serving through the open-breaker regime: a burst more.
    with lock:
        through = served[0] + 20
    carried_on = _wait_for(lambda: served[0] >= through or errors)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    client.close()

    assert not errors, f"client saw {len(errors)} errors: {errors[:3]}"
    assert opened, "killed replica's breaker never opened"
    assert carried_on, "traffic stopped behind the open breaker"

    # Traffic continues: the survivor alone carries new requests.
    before = _count_ok(fleet["url"])
    expect2, inputs2 = _inputs()
    c2 = httpclient.InferenceServerClient(fleet["url"])
    for _ in range(10):
        assert (c2.infer("simple", inputs2).as_numpy("OUTPUT0")
                == expect2).all()
    c2.close()
    after = _count_ok(fleet["url"])
    assert after[victim_id] == before.get(victim_id, 0.0), \
        "dead replica still receiving traffic"
    assert after[survivor] >= before.get(survivor, 0.0) + 10


def _count_ok(url):
    text = urllib.request.urlopen(f"http://{url}/metrics",
                                  timeout=5).read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith('tpu_router_requests_total{') \
                and 'outcome="ok"' in line:
            replica = line.split('replica="', 1)[1].split('"', 1)[0]
            out[replica] = float(line.rsplit(" ", 1)[1])
    return out


def test_rolling_drain_zero_dropped(fleet):
    """Coordinated rolling drain of one replica under live traffic:
    nothing dropped, the drained process exits 0, fleet keeps serving."""
    expect, inputs = _inputs()
    client = httpclient.InferenceServerClient(fleet["url"], concurrency=2)
    errors, completed = [], [0]
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            try:
                result = client.infer("simple", inputs)
                assert (result.as_numpy("OUTPUT0") == expect).all()
                completed[0] += 1
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.5)

    victim = fleet["procs"][0]
    victim_id = fleet["router"].replicas[0].id
    reports = rolling_drain(fleet["router"], [victim_id], deadline_s=30.0)
    assert reports[0]["outcome"] in ("clean", "gone"), reports
    victim.proc.wait(timeout=30)
    assert victim.proc.returncode == 0, \
        f"drained replica exited {victim.proc.returncode}"

    time.sleep(0.5)  # fleet keeps serving after the walk
    stop.set()
    for t in threads:
        t.join(timeout=30)
    client.close()
    assert not errors, f"drain dropped requests: {errors[:3]}"
    assert completed[0] > 0

    # The drained replica stays out of the eligible set.
    status = _status(fleet["url"])
    assert victim_id not in status["eligible"]
