"""HTTP/REST frontend: the KServe v2 endpoint surface.

Routes mirror what the reference client calls (http_client.cc:1241-1245 for
infer, http_client.h:112-341 for the control plane): health, metadata,
config, stats, repository control, shared-memory registration, and
``POST /v2/models/<m>[/versions/<v>]/infer`` with the JSON + binary-tensor
body split by ``Inference-Header-Content-Length``. Request bodies may be
deflate/gzip compressed (the reference client can send both,
http_client.cc:122-198); responses compress when the client accepts it.

Implementation: stdlib ThreadingHTTPServer — each connection gets a thread;
actual device work is serialized by the engine's per-model schedulers, so the
frontend threads only do framing.
"""

from __future__ import annotations

import gzip
import json
import logging
from client_tpu import config as envcfg
import re
import threading
import time
import zlib
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

_log = logging.getLogger("client_tpu")

from client_tpu.engine.engine import TpuEngine
from client_tpu.engine.types import EngineError, InferRequest, OutputRequest
from client_tpu.faults import FaultInjected
from client_tpu.observability.tracing import (
    TraceContext,
    server_timing_header,
)
from client_tpu.protocol import rest
from client_tpu.protocol.loadreport import LOAD_HEADER, encode_header
from client_tpu.protocol.pushback import (
    RETRY_AFTER_HEADER,
    format_retry_after_s,
)
from client_tpu.server.classification import classify_output
from client_tpu.server.sse import StreamWriter, json_response_dict

_ROUTES: list[tuple[str, re.Pattern, str]] = [
    ("GET", re.compile(r"^/v2/health/live$"), "health_live"),
    ("GET", re.compile(r"^/v2/health/ready$"), "health_ready"),
    ("GET", re.compile(r"^/v2(?:/)?$"), "server_metadata"),
    ("GET", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?/ready$"), "model_ready"),
    ("GET", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?/config$"), "model_config"),
    ("GET", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?/stats$"), "model_stats"),
    ("GET", re.compile(r"^/v2/models/stats$"), "all_stats"),
    ("GET", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?$"), "model_metadata"),
    ("POST", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?/infer$"), "infer"),
    ("POST", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?"
                        r"/generate$"), "generate"),
    ("POST", re.compile(r"^/v2/models/([^/]+)(?:/versions/([^/]+))?"
                        r"/generate_stream$"), "generate_stream"),
    ("POST", re.compile(r"^/v2/repository/index$"), "repo_index"),
    ("POST", re.compile(r"^/v2/repository/models/([^/]+)/load$"), "repo_load"),
    ("POST", re.compile(r"^/v2/repository/models/([^/]+)/unload$"), "repo_unload"),
    ("GET", re.compile(r"^/v2/(systemsharedmemory|cudasharedmemory|tpusharedmemory)"
                       r"(?:/region/([^/]+))?/status$"), "shm_status"),
    ("POST", re.compile(r"^/v2/(systemsharedmemory|cudasharedmemory|tpusharedmemory)"
                        r"/region/([^/]+)/register$"), "shm_register"),
    ("POST", re.compile(r"^/v2/(systemsharedmemory|cudasharedmemory|tpusharedmemory)"
                        r"(?:/region/([^/]+))?/unregister$"), "shm_unregister"),
    ("GET", re.compile(r"^/v2/shm/ring(?:/([^/]+))?/status$"), "ring_status"),
    ("POST", re.compile(r"^/v2/shm/ring/([^/]+)/register$"), "ring_register"),
    ("POST", re.compile(r"^/v2/shm/ring(?:/([^/]+))?/unregister$"),
     "ring_unregister"),
    ("POST", re.compile(r"^/v2/shm/ring/([^/]+)/doorbell$"), "ring_doorbell"),
    ("GET", re.compile(r"^/v2/shm/dataset(?:/([^/]+))?/status$"),
     "dataset_status"),
    ("POST", re.compile(r"^/v2/shm/dataset/([^/]+)/register$"),
     "dataset_register"),
    ("POST", re.compile(r"^/v2/shm/dataset(?:/([^/]+))?/unregister$"),
     "dataset_unregister"),
    ("GET", re.compile(r"^/v2/trace/setting$"), "trace_setting"),
    ("POST", re.compile(r"^/v2/trace/setting$"), "trace_update"),
    ("GET", re.compile(r"^/v2/trace/requests$"), "trace_requests"),
    ("GET", re.compile(r"^/v2/events$"), "events"),
    ("GET", re.compile(r"^/v2/slo$"), "slo"),
    ("GET", re.compile(r"^/v2/profile$"), "profile"),
    ("GET", re.compile(r"^/v2/costs$"), "costs"),
    ("GET", re.compile(r"^/v2/qos$"), "qos"),
    ("GET", re.compile(r"^/v2/timeseries$"), "timeseries"),
    ("GET", re.compile(r"^/v2/memory$"), "memory"),
    ("GET", re.compile(r"^/v2/load$"), "load"),
    ("GET", re.compile(r"^/v2/debug/bundles$"), "debug_bundles"),
    ("GET", re.compile(r"^/v2/debug/bundles/([^/]+)$"), "debug_bundle"),
    ("POST", re.compile(r"^/v2/debug/capture$"), "debug_capture"),
    ("GET", re.compile(r"^/metrics$"), "metrics"),
]


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # Nagle on the server side interacts with client delayed-ACK to add a
    # ~40ms stall per response (the C++ client sets TCP_NODELAY; the server
    # must too — measured 44ms -> <2ms round-trip on the perf harness).
    disable_nagle_algorithm = True
    # Buffer response writes so header+body leave in one segment.
    wbufsize = 64 * 1024

    def handle_expect_100(self):
        # With buffered wfile the interim '100 Continue' would sit in the
        # buffer while we block reading the body — flush it out explicitly.
        result = super().handle_expect_100()
        self.wfile.flush()
        return result
    engine: TpuEngine = None  # patched onto the subclass by HttpInferenceServer
    verbose = False

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):  # noqa: A003
        if self.verbose:
            super().log_message(fmt, *args)

    def _dispatch(self, method: str) -> None:
        try:
            # Chaos site: before any request byte past the headers is
            # consumed. A "drop" action closes the keep-alive socket with
            # no response — exactly the stale-socket/idle-timeout shape
            # the client-side replay and RetryPolicy must absorb.
            try:
                self.engine.faults.fire("http.pre_read")
            except FaultInjected as exc:
                if exc.kind == "drop":
                    self.close_connection = True
                    return
                # The injected error must still drain the request body —
                # the same keep-alive hazard the normal path documents
                # below: unread POST bytes would prefix the next request
                # line on this socket and desync the connection.
                try:
                    if method == "POST":
                        self.rfile.read(
                            int(self.headers.get("Content-Length", 0) or 0))
                except (OSError, ValueError):
                    self.close_connection = True
                self._send_error(exc.status or 503, str(exc))
                return
            # Drain the request body up front: handlers that ignore it (e.g.
            # repository index with an empty JSON body) must not leave bytes
            # in the keep-alive stream, or they would prefix the next
            # request line and desync the connection.
            self._raw_body = (self.rfile.read(
                int(self.headers.get("Content-Length", 0) or 0))
                if method == "POST" else b"")
            for m, pat, name in _ROUTES:
                if m != method:
                    continue
                match = pat.match(self.path.split("?")[0])
                if match:
                    getattr(self, "h_" + name)(*match.groups())
                    return
            self._send_error(404, f"no route for {method} {self.path}")
        except EngineError as exc:
            self._send_error(exc.status, str(exc),
                             retry_after_s=getattr(exc, "retry_after_s",
                                                   None))
        except (json.JSONDecodeError, ValueError, KeyError, zlib.error,
                gzip.BadGzipFile) as exc:
            self._send_error(400, f"malformed request: {exc!r}")
        except BrokenPipeError:
            pass
        except Exception as exc:  # noqa: BLE001
            self._send_error(500, f"internal error: {exc}")
        finally:
            # A draining server must shed its keep-alive sockets: the
            # accept loop is already stopped, so a pooled client (the L7
            # router, a probe loop) holding a live connection would keep
            # this "drained" frontend answering indefinitely. Closing
            # after the in-flight response is what lets the fleet
            # observe the replica as gone.
            try:
                if not self.engine.is_ready():
                    self.close_connection = True
            # tpulint: allow[swallowed-exception] health probe must not break the response already sent
            except Exception:  # noqa: BLE001 — health probe must not
                pass           # break the response already sent

    def do_GET(self):  # noqa: N802
        self._dispatch("GET")

    def do_POST(self):  # noqa: N802
        self._dispatch("POST")

    def _read_body(self) -> bytes:
        body = self._raw_body
        encoding = (self.headers.get("Content-Encoding") or "").lower()
        if encoding == "deflate":
            body = zlib.decompress(body)
        elif encoding == "gzip":
            body = gzip.decompress(body)
        elif encoding:
            raise EngineError(f"unsupported Content-Encoding '{encoding}'", 415)
        return body

    def _send(self, status: int, body: bytes,
              content_type: str = "application/json",
              extra_headers: dict | None = None) -> None:
        accept = (self.headers.get("Accept-Encoding") or "").lower()
        headers = dict(extra_headers or {})
        if body and "gzip" in accept:
            body = gzip.compress(body, compresslevel=1)
            headers["Content-Encoding"] = "gzip"
        elif body and "deflate" in accept:
            body = zlib.compress(body, level=1)
            headers["Content-Encoding"] = "deflate"
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers.items():
            self.send_header(k, v)
        self.end_headers()
        if body:
            self.wfile.write(body)

    def _send_json(self, obj, status: int = 200) -> None:
        self._send(status, json.dumps(obj).encode("utf-8"))

    def _send_error(self, status: int, msg: str,
                    retry_after_s: float | None = None) -> None:
        # Admission/drain sheds carry server pushback: Retry-After in
        # fractional seconds (our RetryPolicy parses floats; proxies that
        # only read integral seconds round down harmlessly). The shared
        # formatter keeps the text identical to the gRPC metadata form.
        headers = {}
        if retry_after_s is not None:
            headers[RETRY_AFTER_HEADER] = format_retry_after_s(retry_after_s)
        if status in (429, 503):
            # A shed/drain rejection names the health state it came from,
            # so an L7 router can tell a DRAINING replica (stop routing,
            # don't breaker it) from an overloaded or dead one.
            try:
                headers["X-Health-State"] = self.engine.health_state()
            # tpulint: allow[swallowed-exception] telemetry must not mask the error being reported
            except Exception:  # noqa: BLE001 — telemetry must not mask
                pass           # the error being reported
        try:
            self._send(status, json.dumps({"error": msg}).encode("utf-8"),
                       extra_headers=headers or None)
        # tpulint: allow[swallowed-exception] peer may have gone away
        except Exception:  # noqa: BLE001 — peer may have gone away
            pass

    # -- handlers -----------------------------------------------------------

    def h_health_live(self):
        self._send(200 if self.engine.is_live() else 400, b"")

    def h_health_ready(self):
        # Readiness with nuance: 200 while serving (READY or DEGRADED —
        # degraded still accepts work), 503 while DRAINING/down. The state
        # rides in both the JSON body and a header so HEAD-style probes
        # that ignore bodies can still read it.
        state = (self.engine.health_state()
                 if hasattr(self.engine, "health_state")
                 else ("READY" if self.engine.is_ready() else "DRAINING"))
        ready = self.engine.is_ready()
        self._send(200 if ready else 503,
                   json.dumps({"state": state}).encode("utf-8"),
                   extra_headers={"X-Health-State": state})

    def h_server_metadata(self):
        md = self.engine.server_metadata()
        # trace (/v2/trace/setting) and generate (/v2/models/<m>/generate*)
        # are HTTP-frontend routes, so only this frontend advertises them.
        md["extensions"] = list(md["extensions"]) + ["trace", "generate"]
        self._send_json(md)

    def h_model_ready(self, name, version=None):
        ready = self.engine.model_is_ready(name, version or "")
        self._send(200 if ready else 400, b"")

    def h_model_metadata(self, name, version=None):
        self._send_json(self.engine.model_metadata(name, version or ""))

    def h_model_config(self, name, version=None):
        self._send_json(self.engine.model_config(name, version or ""))

    def h_model_stats(self, name, version=None):
        self._send_json(self.engine.model_statistics(name, version or ""))

    def h_all_stats(self):
        self._send_json(self.engine.model_statistics())

    def h_repo_index(self):
        self._send_json(self.engine.repository_index())

    def h_repo_load(self, name):
        body = self._read_body()
        params = {}
        if body:
            try:
                params = json.loads(body).get("parameters", {}) or {}
            except (ValueError, AttributeError):
                raise EngineError("malformed load request body", 400)
        if params:
            # Same policy as the gRPC frontend: explicit config/file
            # overrides are not supported by the in-process repository —
            # reject rather than silently load the on-disk config.
            raise EngineError(
                "load parameters (config/file overrides) are not supported",
                400)
        self.engine.load_model(name)
        self._send_json({})

    def h_repo_unload(self, name):
        body = self._read_body()
        unload_dependents = False
        if body:
            try:
                params = json.loads(body).get("parameters", {}) or {}
            except (ValueError, AttributeError):
                raise EngineError("malformed unload request body", 400)
            unload_dependents = bool(params.get("unload_dependents", False))
        self.engine.unload_model(name, unload_dependents=unload_dependents)
        self._send_json({})

    # -- shared memory control plane ----------------------------------------

    def _shm_manager(self, kind: str):
        if kind == "systemsharedmemory":
            mgr = self.engine.system_shm
        else:  # cudasharedmemory is served by the TPU region manager
            mgr = self.engine.tpu_shm
        if mgr is None:
            raise EngineError(f"{kind} is not enabled on this server", 400)
        return mgr

    OPENMETRICS_CT = "application/openmetrics-text; version=1.0.0; " \
                     "charset=utf-8"

    def h_metrics(self):
        # Content negotiation mirrors prometheus/client_python: a scraper
        # that Accepts application/openmetrics-text gets OpenMetrics 1.0
        # (exemplars, # EOF); everyone else the classic 0.0.4 text format.
        accept = self.headers.get("Accept", "") or ""
        om = "application/openmetrics-text" in accept
        body = self.engine.prometheus_metrics(openmetrics=om)
        self._send(200, body.encode("utf-8"),
                   content_type=(self.OPENMETRICS_CT if om
                                 else "text/plain; version=0.0.4"))

    def h_events(self):
        """Operational event timeline (``/v2/events``). Filters:
        ``?model=`` exact, ``?severity=`` minimum (DEBUG..ERROR),
        ``?category=``, ``?since=<seq>`` exclusive cursor (use the
        previous response's ``next_seq``), ``?since_wall=``/
        ``?until_wall=`` an epoch-seconds window (exclusive lower,
        inclusive upper), ``?limit=<n>`` newest n."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)

        def one(key):
            return (q.get(key) or [None])[0]

        def num(key, cast):
            raw = one(key)
            if raw is None:
                return None
            try:
                return cast(raw)
            except ValueError:
                raise EngineError(f"malformed {key!r} parameter", 400)

        # ``since_wall``/``until_wall`` are the wall-window pair shared
        # with /v2/timeseries; ``since_ts`` predates them and stays as
        # an alias for the lower bound.
        since_wall = num("since_wall", float)
        if since_wall is None:
            since_wall = num("since_ts", float)
        try:
            self._send_json(self.engine.events_export(
                model=one("model"), severity=one("severity"),
                category=one("category"), since_seq=num("since", int),
                since_ts=since_wall,
                until_ts=num("until_wall", float),
                limit=num("limit", int)))
        except ValueError as exc:  # unknown severity name
            raise EngineError(str(exc), 400)

    def h_slo(self):
        """Per-model SLO burn-rate report (``/v2/slo``)."""
        self._send_json(self.engine.slo_snapshot())

    def h_profile(self):
        """Efficiency profiler cost table (``/v2/profile``): per-model/
        per-bucket fill ratios, padding-waste device-seconds, compile
        counts, duty cycle. ``?model=`` filters to one model."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)
        model = (q.get("model") or [None])[0]
        self._send_json(self.engine.profile_snapshot(model=model))

    def h_costs(self):
        """Per-tenant cost ledger (``/v2/costs``): device-seconds,
        HBM-byte-seconds, queue-seconds, and interference attribution,
        with reconciliation against the profiler and HBM census.
        ``?model=`` filters per-model rows to one model."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)
        model = (q.get("model") or [None])[0]
        self._send_json(self.engine.costs_snapshot(model=model))

    def h_qos(self):
        """Tenant QoS status (``/v2/qos``): the class table (weights,
        quotas, governor throttle ratios, inflight, shed/preemption
        tallies) plus per-model WFQ lane depths. ``?model=`` narrows
        the lane depths to one model."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)
        model = (q.get("model") or [None])[0]
        self._send_json(self.engine.qos_snapshot(model=model))

    def h_timeseries(self):
        """Flight-recorder export (``/v2/timeseries``): the 1 Hz signal
        ring. Filters: ``?signal=`` one signal family, ``?model=``
        narrows per-model maps, ``?since=<seq>`` exclusive cursor (use
        the previous response's ``next_seq``), ``?since_wall=``/
        ``?until_wall=`` an epoch-seconds window (exclusive lower,
        inclusive upper), ``?limit=<n>`` newest n."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)

        def one(key):
            return (q.get(key) or [None])[0]

        def num(key, cast):
            raw = one(key)
            if raw is None:
                return None
            try:
                return cast(raw)
            except ValueError:
                raise EngineError(f"malformed {key!r} parameter", 400)

        try:
            self._send_json(self.engine.timeseries_export(
                signal=one("signal"), model=one("model"),
                since_seq=num("since", int),
                since_wall=num("since_wall", float),
                until_wall=num("until_wall", float),
                limit=num("limit", int)))
        except ValueError as exc:  # unknown signal name
            raise EngineError(str(exc), 400)

    def h_memory(self):
        """HBM census report (``/v2/memory``): per-(model, component)
        live device bytes, plan-vs-actual drift, watermark, pressure."""
        self._send_json(self.engine.memory_census())

    def h_load(self):
        """Replica load report (``/v2/load``): the pull form of the
        ``X-Tpu-Load`` response piggyback — health state, in-flight,
        queue depth, EWMA wait estimate, SLO fast-burn, loaded models.
        Routers bootstrap from this and refresh via piggyback."""
        report = self.engine.load_report()
        self._send(200, json.dumps(report.to_json_dict()).encode("utf-8"),
                   extra_headers={LOAD_HEADER: encode_header(report)})

    def h_debug_bundles(self):
        """Incident-blackbox bundle index (``/v2/debug/bundles``):
        retained bundles newest first, retention caps, capture
        counters."""
        self._send_json(self.engine.blackbox_bundles())

    def h_debug_bundle(self, bundle_id):
        """One full incident bundle (``/v2/debug/bundles/{id}``):
        the JSON document ``tools/blackbox_report.py`` renders.
        404 unknown id; 400 malformed id or corrupt bundle — never
        500."""
        self._send_json(self.engine.blackbox_bundles(bundle_id))

    def h_debug_capture(self):
        """Manual incident capture (``POST /v2/debug/capture``). Body
        keys (all optional): ``trigger`` (default ``manual``; an
        automatic trigger name respects debounce/cooldown and may
        return ``{"deduped": true}``), ``incident`` (share one id
        across a fleet), ``note`` (free text stored in the bundle)."""
        body = json.loads(self._read_body() or b"{}")
        if not isinstance(body, dict):
            raise EngineError("request body must be a JSON object", 400)
        self._send_json(self.engine.blackbox_capture(
            str(body.get("trigger") or "manual"),
            incident=body.get("incident") or None,
            note=body.get("note") or None))

    def h_trace_setting(self):
        self._send_json(self.engine.trace_setting())

    def h_trace_update(self):
        body = json.loads(self._read_body() or b"{}")
        self._send_json(self.engine.update_trace_setting(body))

    def h_trace_requests(self):
        """Chrome trace-event JSON of recently traced requests; open the
        result in chrome://tracing or Perfetto. ``?trace_id=<32hex>``
        filters to one request's timeline."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)
        trace_id = (q.get("trace_id") or [None])[0]
        self._send_json(self.engine.request_trace_export(trace_id))

    def h_shm_status(self, kind, region=None):
        self._send_json(self._shm_manager(kind).status(region))

    def h_shm_register(self, kind, region):
        body = json.loads(self._read_body() or b"{}")
        self._shm_manager(kind).register_from_json(region, body)
        self._send_json({})

    def h_shm_unregister(self, kind, region=None):
        self._read_body()
        self._shm_manager(kind).unregister(region)
        self._send_json({})

    # -- shm slot ring (zero-copy data plane; engine.shmring) ---------------

    def h_ring_status(self, name=None):
        self._send_json(self.engine.ring_shm.status(name))

    def h_ring_register(self, name):
        body = json.loads(self._read_body() or b"{}")
        self.engine.ring_shm.register_from_json(name, body)
        self._send_json({})

    def h_ring_unregister(self, name=None):
        self._read_body()
        self.engine.ring_shm.unregister(name)
        self._send_json({})

    def h_ring_doorbell(self, name):
        """The batched doorbell: one POST admits a whole span of FILLED
        slots; completions land in shm, not in this response."""
        spec = json.loads(self._read_body() or b"{}")
        self._send_json(self.engine.ring_doorbell(name, spec))

    # -- staged datasets (many-producer fan-in; engine.staged) --------------

    def h_dataset_status(self, name=None):
        self._send_json(self.engine.staged_shm.status(name))

    def h_dataset_register(self, name):
        body = json.loads(self._read_body() or b"{}")
        self.engine.staged_shm.register_from_json(name, body)
        self._send_json({})

    def h_dataset_unregister(self, name=None):
        self._read_body()
        self.engine.staged_shm.unregister(name)
        self._send_json({})

    # -- inference ----------------------------------------------------------

    def h_infer(self, name, version=None):
        req = self._parse_infer_request(name, version)
        resp = self.engine.infer(req)
        self._send_infer_response(req, resp)

    # Stall guard for the generate endpoints: how long to wait for the
    # next response of an in-flight stream before cancelling it.
    GENERATE_STALL_TIMEOUT_S = 300.0

    def h_generate(self, name, version=None):
        """Non-streaming generate: run a (possibly decoupled) model and
        return every response as a JSON array. The streaming variant below
        is the live-token path; this one is the curl-friendly collector."""
        req = self._parse_generate_request(name, version)
        out = []
        for resp in self._stream_responses(req):
            if resp.error is not None:
                raise resp.error
            if resp.final and not resp.outputs:
                continue
            out.append(self._json_response_dict(resp))
        self._send_json({"model_name": name, "responses": out})

    def h_generate_stream(self, name, version=None):
        """Server-sent events: one `data: <v2 response JSON>` event per
        decoupled response, chunked transfer, terminated by the final-flag
        response. A dead client cancels the request (the generative
        scheduler then frees its KV arena slot).  The events are not
        written by this thread: the stream is declared on the request
        (``server/sse.py``), a generative scheduler hands the server's
        stream writer a whole wave's tokens at once, and this thread parks
        until the stream's last byte has left."""
        req = self._parse_generate_request(name, version)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("Cache-Control", "no-cache")
        self.end_headers()
        self.wfile.flush()  # time-to-first-header, not time-to-first-token
        # Headers are out: from here every outcome must stay inside the
        # chunked body (a second status line would desync the stream), and
        # an abandoned request must stop generating.
        sock = self.connection
        timeout = sock.gettimeout()
        sock.setblocking(False)  # the writer's sends never block
        stream = self.stream_writer.open(
            req, sock, self._stream_pending_limit(),
            envcfg.env_float("CLIENT_TPU_STREAM_WRITER_DELAY_MS") / 1e3)
        try:
            try:
                self.engine.async_infer(req, stream.respond)
            except Exception as exc:  # noqa: BLE001 — refused at submit
                stream.fail(exc)
            stream.wait(self.GENERATE_STALL_TIMEOUT_S)
        finally:
            sock.settimeout(timeout)
        if stream.broken:
            self.close_connection = True

    def _parse_generate_request(self, name, version) -> InferRequest:
        req = self._parse_infer_request(name, version)
        for o in req.outputs:
            if o.shm_region or o.classification_count > 0 or o.binary:
                raise EngineError(
                    "generate endpoints return JSON tensors only; output "
                    "parameters (shared memory, classification, "
                    "binary_data) are not supported", 400)
        return req

    # Slow-consumer bound for SSE streams: responses pending unread before
    # the request is cancelled (the generative scheduler then stops
    # producing at the next wave) — a stalled reader caps memory. One SSE
    # stream carries ONE request, so cancelling it is already per-request.
    STREAM_PENDING_LIMIT = 1024

    def _stream_pending_limit(self) -> int:
        """Read the env knob per stream (not at import) so it matches the
        gRPC servicer's construction-time semantics."""
        return max(1, envcfg.env_int("CLIENT_TPU_STREAM_PENDING_LIMIT"))

    def _stream_responses(self, req: InferRequest):
        """Submit and yield responses until the final one (``/generate``'s
        collector; ``/generate_stream`` has ``server/sse.py``); a stall
        cancels the request and raises 504; a backlog past
        STREAM_PENDING_LIMIT cancels it too (logged)."""
        import queue as q

        out_q: q.Queue = q.Queue()
        choked = [False]
        limit = self._stream_pending_limit()
        # Progress-gated cancel, mirroring the gRPC servicer's choke: the
        # pipelined decoder legitimately delivers depth x chunk rows that
        # were already in flight when backpressure paused it, so crossing
        # the mark only ARMS the cancel; it fires when a later enqueue
        # finds the writer advanced NOTHING for the grace window (a
        # reader that stopped draining), or at the 8x hard mark (memory
        # bound if a producer ignores the probe).
        progress = [0]   # rows yielded to the SSE writer
        armed = [None]   # (progress, monotonic) at backlog crossing

        def enqueue(resp):
            out_q.put(resp)
            if choked[0]:
                return
            size = out_q.qsize()
            if size < limit:
                armed[0] = None
                return
            if size < 8 * limit:
                p = time.monotonic()
                if armed[0] is None or armed[0][0] != progress[0]:
                    armed[0] = (progress[0], p)
                    return
                if p - armed[0][1] < 0.25:
                    return
            choked[0] = True
            _log.warning(
                "generate stream backlog at %d pending responses "
                "(mark %d) with a stalled reader; cancelling request "
                "(slow consumer)", size, limit)
            req.cancel()

        # Transport flow control (same contract as the gRPC stream
        # writer): decode waves pause for this stream at HALF the cancel
        # mark, so a slow-but-alive SSE reader is writer-paced (TCP
        # backpressure propagates here through the blocking chunk write)
        # and never reaches the cancel; the choke above remains the
        # backstop for a stalled reader, and the generative scheduler's
        # BACKPRESSURE_TIMEOUT_S reclaims the arena slot of a stream
        # throttled past its bound.
        bp_mark = max(1, limit // 2)
        req.backpressure = lambda: out_q.qsize() >= bp_mark

        self.engine.async_infer(req, enqueue)
        # Same coalescing contract as the gRPC stream writer (an SSE event
        # also pays per-message framing): with `response_coalesce` set,
        # rows already backlogged behind a slow chunk write merge into one
        # [k]-row event; off backlog every response ships alone.
        from client_tpu.server.coalesce import drain_run

        def get_nowait():
            try:
                return out_q.get_nowait()
            except q.Empty:
                return None

        delay_s = envcfg.env_float(
            "CLIENT_TPU_STREAM_WRITER_DELAY_MS") / 1e3
        while True:
            try:
                resp = out_q.get(timeout=self.GENERATE_STALL_TIMEOUT_S)
            except q.Empty:
                req.cancel()
                raise EngineError("generation stalled", 504) from None
            merged, leftover = drain_run(resp, get_nowait, req)
            for resp in ((merged,) if leftover is None
                         else (merged, leftover)):
                yield resp
                progress[0] += 1  # reader took an event (choke gate)
                if delay_s:
                    time.sleep(delay_s)
                if resp.error is not None or resp.final:
                    return

    @staticmethod
    def _json_response_dict(resp) -> dict:
        """v2 response head with all tensors as JSON data (no binary tails
        — SSE events and collected arrays are text)."""
        return json_response_dict(resp)

    def _parse_infer_request(self, name, version=None) -> InferRequest:
        body = self._read_body()
        header_len = self.headers.get(rest.HEADER_INFERENCE_CONTENT_LENGTH)
        head, tail = rest.split_body(
            body, int(header_len) if header_len is not None else None)

        inputs: dict[str, np.ndarray] = {}
        for wire in rest.parse_tensors(head.get("inputs", []), tail):
            shm_region = wire.parameters.get("shared_memory_region")
            if shm_region is not None:
                arr = self._read_shm_input(wire)
            else:
                arr = wire.to_numpy()
            inputs[wire.name] = arr

        outputs: list[OutputRequest] = []
        request_binary_all = bool(
            (head.get("parameters") or {}).get("binary_data_output", False))
        for o in head.get("outputs", []) or []:
            p = o.get("parameters", {}) or {}
            outputs.append(OutputRequest(
                name=o["name"],
                classification_count=int(p.get("classification", 0)),
                shm_region=p.get("shared_memory_region"),
                shm_offset=int(p.get("shared_memory_offset", 0)),
                shm_byte_size=int(p.get("shared_memory_byte_size", 0)),
                binary=bool(p.get("binary_data", request_binary_all)),
                parameters=p,
            ))

        params = head.get("parameters", {}) or {}
        req = InferRequest(
            model_name=name,
            model_version=version or "",
            request_id=head.get("id", ""),
            inputs=inputs,
            outputs=outputs,
            parameters=params,
            sequence_id=int(params.get("sequence_id", 0)),
            sequence_start=bool(params.get("sequence_start", False)),
            sequence_end=bool(params.get("sequence_end", False)),
            priority=int(params.get("priority", 0)),
            timeout_us=int(params.get("timeout", 0)),
            # Cost-ledger tenant: the `X-Tpu-Tenant` header (transport-
            # level, set by our client) or the `tenant` request parameter
            # (protocol-level, survives proxies that strip unknown
            # headers). Header wins, like timeout-ms below.
            tenant=str(self.headers.get("x-tpu-tenant")
                       or params.get("tenant", "") or ""),
            # Adopt the caller's W3C trace context (or start a new trace);
            # every HTTP inference is traced into the engine's ring buffer.
            trace=TraceContext.from_traceparent(
                self.headers.get("traceparent")),
        )
        # End-to-end deadline: the `timeout-ms` header (transport-level,
        # set by our HTTP client from its request budget) or the
        # `timeout_ms` request parameter (protocol-level, works through
        # proxies that strip unknown headers). Header wins — it reflects
        # the budget *remaining* at send time.
        timeout_ms = self.headers.get("timeout-ms") \
            or params.get("timeout_ms")
        if timeout_ms is not None:
            try:
                req.set_deadline_from_timeout_ms(float(timeout_ms))
            except (TypeError, ValueError):
                raise EngineError(
                    f"invalid timeout-ms value {timeout_ms!r}", 400) from None
        return req

    def _read_shm_input(self, wire) -> np.ndarray:
        return self.engine.read_shm_tensor(
            wire.parameters["shared_memory_region"],
            int(wire.parameters.get("shared_memory_offset", 0)),
            int(wire.parameters.get("shared_memory_byte_size", 0)),
            wire.datatype, wire.shape)

    def _send_infer_response(self, req: InferRequest, resp) -> None:
        entries = []
        cfg = None
        model = self.engine.repository.get(req.model_name)
        if model is not None:
            cfg = model.config
        out_req = {o.name: o for o in req.outputs}
        for out_name, arr in resp.outputs.items():
            o = out_req.get(out_name)
            # classification extension
            if o is not None and o.classification_count > 0:
                labels = None
                if cfg is not None:
                    labels = (cfg.parameters.get("labels") or {}).get(out_name)
                arr = classify_output(arr, o.classification_count, labels)
                entry, raw = rest.build_tensor_json(
                    out_name, arr, "BYTES", arr.shape,
                    binary=o.binary if o else False)
                entries.append((entry, raw))
                continue
            # shared-memory output placement
            if o is not None and o.shm_region:
                written = self._write_shm_output(o, arr)
                from client_tpu.protocol.dtypes import np_to_wire_dtype

                entry = {
                    "name": out_name,
                    "datatype": np_to_wire_dtype(arr.dtype),
                    "shape": list(arr.shape),
                    "parameters": {
                        "shared_memory_region": o.shm_region,
                        "shared_memory_offset": o.shm_offset,
                        "shared_memory_byte_size": written,
                    },
                }
                entries.append((entry, None))
                continue
            from client_tpu.protocol.dtypes import np_to_wire_dtype

            dt = np_to_wire_dtype(arr.dtype)
            # Binary encoding is opt-in (v2 binary-data extension default is
            # false): per-output binary_data param, or the request-wide
            # binary_data_output parameter for unlisted outputs.
            binary = o.binary if o is not None else bool(
                req.parameters.get("binary_data_output", False))
            entry, raw = rest.build_tensor_json(
                out_name, arr, dt, arr.shape, binary=binary)
            entries.append((entry, raw))

        body, jlen = rest.build_infer_response_body(
            entries, model_name=resp.model_name,
            model_version=resp.model_version, request_id=resp.request_id,
            parameters={k: v for k, v in resp.parameters.items()} or None)
        has_binary = any(raw is not None for _, raw in entries)
        headers = {}
        if has_binary:
            headers[rest.HEADER_INFERENCE_CONTENT_LENGTH] = str(jlen)
            ctype = "application/octet-stream"
        else:
            ctype = "application/json"
        # Round-trip the trace id (clients correlate against
        # /v2/trace/requests) and surface the server-side phase breakdown
        # as a standard Server-Timing header.
        if req.trace is not None:
            headers["traceparent"] = req.trace.to_traceparent()
        if resp.times is not None:
            headers["Server-Timing"] = server_timing_header(resp.times)
        # Load-report piggyback: every response refreshes the caller's
        # view of this replica's load, so steady-state L7 routing costs
        # zero extra RPCs (the report itself is cached engine-side).
        try:
            headers[LOAD_HEADER] = encode_header(self.engine.load_report())
        # tpulint: allow[swallowed-exception] telemetry must not fail a successful inference
        except Exception:  # noqa: BLE001 — telemetry must not fail a
            pass           # successful inference
        self._send(200, body, content_type=ctype, extra_headers=headers)

    def _write_shm_output(self, o: OutputRequest, arr: np.ndarray) -> int:
        return self.engine.write_shm_tensor(o.shm_region, o.shm_offset,
                                            o.shm_byte_size, arr)


class HttpInferenceServer:
    """Threaded v2 REST server over a TpuEngine."""

    def __init__(self, engine: TpuEngine, host: str = "127.0.0.1",
                 port: int = 8000, verbose: bool = False,
                 certfile: str | None = None, keyfile: str | None = None):
        # One writer thread for every SSE stream of this server, started
        # with the first stream.
        self._stream_writer = StreamWriter()
        handler = type("BoundHandler", (_Handler,),
                       {"engine": engine, "verbose": verbose,
                        "stream_writer": self._stream_writer})
        self.engine = engine
        # socketserver's default accept backlog (5) drops connections under
        # concurrent-client bursts — raise it before the socket listens.
        server_cls = type("_Httpd", (ThreadingHTTPServer,),
                          {"request_queue_size": 128})
        self.httpd = server_cls((host, port), handler)
        if certfile:
            # HTTPS endpoint (exercised by the native client's https://
            # support; the reference terminates TLS in libcurl).
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(certfile, keyfile)
            self.httpd.socket = ctx.wrap_socket(self.httpd.socket,
                                                server_side=True)
        self.httpd.daemon_threads = True
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    @property
    def url(self) -> str:
        host = self.httpd.server_address[0]
        return f"{host}:{self.port}"

    def start(self) -> "HttpInferenceServer":
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="http-server", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self._stream_writer.stop()
        if self._thread:
            self._thread.join(timeout=5)
