#!/usr/bin/env bash
# CI gate: tier-1 tests + chaos suite + live endpoint lint + autotune
# e2e + router e2e + fused kernel parity + DLRM e2e + shm ring e2e +
# staged fan-in e2e + QoS gauntlet smoke + closed-loop smoke +
# incident blackbox + bench gate + static analysis / lockdep gate.
#
#   tools/ci_check.sh            # everything (tier-1 already includes chaos)
#   tools/ci_check.sh --fast     # all stages except tier-1
#
# Fourteen stages:
#   1. tier-1: the full fast suite (ROADMAP.md contract; excludes `slow`).
#   2. chaos: the deterministic fault-injection suite alone (`-m chaos`) —
#      redundant with tier-1 when stage 1 runs, but the -m filter proves
#      the marker set stays collectible on its own (a broken marker would
#      silently drop these tests from any filtered CI job).
#   3. live scrape: boot a real HTTP server, lint /metrics in both the
#      classic and OpenMetrics expositions with tools/promlint.py (the
#      OpenMetrics pass also requires an exemplar on tpu_request_duration),
#      and smoke-scrape /v2/events, /v2/slo, /v2/timeseries (flight
#      recorder ring), /v2/memory (HBM census) and /v2/costs (tenant
#      cost ledger) — catching malformed renderings and broken ops
#      endpoints that unit tests of individual counters never exercise.
#      The census gauge family tpu_hbm_census_bytes and the tpu_cost_*
#      counter families must render in both dialects.
#   4. autotune e2e: boot the server with CLIENT_TPU_AUTOTUNE enabled and
#      a deliberately misfit bucket ladder, drive skewed batch-1 traffic,
#      and assert the tuner promotes a bucket (journaled, applied state in
#      /v2/profile) and tpu_autotune_* counters render promlint-clean in
#      both exposition dialects.
#   5. router e2e: two in-process replicas behind the standalone L7
#      router — drive traffic through the proxy (both replicas must
#      receive some), smoke /v2/load + /v2/fleet/profile +
#      /v2/fleet/events + /v2/fleet/costs (federated cost ledger),
#      round-trip one stitched trace (router spans +
#      the serving replica's phase spans under one trace id), induce
#      load-report skew and assert tpu_fleet_drift_score crosses the
#      monitor threshold, roll-drain one replica with live in-process
#      drain (survivor keeps serving), and lint tpu_router_* and the
#      fleet drift gauge in both exposition dialects.
#   6. fused kernel parity: the Pallas decode-kernel suite
#      (tests/test_ops.py) in interpret mode, then a fused-path engine
#      driven end to end so tpu_decode_wave_seconds renders and lints
#      clean in both exposition dialects.
#   7. dlrm e2e: serve the ragged-CSR DLRM model (host tables + hot-row
#      cache) under CLIENT_TPU_AUTOTUNE with a deliberately misfit lookup
#      ladder, drive small-nnz traffic, and assert the tuner promotes a
#      LOOKUP-axis bucket (applied in /v2/profile, buckets tagged
#      axis=lookups) and the tpu_emb_* cache metrics render
#      promlint-clean in both exposition dialects.
#   8. shm ring e2e: a REAL producer process creates a slot ring in
#      /dev/shm, registers it over HTTP, stages a span of requests, rings
#      ONE batched doorbell, and polls the slot state words for
#      completions — asserting the reaped outputs are byte-identical to
#      the binary-HTTP path for the same inputs, and that tpu_shm_ring_*
#      render promlint-clean in both exposition dialects.
#   9. staged fan-in e2e: EIGHT real producer processes (tools/replay.py
#      workers) share ONE staged-dataset segment and fan into the
#      engine-side multi-ring reaper via descriptor-only slots — zero
#      doorbells. Asserts every completion arrives error-free, the
#      summed per-tensor CRC32s are byte-identical to the binary-HTTP
#      path for the same rows, and tpu_shm_dataset_* / tpu_shm_reaper_*
#      render promlint-clean in both exposition dialects.
#  10. qos gauntlet smoke: one engine serving a protected interactive
#      class and a quota'd batch class under CLIENT_TPU_SLO, hit with
#      an in-process flash crowd on the batch model — the SLO fast-burn
#      must fire and the governor must throttle the batch class
#      (journal qos.throttle, /v2/qos shows the throttled ratio), and
#      the tpu_qos_* families must render promlint-clean in both
#      exposition dialects. The full routed gauntlet (restore edge,
#      per-class p99 SLOs, adversarial mix) runs in bench.py and is
#      gated by stage 13 when BENCH_HISTORY.json is present.
#  11. closed-loop smoke: the self-drive dispatch retune must fire on
#      probe-shaped sparse traffic (journal autotune.dispatch_tighten,
#      override applied) and restore on quiet, with the loop state
#      rendered by profile_report --loops.
#  12. incident blackbox: a live manual capture (POST /v2/debug/capture)
#      must write a bundle whose index lists identically over HTTP and
#      gRPC, the bundle's journal/timeseries/traces/fingerprint
#      sections must be intact, tools/blackbox_report.py must render
#      it, and the tpu_blackbox_* families must lint clean in both
#      exposition dialects.
#  13. bench gate: tools/bench_summary.py --check fails the build when the
#      newest BENCH_HISTORY.json run regressed any probe's p99 by >25%.
#  14. analysis gate: tpulint (python -m tools.analyze) against the
#      reviewed baseline, promlint --definitions over every metric
#      registration site, and the concurrency-heavy tier-1 subset
#      re-run under CLIENT_TPU_LOCKDEP=1 so the runtime lock-order and
#      blocking-under-lock checkers ride every lock the suite takes
#      (docs/ANALYSIS.md).
set -u -o pipefail

cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# CI runs must not depend on executables a previous run left on disk (and
# XLA:CPU logs two multi-KB loader lines per persistent-cache hit).
export JAX_ENABLE_COMPILATION_CACHE="${JAX_ENABLE_COMPILATION_CACHE:-false}"
FAST=0
[ "${1:-}" = "--fast" ] && FAST=1
rc=0

if [ "$FAST" -eq 0 ]; then
    echo "=== stage 1/14: tier-1 test suite ==="
    rm -f /tmp/_t1.log
    timeout -k 10 870 python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
        -p no:randomly 2>&1 | tee /tmp/_t1.log
    t1=${PIPESTATUS[0]}
    echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
        | tr -cd . | wc -c)"
    [ "$t1" -ne 0 ] && { echo "tier-1 FAILED (exit $t1)"; rc=1; }
else
    echo "=== stage 1/14: tier-1 skipped (--fast) ==="
fi

echo "=== stage 2/14: chaos (fault-injection) suite ==="
timeout -k 10 300 python -m pytest tests/ -q -m chaos \
    -p no:cacheprovider -p no:xdist -p no:randomly
[ $? -ne 0 ] && { echo "chaos suite FAILED"; rc=1; }

echo "=== stage 3/14: live scrape (promlint + ops endpoints) ==="
SCRAPE_DIR=$(mktemp -d)
# Pinned peaks: MFU/MBU need a peak spec, and the CI host is a CPU whose
# device kind resolves to "peaks unknown" — the override also exercises
# the CLIENT_TPU_ROOFLINE grammar on every CI run.
CLIENT_TPU_ROOFLINE='{"peak_flops": 1e12, "peak_bytes_per_s": 1e11}' \
python - "$SCRAPE_DIR" <<'EOF'
import json
import sys
from urllib.request import Request, urlopen

from client_tpu.models import build_repository
from client_tpu.engine import TpuEngine
from client_tpu.observability.tracing import TraceContext
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]
engine = TpuEngine(build_repository(["simple"]), warmup=False)
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
try:
    # One traced inference so per-model counters/histograms render
    # non-trivially and the duration histogram carries an exemplar.
    import numpy as np
    from client_tpu.engine.types import InferRequest

    engine.infer(InferRequest(
        model_name="simple",
        inputs={"INPUT0": np.zeros((1, 16), dtype=np.int32),
                "INPUT1": np.zeros((1, 16), dtype=np.int32)},
        trace=TraceContext.new(),
    ), timeout_s=120)
    # A second, tenant-tagged inference: the first is the cold call
    # (compile time excluded from charging on both meters), so this is
    # the one the cost ledger bills — /v2/costs must show the tenant.
    engine.infer(InferRequest(
        model_name="simple",
        inputs={"INPUT0": np.zeros((1, 16), dtype=np.int32),
                "INPUT1": np.zeros((1, 16), dtype=np.int32)},
        tenant="ci",
    ), timeout_s=120)
    base = f"http://{srv.url}"
    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    if not any("tpu_request_duration" in ln and " # {" in ln
               for ln in om.splitlines()):
        sys.exit("no exemplar on tpu_request_duration in OpenMetrics scrape")
    events = json.load(urlopen(f"{base}/v2/events", timeout=10))
    if "events" not in events or not any(
            e["category"] == "lifecycle" for e in events["events"]):
        sys.exit(f"/v2/events smoke failed: {str(events)[:200]}")
    slo = json.load(urlopen(f"{base}/v2/slo", timeout=10))
    if "enabled" not in slo or "windows" not in slo:
        sys.exit(f"/v2/slo smoke failed: {str(slo)[:200]}")
    prof = json.load(urlopen(f"{base}/v2/profile", timeout=10))
    if "models" not in prof or "duty_cycle" not in prof:
        sys.exit(f"/v2/profile smoke failed: {str(prof)[:200]}")
    # Roofline attribution: the snapshot header resolves the peaks and
    # every model entry joins its cost model with measured device time.
    roof = prof.get("roofline")
    if not roof or not isinstance(roof.get("peaks"), dict):
        sys.exit(f"/v2/profile roofline header missing: {str(roof)[:200]}")
    for mkey, m in prof["models"].items():
        mr = m.get("roofline")
        if not mr or mr.get("mfu") is None or mr.get("bound") == "unknown":
            sys.exit(f"/v2/profile roofline join failed for {mkey}: "
                     f"{str(mr)[:200]}")
    if "tpu_batch_fill_ratio" not in classic:
        sys.exit("tpu_batch_fill_ratio missing from /metrics scrape")
    engine.recorder.tick()  # deterministic sample even on a fast scrape
    ts = json.load(urlopen(f"{base}/v2/timeseries", timeout=10))
    if not ts.get("enabled") or not ts.get("samples"):
        sys.exit(f"/v2/timeseries smoke failed: {str(ts)[:200]}")
    mem = json.load(urlopen(f"{base}/v2/memory", timeout=10))
    if "owners" not in mem or "attributed_fraction" not in mem:
        sys.exit(f"/v2/memory smoke failed: {str(mem)[:200]}")
    if "tpu_hbm_census_bytes" not in classic:
        sys.exit("tpu_hbm_census_bytes missing from /metrics scrape")
    costs = json.load(urlopen(f"{base}/v2/costs", timeout=10))
    if "tenants" not in costs or "reconciliation" not in costs:
        sys.exit(f"/v2/costs smoke failed: {str(costs)[:200]}")
    if "ci" not in costs["tenants"]:
        sys.exit(f"/v2/costs missing the tagged tenant: "
                 f"{sorted(costs['tenants'])}")
    if "tpu_cost_device_seconds_total" not in classic:
        sys.exit("tpu_cost_device_seconds_total missing from /metrics")
    print(f"ops endpoints ok: {len(events['events'])} event(s), "
          f"slo enabled={slo['enabled']}, "
          f"profile models={len(prof['models'])}, "
          f"timeseries samples={len(ts['samples'])}, "
          f"census owners={len(mem['owners'])}, "
          f"cost tenants={sorted(costs['tenants'])}")
finally:
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "live scrape FAILED"; rc=1; }
python tools/promlint.py "$SCRAPE_DIR/metrics.txt" \
    || { echo "promlint (classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$SCRAPE_DIR/metrics.om.txt" \
    || { echo "promlint (openmetrics) FAILED"; rc=1; }
grep -q "^tpu_hbm_census_bytes" "$SCRAPE_DIR/metrics.txt" \
    || { echo "tpu_hbm_census_bytes missing from classic dialect"; rc=1; }
grep -q "^tpu_hbm_census_bytes" "$SCRAPE_DIR/metrics.om.txt" \
    || { echo "tpu_hbm_census_bytes missing from openmetrics dialect"; rc=1; }
grep -q "^tpu_cost_" "$SCRAPE_DIR/metrics.txt" \
    || { echo "tpu_cost_* missing from classic dialect"; rc=1; }
grep -q "^tpu_cost_" "$SCRAPE_DIR/metrics.om.txt" \
    || { echo "tpu_cost_* missing from openmetrics dialect"; rc=1; }
grep -q "^tpu_mfu{" "$SCRAPE_DIR/metrics.txt" \
    || { echo "tpu_mfu missing from classic dialect"; rc=1; }
grep -q "^tpu_mfu{" "$SCRAPE_DIR/metrics.om.txt" \
    || { echo "tpu_mfu missing from openmetrics dialect"; rc=1; }
grep -q "^tpu_mbu{" "$SCRAPE_DIR/metrics.txt" \
    || { echo "tpu_mbu missing from classic dialect"; rc=1; }
grep -q "^tpu_mbu{" "$SCRAPE_DIR/metrics.om.txt" \
    || { echo "tpu_mbu missing from openmetrics dialect"; rc=1; }
rm -rf "$SCRAPE_DIR"

echo "=== stage 4/14: autotune e2e (promotion + metrics) ==="
TUNE_DIR=$(mktemp -d)
CLIENT_TPU_AUTOTUNE='{"interval_s": 0.2, "cooldown_s": 0.5}' \
timeout -k 10 300 python - "$TUNE_DIR" <<'EOF'
import json
import sys
import time
from urllib.request import Request, urlopen

import numpy as np

from client_tpu.engine import TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.types import InferRequest
from client_tpu.models.simple import AddSubBackend
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]
# Misfit ladder on purpose: only the max bucket exists, so batch-1
# traffic runs at 1/32 fill until the tuner promotes a 1-row bucket.
backend = AddSubBackend(name="simple", max_batch_size=32)
backend.config.batch_buckets = [32]
repo = ModelRepository()
repo.register_backend(backend)
engine = TpuEngine(repo, warmup=True)
if engine.autotuner is None:
    sys.exit("CLIENT_TPU_AUTOTUNE set but engine built no autotuner")
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
try:
    base = f"http://{srv.url}"
    ins = {"INPUT0": np.zeros((1, 16), dtype=np.int32),
           "INPUT1": np.zeros((1, 16), dtype=np.int32)}
    for _ in range(16):  # skewed traffic: all batch-1
        engine.infer(InferRequest(model_name="simple", inputs=ins),
                     timeout_s=120)
    applied = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not applied:
        prof = json.load(urlopen(f"{base}/v2/profile", timeout=10))
        applied = [d for d in prof.get("autotune", {}).get("decisions", [])
                   if d["action"] == "add_bucket" and d["applied"]]
        if not applied:
            time.sleep(0.25)
    if not applied:
        sys.exit(f"no applied promotion within 30s: "
                 f"{json.dumps(prof.get('autotune'))[:400]}")
    states = [s.get("state") for m in prof["models"].values()
              for s in (m.get("suggestions") or [])]
    if "applied" not in states:
        sys.exit(f"/v2/profile has no suggestion in state=applied: {states}")
    events = json.load(urlopen(
        f"{base}/v2/events?category=autotune", timeout=10))
    if not any(e["name"] == "add_bucket" for e in events["events"]):
        sys.exit("journal has no autotune.add_bucket event")
    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    if "tpu_autotune_decisions_total" not in classic:
        sys.exit("tpu_autotune_decisions_total missing from /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    print(f"autotune e2e ok: promotion {applied[0]['bucket']} applied, "
          f"{len(events['events'])} journal event(s)")
finally:
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "autotune e2e FAILED"; rc=1; }
python tools/promlint.py "$TUNE_DIR/metrics.txt" \
    || { echo "promlint (autotune classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$TUNE_DIR/metrics.om.txt" \
    || { echo "promlint (autotune openmetrics) FAILED"; rc=1; }
rm -rf "$TUNE_DIR"

echo "=== stage 5/14: router e2e (balance + roll-drain + fleet + metrics) ==="
ROUTER_DIR=$(mktemp -d)
timeout -k 10 300 python - "$ROUTER_DIR" <<'EOF'
import json
import sys
import threading
from urllib.request import Request, urlopen

import numpy as np

import client_tpu.http as httpclient
from client_tpu.admission.drain import drain as engine_drain
from client_tpu.engine import TpuEngine
from client_tpu.models import build_repository
from client_tpu.observability import FleetMonitorConfig
from client_tpu.router import Replica, Router, RouterHttpServer, rolling_drain
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]
engines = [TpuEngine(build_repository(["simple"]), warmup=False)
           for _ in range(2)]
replicas = [HttpInferenceServer(e, host="127.0.0.1", port=0).start()
            for e in engines]
router = Router([Replica(f"http://{r.url}") for r in replicas], seed=7)
srv = RouterHttpServer(router, port=0, monitor_config=FleetMonitorConfig(
    interval_s=3600.0, threshold=0.5)).start()
try:
    base = f"http://{srv.url}"
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
    i0.set_data_from_numpy(a)
    i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
    i1.set_data_from_numpy(b)
    client = httpclient.InferenceServerClient(base)
    for _ in range(40):
        result = client.infer("simple", [i0, i1])
        if not (result.as_numpy("OUTPUT0") == a + b).all():
            sys.exit("router proxy returned wrong OUTPUT0")

    # /v2/load smoke: every replica reporting, all READY.
    load = json.load(urlopen(f"{base}/v2/load", timeout=10))
    if set(load["replicas"]) != {r.id for r in router.replicas}:
        sys.exit(f"/v2/load replica set mismatch: {str(load)[:300]}")
    if any(rep["load"].get("state") != "READY"
           for rep in load["replicas"].values()):
        sys.exit(f"/v2/load has non-READY replica: {str(load)[:300]}")

    # Uniform load must reach both replicas (P2C, no affinity key).
    ok_children = router.metrics.requests._children
    counts = {rid: (ch.v if (ch := ok_children.get((rid, "ok"))) else 0.0)
              for rid in load["replicas"]}
    if any(v <= 0 for v in counts.values()):
        sys.exit(f"one replica got no traffic: {counts}")

    # Fleet federation smoke against the 2 live replicas: per-replica
    # profile rows, cursor-merged events, and a stitched trace tree.
    fleet_prof = json.load(urlopen(f"{base}/v2/fleet/profile", timeout=10))
    if set(fleet_prof["replicas"]) != {r.id for r in router.replicas}:
        sys.exit(f"/v2/fleet/profile replica rows wrong: "
                 f"{str(fleet_prof)[:300]}")
    if fleet_prof["errors"]:
        sys.exit(f"/v2/fleet/profile fetch errors: {fleet_prof['errors']}")
    fleet_evts = json.load(urlopen(f"{base}/v2/fleet/events?limit=50",
                                   timeout=10))
    if set(fleet_evts["cursors"]) != {r.id for r in router.replicas}:
        sys.exit(f"/v2/fleet/events cursors wrong: {str(fleet_evts)[:300]}")
    if not fleet_evts["events"]:
        sys.exit("/v2/fleet/events merged to an empty journal")
    fleet_costs = json.load(urlopen(f"{base}/v2/fleet/costs", timeout=10))
    if set(fleet_costs["replicas"]) != {r.id for r in router.replicas}:
        sys.exit(f"/v2/fleet/costs replica rows wrong: "
                 f"{str(fleet_costs)[:300]}")
    if "default" not in fleet_costs.get("tenants", {}):
        sys.exit(f"/v2/fleet/costs has no default-tenant charges: "
                 f"{str(fleet_costs)[:300]}")

    # Stitched trace round-trip: one more infer (raw urlopen, no client
    # traceparent), then resolve the echoed trace id on the router into
    # router spans + replica phase spans.
    infer_body = json.dumps({"inputs": [
        {"name": "INPUT0", "shape": [1, 16], "datatype": "INT32",
         "data": a.flatten().tolist()},
        {"name": "INPUT1", "shape": [1, 16], "datatype": "INT32",
         "data": b.flatten().tolist()}]}).encode()
    resp = urlopen(Request(f"{base}/v2/models/simple/infer",
                           data=infer_body, method="POST"), timeout=10)
    resp.read()
    trace_id = resp.headers.get("X-Tpu-Trace-Id")
    if not trace_id:
        sys.exit("router response missing X-Tpu-Trace-Id")
    stitched = json.load(urlopen(
        f"{base}/v2/trace/requests?trace_id={trace_id}", timeout=10))
    names = {e["name"] for e in stitched["traceEvents"]}
    for need in ("router:request", "router:select", "router:proxy",
                 "simple:request"):
        if need not in names:
            sys.exit(f"stitched trace missing span {need}: {sorted(names)}")

    # Induce skew (divergent queue-wait reports) and tick the drift
    # monitor: the flagged replica must cross the gauge threshold.
    from client_tpu.protocol.loadreport import LoadReport
    router.replicas[0].observe_report(LoadReport(wait_s=0.01))
    router.replicas[1].observe_report(LoadReport(wait_s=5.0))
    report = srv.monitor.tick()
    if router.replicas[1].id not in report["flagged"]:
        sys.exit(f"induced skew not flagged: {str(report)[:300]}")
    from client_tpu.observability import scrape
    drift_samples = [s for s in scrape.parse_samples(router.metrics.render())
                     if s[0] == "tpu_fleet_drift_score"]
    if not any(v > 0.5 for _, _, v in drift_samples):
        sys.exit(f"tpu_fleet_drift_score never crossed 0.5: {drift_samples}")
    print(f"fleet ok: {len(fleet_prof['replicas'])} profile rows, "
          f"{len(fleet_evts['events'])} merged events, stitched trace "
          f"{trace_id[:8]}…, drift flagged {sorted(report['flagged'])}")

    # Roll-drain replica 0 via the real in-process drain sequence (the
    # same code SIGTERM runs), then prove the survivor keeps serving.
    victim_id = router.replicas[0].id

    def trigger():
        threading.Thread(
            target=engine_drain, args=(engines[0],),
            kwargs={"http_servers": [replicas[0]], "deadline_s": 10.0},
            daemon=True).start()

    reports = rolling_drain(router, [victim_id],
                            triggers={victim_id: trigger}, deadline_s=30.0)
    if reports[0]["outcome"] not in ("clean", "gone"):
        sys.exit(f"rolling drain not clean: {reports}")
    for _ in range(10):
        result = client.infer("simple", [i0, i1])
        if not (result.as_numpy("OUTPUT0") == a + b).all():
            sys.exit("survivor returned wrong OUTPUT0 after drain")
    status = json.load(urlopen(f"{base}/v2/router/status", timeout=10))
    if victim_id in status["eligible"]:
        sys.exit("drained replica still eligible")
    client.close()

    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    if "tpu_router_requests_total" not in classic:
        sys.exit("tpu_router_requests_total missing from router /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    print(f"router e2e ok: spread {counts}, drain "
          f"{reports[0]['outcome']}, survivor serving")
finally:
    srv.stop()
    for r in replicas:
        try:
            r.stop()
        except Exception:  # noqa: BLE001 — drained frontend already closed
            pass
    for e in engines:
        try:
            e.shutdown()
        except Exception:  # noqa: BLE001 — drained engine already down
            pass
EOF
[ $? -ne 0 ] && { echo "router e2e FAILED"; rc=1; }
python tools/promlint.py "$ROUTER_DIR/metrics.txt" \
    || { echo "promlint (router classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$ROUTER_DIR/metrics.om.txt" \
    || { echo "promlint (router openmetrics) FAILED"; rc=1; }
grep -q "^tpu_fleet_drift_score{" "$ROUTER_DIR/metrics.txt" \
    || { echo "tpu_fleet_drift_score missing from classic dialect"; rc=1; }
grep -q "^tpu_fleet_drift_score{" "$ROUTER_DIR/metrics.om.txt" \
    || { echo "tpu_fleet_drift_score missing from openmetrics dialect"; rc=1; }
rm -rf "$ROUTER_DIR"

echo "=== stage 6/14: fused decode kernel parity (interpret) + wave metrics ==="
# The Pallas decode kernel and the sharded KV arena run in interpret mode
# on CPU (docs/KERNELS.md): this stage proves (a) fused == reference on
# the fast parity subset, (b) an engine on the fused path emits
# tpu_decode_wave_seconds, and (c) that histogram renders promlint-clean
# in both exposition dialects.
timeout -k 10 300 python -m pytest tests/test_ops.py -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
[ $? -ne 0 ] && { echo "kernel parity suite FAILED"; rc=1; }
KERNEL_DIR=$(mktemp -d)
timeout -k 10 300 python - "$KERNEL_DIR" <<'EOF'
import sys
import threading
from urllib.request import Request, urlopen

import numpy as np

from client_tpu.engine import TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.types import InferRequest
from client_tpu.models.generate import TinyGptBackend
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]
repo = ModelRepository()
repo.register_backend(TinyGptBackend(
    name="tiny_gpt", n_layers=2, d_model=64, n_heads=2, d_ff=128,
    vocab=128, max_seq_len=32, max_streams=4, attn_impl="fused"))
engine = TpuEngine(repo)
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
try:
    done = threading.Event()
    errs = []

    def cb(resp):
        if resp.error is not None:
            errs.append(resp.error)
            done.set()
        elif resp.final:
            done.set()

    engine.async_infer(InferRequest(
        model_name="tiny_gpt",
        inputs={"INPUT_IDS": np.asarray([1, 2, 3], np.int32)},
        parameters={"max_tokens": 6}), cb)
    if not done.wait(120):
        sys.exit("fused generation stalled")
    if errs:
        sys.exit(f"fused generation failed: {errs[0]}")
    base = f"http://{srv.url}"
    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    if "tpu_decode_wave_seconds" not in classic:
        sys.exit("tpu_decode_wave_seconds missing from /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    print("fused engine e2e ok: tpu_decode_wave_seconds rendered")
finally:
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "fused wave metrics e2e FAILED"; rc=1; }
python tools/promlint.py "$KERNEL_DIR/metrics.txt" \
    || { echo "promlint (kernel classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$KERNEL_DIR/metrics.om.txt" \
    || { echo "promlint (kernel openmetrics) FAILED"; rc=1; }
rm -rf "$KERNEL_DIR"

echo "=== stage 7/14: dlrm e2e (lookup-bucket promotion + emb metrics) ==="
DLRM_DIR=$(mktemp -d)
CLIENT_TPU_AUTOTUNE='{"interval_s": 0.2, "cooldown_s": 0.5}' \
timeout -k 10 300 python - "$DLRM_DIR" <<'EOF'
import json
import sys
import time
from urllib.request import Request, urlopen

import numpy as np

from client_tpu.engine import TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.types import InferRequest
from client_tpu.models.dlrm import DlrmBackend
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]
# Misfit LOOKUP ladder on purpose: only the 128-lookup bucket exists, so
# ~8-nnz CSR traffic runs at 8/128 fill until the tuner promotes a small
# lookup bucket. Host tables + hot-row cache so tpu_emb_* render.
backend = DlrmBackend(name="dlrm", host_tables=True,
                      cache_budget_bytes=4096, lookup_buckets=[128])
repo = ModelRepository()
repo.register_backend(backend)
engine = TpuEngine(repo, warmup=True)
if engine.autotuner is None:
    sys.exit("CLIENT_TPU_AUTOTUNE set but engine built no autotuner")
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
try:
    base = f"http://{srv.url}"
    rng = np.random.default_rng(11)
    for _ in range(16):  # skewed traffic: ~8 lookups per request
        counts = rng.integers(1, 3, size=4)  # 1 row x 4 tables
        idx = rng.integers(0, 64, size=int(counts.sum())).astype(np.int32)
        off = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
        engine.infer(InferRequest(model_name="dlrm", inputs={
            "DENSE": rng.standard_normal((1, 8)).astype(np.float32),
            "INDICES": idx, "OFFSETS": off}), timeout_s=120)
    applied = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not applied:
        prof = json.load(urlopen(f"{base}/v2/profile", timeout=10))
        applied = [d for d in prof.get("autotune", {}).get("decisions", [])
                   if d["action"] == "add_bucket" and d["applied"]]
        if not applied:
            time.sleep(0.25)
    if not applied:
        sys.exit(f"no applied lookup-bucket promotion within 30s: "
                 f"{json.dumps(prof.get('autotune'))[:400]}")
    axes = {b.get("axis") for m in prof["models"].values()
            for b in (m.get("buckets") or [])}
    if axes != {"lookups"}:
        sys.exit(f"profile buckets not tagged axis=lookups: {axes}")
    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    for fam in ("tpu_emb_lookups_total", "tpu_emb_cache_hits_total",
                "tpu_emb_cache_size_bytes"):
        if fam not in classic:
            sys.exit(f"{fam} missing from /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    print(f"dlrm e2e ok: lookup bucket {applied[0]['bucket']} applied, "
          f"tpu_emb_* rendered")
finally:
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "dlrm e2e FAILED"; rc=1; }
python tools/promlint.py "$DLRM_DIR/metrics.txt" \
    || { echo "promlint (dlrm classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$DLRM_DIR/metrics.om.txt" \
    || { echo "promlint (dlrm openmetrics) FAILED"; rc=1; }
rm -rf "$DLRM_DIR"

echo "=== stage 8/14: shm ring e2e (producer process + doorbell + metrics) ==="
RING_DIR=$(mktemp -d)
timeout -k 10 300 python - "$RING_DIR" <<'EOF'
import json
import os
import subprocess
import sys
from urllib.request import Request, urlopen

import numpy as np

import client_tpu.http as httpclient
from client_tpu.engine import TpuEngine
from client_tpu.models import build_repository
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]

# The producer runs as a SEPARATE process: the whole point of the ring is
# the cross-process /dev/shm contract, so CI must not fake it in-process.
PRODUCER = r'''
import sys

import numpy as np

import client_tpu.http as httpclient
from client_tpu.utils.shm_ring import RingProducer

url, out_npz, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
client = httpclient.InferenceServerClient(url)
outs = {}
with RingProducer(client, "ci_ring", "/ci_ring_e2e", slot_count=8,
                  slot_bytes=4096) as prod:
    b = np.ones((1, 16), dtype=np.int32)
    for i in range(n):
        a = np.arange(16, dtype=np.int32).reshape(1, 16) + i
        assert prod.fill({"INPUT0": a, "INPUT1": b}) is not None
    res = prod.doorbell("simple")
    assert res["admitted"] == n, res
    for _ in range(n):
        slot, o, err = prod.reap(timeout_s=120)
        assert err is None, err
        outs[f"o0_{slot}"] = o["OUTPUT0"]
        outs[f"o1_{slot}"] = o["OUTPUT1"]
client.close()
np.savez(out_npz, **outs)
'''

engine = TpuEngine(build_repository(["simple"]), warmup=False)
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
try:
    n = 6
    # Reference outputs via the binary-HTTP data plane, same inputs.
    client = httpclient.InferenceServerClient(srv.url)
    b = np.ones((1, 16), dtype=np.int32)
    i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
    i1.set_data_from_numpy(b)
    ref = []
    for i in range(n):
        a = np.arange(16, dtype=np.int32).reshape(1, 16) + i
        i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
        i0.set_data_from_numpy(a)
        r = client.infer("simple", [i0, i1])
        ref.append((r.as_numpy("OUTPUT0"), r.as_numpy("OUTPUT1")))
    client.close()

    prod_py = os.path.join(out_dir, "producer.py")
    with open(prod_py, "w") as f:
        f.write(PRODUCER)
    out_npz = os.path.join(out_dir, "ring_outputs.npz")
    proc = subprocess.run(
        [sys.executable, prod_py, srv.url, out_npz, str(n)],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, PYTHONPATH=os.getcwd()))
    if proc.returncode != 0:
        sys.exit("ring producer process failed:\n"
                 f"{proc.stdout}{proc.stderr}")

    got = np.load(out_npz)
    for i in range(n):  # fresh ring: request i landed in slot i
        r0, r1 = ref[i]
        if got[f"o0_{i}"].tobytes() != r0.tobytes() or \
                got[f"o1_{i}"].tobytes() != r1.tobytes():
            sys.exit(f"slot {i}: ring outputs not byte-identical to HTTP")

    events = json.load(urlopen(
        f"http://{srv.url}/v2/events?category=shm_ring", timeout=10))
    names = {e["name"] for e in events["events"]}
    if not {"attach", "detach"} <= names:
        sys.exit(f"journal missing shm_ring attach/detach: {names}")
    classic = urlopen(f"http://{srv.url}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"http://{srv.url}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    for fam in ("tpu_shm_ring_doorbells_total", "tpu_shm_ring_slots_total",
                "tpu_shm_ring_doorbell_span"):
        if fam not in classic:
            sys.exit(f"{fam} missing from /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    print(f"shm ring e2e ok: {n} slots byte-identical to HTTP, "
          f"one doorbell, tpu_shm_ring_* rendered")
finally:
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "shm ring e2e FAILED"; rc=1; }
python tools/promlint.py "$RING_DIR/metrics.txt" \
    || { echo "promlint (shm ring classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$RING_DIR/metrics.om.txt" \
    || { echo "promlint (shm ring openmetrics) FAILED"; rc=1; }
rm -rf "$RING_DIR"

echo "=== stage 9/14: staged fan-in e2e (8 producer processes + reaper metrics) ==="
FANIN_DIR=$(mktemp -d)
timeout -k 10 300 python - "$FANIN_DIR" <<'EOF'
import json
import sys
import zlib
from urllib.request import Request, urlopen

import numpy as np

import client_tpu.http as httpclient
from client_tpu.engine import TpuEngine
from client_tpu.models import build_repository
from client_tpu.server import HttpInferenceServer
from client_tpu.utils.shm_ring.staged import build_staged_dataset
from tools.replay import collect_workers, spawn_workers

out_dir = sys.argv[1]
ROWS, PRODUCERS, PER = 16, 8, 6

engine = TpuEngine(build_repository(["simple"]), warmup=False)
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
ds = None
try:
    base = np.arange(16, dtype=np.int32).reshape(1, 16)
    ds = build_staged_dataset("/ci_fanin_dset", {
        "INPUT0": np.concatenate([base + r for r in range(ROWS)]),
        "INPUT1": np.full((ROWS, 16), 3, dtype=np.int32),
    })
    client = httpclient.InferenceServerClient(srv.url)
    client.register_staged_dataset("ci_fanin", "/ci_fanin_dset")

    # Oracle: binary-HTTP outputs for the rows each worker replays
    # (worker i starts at row i, wraps mod ROWS), CRC-folded exactly
    # like tools/replay._reap_one does on the ring side.
    expect = 0
    row_crc = {}
    for row in range(ROWS):
        i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
        i0.set_data_from_numpy((base + row).astype(np.int32))
        i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
        i1.set_data_from_numpy(np.full((1, 16), 3, dtype=np.int32))
        r = client.infer("simple", [i0, i1])
        row_crc[row] = sum(
            zlib.crc32(r.as_numpy(n).tobytes())
            for n in ("OUTPUT0", "OUTPUT1"))
    for i in range(PRODUCERS):
        for k in range(PER):
            expect += row_crc[(i + k) % ROWS]

    procs = spawn_workers(srv.url, "simple", "/ci_fanin_dset", "ci_fanin",
                          PRODUCERS, duration=0.0, count=PER,
                          slot_count=8, slot_bytes=4096,
                          key_prefix="/ci_fanin_ring")
    stats = collect_workers(procs, timeout_s=240.0)
    failed = [s for s in stats if "error" in s]
    if failed:
        sys.exit(f"fan-in producer processes failed: {failed}")
    done = sum(s["completions"] for s in stats)
    errs = sum(s["errors"] for s in stats)
    if done != PRODUCERS * PER or errs:
        sys.exit(f"fan-in completions {done}/{PRODUCERS * PER}, "
                 f"errors {errs}: {stats}")
    got = sum(s["crc"] for s in stats)
    if got != expect:
        sys.exit(f"fan-in outputs not byte-identical to HTTP: "
                 f"crc {got} != {expect}")

    events = json.load(urlopen(
        f"http://{srv.url}/v2/events?category=shm_ring", timeout=10))
    names = {e["name"] for e in events["events"]}
    if "attach" not in names:
        sys.exit(f"journal missing shm_ring attach: {names}")
    # Scrape while the dataset is still registered so the byte gauge
    # has a live child; reaper counters survive ring detach.
    classic = urlopen(f"http://{srv.url}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"http://{srv.url}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    for fam in ("tpu_shm_dataset_bytes", "tpu_shm_dataset_refs_total",
                "tpu_shm_reaper_sweeps_total", "tpu_shm_reaper_slots_total",
                "tpu_shm_reaper_rings"):
        if fam not in classic:
            sys.exit(f"{fam} missing from /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    client.unregister_staged_dataset("ci_fanin")
    client.close()
    print(f"staged fan-in e2e ok: {PRODUCERS} producer processes, "
          f"{done} completions byte-identical to HTTP, "
          f"tpu_shm_dataset_*/tpu_shm_reaper_* rendered")
finally:
    if ds is not None:
        ds.close(unlink=True)
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "staged fan-in e2e FAILED"; rc=1; }
python tools/promlint.py "$FANIN_DIR/metrics.txt" \
    || { echo "promlint (fan-in classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$FANIN_DIR/metrics.om.txt" \
    || { echo "promlint (fan-in openmetrics) FAILED"; rc=1; }
rm -rf "$FANIN_DIR"

echo "=== stage 10/14: qos gauntlet smoke (flash crowd -> throttle + metrics) ==="
QOS_DIR=$(mktemp -d)
CLIENT_TPU_SLO='{"availability": 0.999, "latency_threshold_us": 40000.0,
    "latency_target": 0.9, "fast_burn_threshold": 14.4,
    "models": {"batch_net": {"latency_target": 0.5,
                             "fast_burn_threshold": 1.6}}}' \
timeout -k 10 300 python - "$QOS_DIR" <<'EOF'
import json
import sys
import threading
import time
from urllib.request import Request, urlopen

import numpy as np

from client_tpu.admission import AdmissionError
from client_tpu.admission.qos import QosConfig, QosController
from client_tpu.engine import TpuEngine
from client_tpu.engine.config import (
    DynamicBatchingConfig,
    ModelConfig,
    TensorConfig,
)
from client_tpu.engine.model import ModelBackend
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.types import InferRequest
from client_tpu.observability.events import journal
from client_tpu.server import HttpInferenceServer

out_dir = sys.argv[1]
DIM, SERVICE_S, MB = 16, 0.008, 4

# One engine, two models on one shared 'device' lock: the protected
# interactive class and the quota'd batch class the flash crowd slams.
# Same policy shape as the full bench gauntlet, minus the router fleet.
device = threading.Lock()


class SleepIdentity(ModelBackend):
    jittable = False  # time.sleep must run per call, not per trace

    def __init__(self, name):
        self.config = ModelConfig(
            name=name, platform="jax", max_batch_size=MB,
            input=[TensorConfig("INPUT", "FP32", [DIM])],
            output=[TensorConfig("OUTPUT", "FP32", [DIM])],
            dynamic_batching=DynamicBatchingConfig(
                preferred_batch_size=[MB],
                max_queue_delay_microseconds=200),
            instance_count=1)

    def make_apply(self):
        def apply(inputs):
            with device:
                time.sleep(SERVICE_S)
            return {"OUTPUT": np.asarray(inputs["INPUT"])}
        return apply


repo = ModelRepository()
repo.register_backend(SleepIdentity("live_net"))
repo.register_backend(SleepIdentity("batch_net"))
qos = QosController(QosConfig.from_dict({
    "classes": {
        "interactive": {"weight": 8, "preempt": True, "protect": True},
        "batch": {"weight": 2, "priority_level": 4,
                  "tokens_per_s": 600.0, "burst": 60.0,
                  "max_queue_depth": 64},
    },
    "tenants": {"live": "interactive", "flood": "batch"},
    "default_class": "interactive",
    "restore_hold_s": 1.0,
    "governor_interval_s": 0.25,
}))
engine = TpuEngine(repo, warmup=True, qos=qos)
if not engine.slo.enabled:
    sys.exit("CLIENT_TPU_SLO set but engine built no SLO tracker")
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
jrnl = journal()
cursor = jrnl.export(limit=0)["next_seq"]
try:
    base = f"http://{srv.url}"
    inp = np.ones((1, DIM), np.float32)
    stop = threading.Event()
    flood = {"ok": 0, "sheds": 0}

    def flood_loop():
        # Closed-loop flash crowd on the batch model: with a 40 ms
        # queue-inclusive SLO threshold and an 8 ms serial device,
        # 24 outstanding requests put every completion over it.
        while not stop.is_set():
            done = threading.Event()
            try:
                engine.async_infer(InferRequest(
                    model_name="batch_net", tenant="flood",
                    inputs={"INPUT": inp}), lambda resp: done.set())
            except AdmissionError as exc:
                flood["sheds"] += 1
                stop.wait(min(exc.retry_after_s, 0.25))
                continue
            done.wait(60)
            flood["ok"] += 1

    threads = [threading.Thread(target=flood_loop, daemon=True)
               for _ in range(24)]
    for t in threads:
        t.start()
    throttled = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and throttled is None:
        for e in jrnl.snapshot(category="qos"):
            if e.seq >= cursor and e.name == "throttle":
                throttled = e.detail
                break
        time.sleep(0.2)
    if throttled is None:
        sys.exit(f"flash crowd never tripped qos.throttle in 60s "
                 f"(flood ok={flood['ok']} sheds={flood['sheds']}, "
                 f"slo={json.dumps(engine.slo.snapshot())[:300]})")

    # The governed class must be visibly throttled on the ops surface.
    snap = json.load(urlopen(f"{base}/v2/qos", timeout=10))
    ratio = snap["classes"]["batch"]["throttle_ratio"]
    if not (snap["enabled"] and ratio < 1.0):
        sys.exit(f"/v2/qos does not show batch throttled: {str(snap)[:300]}")
    if "batch" not in snap["governor"]["throttled"]:
        sys.exit(f"/v2/qos governor.throttled missing batch: "
                 f"{str(snap)[:300]}")

    # Interactive traffic still flows mid-crowd (protected class).
    for _ in range(3):
        engine.infer(InferRequest(model_name="live_net", tenant="live",
                                  inputs={"INPUT": inp}), timeout_s=60)

    stop.set()
    for t in threads:
        t.join(timeout=30)
    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    for fam in ("tpu_qos_sheds_total", "tpu_qos_inflight",
                "tpu_qos_throttle_ratio"):
        if fam not in classic:
            sys.exit(f"{fam} missing from /metrics")
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    print(f"qos gauntlet smoke ok: throttle fired ({throttled}), "
          f"batch ratio {ratio}, flood ok={flood['ok']} "
          f"sheds={flood['sheds']}, tpu_qos_* rendered")
finally:
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "qos gauntlet smoke FAILED"; rc=1; }
python tools/promlint.py "$QOS_DIR/metrics.txt" \
    || { echo "promlint (qos classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$QOS_DIR/metrics.om.txt" \
    || { echo "promlint (qos openmetrics) FAILED"; rc=1; }
grep -q "^tpu_qos_" "$QOS_DIR/metrics.txt" \
    || { echo "tpu_qos_* missing from classic dialect"; rc=1; }
grep -q "^tpu_qos_" "$QOS_DIR/metrics.om.txt" \
    || { echo "tpu_qos_* missing from openmetrics dialect"; rc=1; }
rm -rf "$QOS_DIR"

echo "=== stage 11/14: closed-loop smoke (self-drive dispatch retune fires + clears) ==="
SD_DIR=$(mktemp -d)
CLIENT_TPU_SELFDRIVE='{"interval_s": 0.2, "min_calls": 4, "fill_low": 0.8,
    "cooldown_s": 0.5, "restore_hold_s": 0.5, "wait_high_s": 5.0}' \
CLIENT_TPU_PROFILE_WINDOW_S=2 \
timeout -k 10 180 python - "$SD_DIR" <<'EOF'
import json
import sys
import time

import numpy as np

from client_tpu.engine import TpuEngine
from client_tpu.engine.config import (
    DynamicBatchingConfig,
    ModelConfig,
    TensorConfig,
)
from client_tpu.engine.model import ModelBackend
from client_tpu.engine.repository import ModelRepository
from client_tpu.engine.types import InferRequest
from client_tpu.observability.events import journal

out_dir = sys.argv[1]
DIM = 16


class Identity(ModelBackend):
    def __init__(self):
        self.config = ModelConfig(
            name="sparse_net", platform="jax", max_batch_size=8,
            input=[TensorConfig("INPUT", "FP32", [DIM])],
            output=[TensorConfig("OUTPUT", "FP32", [DIM])],
            dynamic_batching=DynamicBatchingConfig(
                preferred_batch_size=[8],
                max_queue_delay_microseconds=5000),
            instance_count=1)

    def make_apply(self):
        return lambda inputs: {"OUTPUT": inputs["INPUT"]}


repo = ModelRepository()
repo.register_backend(Identity())
engine = TpuEngine(repo, warmup=True)
if engine.selfdrive is None:
    sys.exit("CLIENT_TPU_SELFDRIVE set but engine built no governor")
jrnl = journal()
cursor = jrnl.export(limit=0)["next_seq"]
try:
    inp = np.ones((1, DIM), np.float32)

    def loop_events(name):
        return [e for e in jrnl.snapshot(category="autotune")
                if e.seq > cursor and e.name == name]

    # Bursts of 3 single-row requests: the gather waits out the 5 ms
    # deadline hoping for the preferred 8, then pads a 3-row batch into
    # the 4-bucket (fill 0.75 < fill_low) — the probe-shaped waste the
    # dispatch loop exists to fix.
    import threading
    tightened = False
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not tightened:
        done = [threading.Event() for _ in range(3)]
        for ev in done:
            engine.async_infer(
                InferRequest(model_name="sparse_net",
                             inputs={"INPUT": inp}),
                lambda resp, ev=ev: ev.set())
        for ev in done:
            ev.wait(30)
        tightened = bool(loop_events("dispatch_tighten"))
    if not tightened:
        sys.exit("sparse load never tripped autotune.dispatch_tighten "
                 f"in 60s ({json.dumps(engine.profile_snapshot().get('selfdrive'))[:400]})")
    sched = engine.scheduler_for("sparse_net")
    ovr = sched.dispatch_overrides()
    if not ovr or ovr.get("max_queue_delay_us", 5000) >= 5000:
        sys.exit(f"tighten journaled but no dispatch override: {ovr}")

    # Quiet: the profiler window (2s) empties, the loop restores the
    # override after restore_hold_s and journals the clear edge.
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline \
            and not loop_events("dispatch_restore"):
        time.sleep(0.2)
    if not loop_events("dispatch_restore"):
        sys.exit("dispatch override never restored on a quiet window")
    if sched.dispatch_overrides():
        sys.exit(f"restore journaled but override still set: "
                 f"{sched.dispatch_overrides()}")

    snap = engine.profile_snapshot()
    sd = snap.get("selfdrive")
    if not sd or sd["dispatch"]["action_count"] < 2:
        sys.exit(f"/v2/profile selfdrive section incomplete: "
                 f"{json.dumps(sd)[:400]}")
    with open(f"{out_dir}/profile.json", "w") as f:
        json.dump(snap, f)
    print(f"closed-loop smoke ok: tighten {ovr} then restored, "
          f"{sd['dispatch']['action_count']} actuation(s)")
finally:
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "closed-loop smoke FAILED"; rc=1; }
python tools/profile_report.py --loops "$SD_DIR/profile.json" \
    > "$SD_DIR/loops.txt" \
    && grep -q "dispatch loop:" "$SD_DIR/loops.txt" \
    || { echo "profile_report --loops FAILED"; rc=1; }
rm -rf "$SD_DIR"

echo "=== stage 12/14: incident blackbox (capture + both transports + report) ==="
BB_DIR=$(mktemp -d)
# @file spec so the CI run also exercises that arm of the env grammar.
printf '{"dir": "%s/bundles"}\n' "$BB_DIR" > "$BB_DIR/bb.json"
CLIENT_TPU_BLACKBOX="@$BB_DIR/bb.json" \
timeout -k 10 180 python - "$BB_DIR" <<'EOF'
import json
import sys
from urllib.request import Request, urlopen

import numpy as np

import client_tpu.grpc as grpcclient
from client_tpu.engine import TpuEngine
from client_tpu.engine.types import InferRequest
from client_tpu.models import build_repository
from client_tpu.observability.tracing import TraceContext
from client_tpu.server import GrpcInferenceServer, HttpInferenceServer

out_dir = sys.argv[1]
engine = TpuEngine(build_repository(["simple"]), warmup=False)
srv = HttpInferenceServer(engine, host="127.0.0.1", port=0).start()
gsrv = GrpcInferenceServer(engine, host="127.0.0.1", port=0).start()
gclient = None
try:
    if engine.blackbox is None:
        sys.exit("CLIENT_TPU_BLACKBOX set but engine built no recorder")
    # One traced inference so the bundle's worst-request section is
    # non-trivial.
    engine.infer(InferRequest(
        model_name="simple",
        inputs={"INPUT0": np.zeros((1, 16), dtype=np.int32),
                "INPUT1": np.zeros((1, 16), dtype=np.int32)},
        trace=TraceContext.new(),
    ), timeout_s=120)
    engine.recorder.tick()  # at least one flight-recorder sample
    base = f"http://{srv.url}"
    cap = json.load(urlopen(Request(
        f"{base}/v2/debug/capture",
        data=json.dumps({"note": "ci manual capture"}).encode(),
        headers={"Content-Type": "application/json"}), timeout=30))
    if cap.get("trigger") != "manual" or not cap.get("id"):
        sys.exit(f"manual capture failed: {str(cap)[:300]}")
    index = json.load(urlopen(f"{base}/v2/debug/bundles", timeout=10))
    ids = [b["id"] for b in index.get("bundles", [])]
    if ids != [cap["id"]]:
        sys.exit(f"HTTP index mismatch: {ids} vs {cap['id']}")
    bundle = json.load(urlopen(
        f"{base}/v2/debug/bundles/{cap['id']}", timeout=10))
    secs = bundle.get("sections", {})
    for want in ("journal", "timeseries", "traces", "fingerprint"):
        if not isinstance(secs.get(want), dict) \
                or "error" in secs[want]:
            sys.exit(f"bundle section {want} bad: "
                     f"{str(secs.get(want))[:200]}")
    if not secs["journal"].get("events"):
        sys.exit("bundle journal section is empty")
    with open(f"{out_dir}/bundle.json", "w") as f:
        json.dump(bundle, f)
    # Transport parity: the gRPC face must list the same bundle and a
    # second manual capture must dedupe nothing (manual never cools).
    gclient = grpcclient.InferenceServerClient(gsrv.url)
    gids = [b["id"] for b in gclient.get_bundles().get("bundles", [])]
    if gids != ids:
        sys.exit(f"gRPC index mismatch: {gids} vs {ids}")
    gcap = gclient.capture_bundle(note="ci grpc capture")
    if not gcap.get("id") or gcap["id"] == cap["id"]:
        sys.exit(f"gRPC capture failed: {str(gcap)[:300]}")
    classic = urlopen(f"{base}/metrics", timeout=10).read().decode()
    om = urlopen(Request(f"{base}/metrics", headers={
        "Accept": "application/openmetrics-text"}), timeout=10).read().decode()
    with open(f"{out_dir}/metrics.txt", "w") as f:
        f.write(classic)
    with open(f"{out_dir}/metrics.om.txt", "w") as f:
        f.write(om)
    if 'tpu_blackbox_captures_total{trigger="manual"} 2' not in classic:
        sys.exit("tpu_blackbox_captures_total{trigger=manual} != 2")
    print(f"blackbox ok: bundle {cap['id']} "
          f"({bundle.get('trigger')}, {len(secs)} sections), "
          f"grpc bundle {gcap['id']}")
finally:
    if gclient is not None:
        gclient.close()
    gsrv.stop()
    srv.stop()
    engine.shutdown()
EOF
[ $? -ne 0 ] && { echo "blackbox smoke FAILED"; rc=1; }
python tools/blackbox_report.py "$BB_DIR/bundle.json" \
    > "$BB_DIR/report.txt" \
    && grep -q "incident bundle" "$BB_DIR/report.txt" \
    && grep -q "journal timeline" "$BB_DIR/report.txt" \
    || { echo "blackbox_report render FAILED"; rc=1; }
python tools/promlint.py "$BB_DIR/metrics.txt" \
    || { echo "promlint blackbox (classic) FAILED"; rc=1; }
python tools/promlint.py --openmetrics "$BB_DIR/metrics.om.txt" \
    || { echo "promlint blackbox (openmetrics) FAILED"; rc=1; }
grep -q "^tpu_blackbox_" "$BB_DIR/metrics.txt" \
    || { echo "tpu_blackbox_* missing from classic dialect"; rc=1; }
grep -q "^tpu_blackbox_" "$BB_DIR/metrics.om.txt" \
    || { echo "tpu_blackbox_* missing from openmetrics dialect"; rc=1; }
rm -rf "$BB_DIR"

echo "=== stage 13/14: bench p99 regression gate ==="
if [ -f BENCH_HISTORY.json ]; then
    python tools/bench_summary.py --check \
        || { echo "bench gate FAILED"; rc=1; }
else
    echo "no BENCH_HISTORY.json — skipping"
fi

echo "=== stage 14/14: static analysis + lockdep gate ==="
python -m tools.analyze --baseline tools/analyze/baseline.json \
    || { echo "tpulint FAILED"; rc=1; }
python tools/promlint.py --definitions client_tpu \
    || { echo "promlint --definitions FAILED"; rc=1; }
CLIENT_TPU_LOCKDEP=1 timeout -k 10 600 python -m pytest -q \
    tests/test_lockdep.py tests/test_engine.py tests/test_generative.py \
    tests/test_shm_ring.py tests/test_shm_fanin.py \
    tests/test_flight_recorder.py tests/test_qos.py \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
[ $? -ne 0 ] && { echo "lockdep-enabled concurrency subset FAILED"; rc=1; }

if [ "$rc" -eq 0 ]; then
    echo "ci_check: ALL STAGES PASSED"
else
    echo "ci_check: FAILURES (see above)"
fi
exit $rc
