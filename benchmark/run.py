#!/usr/bin/env python3
"""One cell, once:  python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, traced ``breakdown``,
and last ``compared``: the numbers that decided ``correct`` beside their
limits, which are the last line of standard error as well).  Everything
the cell is made of is data the harness finds by name through
``BENCHMARK.json``: the configuration file, the traffic file
``benchmark/traffic/<traffic>.json`` and one reader per metric,
``benchmark/metrics/<metric>.py``.

Process layout: this parent never imports JAX.  It starts one child that
holds the chip (``serve.py``: the program's normal launcher serving the
cell's model over HTTP), one short CPU child that holds the plain reference
(``reference.py``), and the load generator's worker(s) (``loadgen.py``).
Server and load generator are pinned to disjoint cores.  No chip is an
error, never a fall-back; ``--rehearse-cpu`` runs tiny sizes on the CPU for
debugging and marks its line REHEARSAL.

Phases: launch -> warm-up (the cell's own traffic until every shape has
run) -> pre-roll (the cell's traffic, untimed, so the window starts in
steady state) -> window (``--seconds``) -> probes and the reference's
verdict -> shutdown.  ``setup_s`` runs from launch to the window's start.
"""

from __future__ import annotations

import argparse
import collections
import glob
import http.client
import importlib.util
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import family as family_mod  # noqa: E402
import reduce as reduce_mod  # noqa: E402
import traffic as traffic_mod  # noqa: E402

READY_S = 1000.0
_SERVING = re.compile(r"^serving http at (\S+):(\d+)")
_children: list[subprocess.Popen] = []


class BenchFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def kill_all() -> None:
    for p in _children:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
    for p in _children:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


def spawn(cmd, env, **kw) -> subprocess.Popen:
    p = subprocess.Popen(cmd, env=env, cwd=ROOT, start_new_session=True, **kw)
    _children.append(p)
    return p


def split_cores(n_loadgen: int) -> tuple[list[int], list[int]]:
    """Disjoint core sets: the load generator (and this parent, and the
    reference child) on the last ``n_loadgen`` cores, the server on the
    rest.  On a machine too small to split, both get everything."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 2 * n_loadgen + 2:
        return cores, cores
    return cores[:-n_loadgen], cores[-n_loadgen:]


def base_env() -> dict:
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = "0"
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


class Server:
    """The child that holds the chip, its stderr read line by line with
    the arrival time of each line (compilations are counted from it)."""

    def __init__(self, cfg_path, seed, cores, chips, max_model_len,
                 rehearse, log_path, server_args):
        env = base_env()
        env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
        cmd = [sys.executable, os.path.join(HERE, "serve.py"),
               "--config", cfg_path, "--seed", str(seed),
               "--chips", str(chips),
               "--cores", ",".join(map(str, cores))]
        if max_model_len:
            cmd += ["--max-model-len", str(max_model_len)]
        if rehearse:
            cmd.append("--rehearse-cpu")
        cmd += ["--", *server_args]
        self.t_start = time.monotonic()
        self.proc = spawn(cmd, env, stderr=subprocess.PIPE,
                          stdout=subprocess.DEVNULL, text=True)
        self.host, self.port = None, None
        self.device = None
        self.fatal = None
        self.compile_times: list[float] = []
        self.cache_misses = 0
        self.marks: dict[str, float] = {}
        self.tail: collections.deque[str] = collections.deque(maxlen=30)
        self._ready = threading.Event()
        self._log = open(log_path, "w")
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stderr:
            now = time.monotonic()
            self._log.write(f"{now - self.t_start:9.3f} {line}")
            self.tail.append(line)
            if line.startswith("BENCH_COMPILE"):
                self.compile_times.append(now)
            elif line.startswith("BENCH_CACHE_MISS"):
                self.cache_misses += 1
            elif line.startswith("BENCH_DEVICE "):
                self.device = json.loads(line[13:])
                self.marks["backend"] = now
            elif line.startswith("BENCH_FATAL"):
                self.fatal = line.strip()
            elif line.startswith("model ") and "READY" in line:
                self.marks["model_ready"] = now
            else:
                m = _SERVING.match(line)
                if m:
                    self.host, self.port = m.group(1), int(m.group(2))
                    self.marks["serving"] = now
                    self._ready.set()
        self._log.flush()
        self._ready.set()

    def wait_ready(self) -> None:
        self._ready.wait(READY_S)
        if self.port is None:
            raise BenchFailure(
                "the server did not come up"
                + (f": {self.fatal}" if self.fatal else "")
                + "\n" + "".join(list(self.tail)[-12:]))

    def request(self, method: str, path: str, body: dict | None = None,
                timeout: float = 120.0):
        c = http.client.HTTPConnection(self.host, self.port, timeout=timeout)
        try:
            c.request(method, path,
                      None if body is None else json.dumps(body))
            r = c.getresponse()
            data = r.read()
            if r.status != 200:
                raise BenchFailure(f"{method} {path} -> {r.status} {data!r}")
            return json.loads(data) if data else {}
        finally:
            c.close()

    def snapshot(self) -> dict:
        """The server's cumulative counters, every served model."""
        return {"t": time.monotonic(),
                "stats": self.request("GET", "/v2/models/stats"),
                "profile": self.request("GET", "/v2/profile")}

    def stop(self) -> float:
        t0 = time.monotonic()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=40)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait(timeout=30)
        self._reader.join(timeout=10)
        self._log.close()
        return time.monotonic() - t0


def run_loadgen(server, cfg, traffic, seed, seconds, phase, cores, tmp,
                tag, on_go=None) -> dict:
    """Start the worker(s), release them together, wait, merge results."""
    nw = int(traffic.get("workers", 1)) if phase != "warmup" else 1
    procs, outs = [], []
    for w in range(nw):
        out = os.path.join(tmp, f"load_{tag}_{w}.json")
        spec = {"host": server.host, "port": server.port, "config": cfg,
                "traffic": traffic, "seed": seed, "seconds": seconds,
                "phase": phase, "worker": w, "workers": nw, "out": out,
                "cores": [cores[w % len(cores)]] if cores else []}
        spec_path = os.path.join(tmp, f"spec_{tag}_{w}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        p = spawn([sys.executable, os.path.join(HERE, "loadgen.py"),
                   spec_path], base_env(), stdin=subprocess.PIPE,
                  stdout=subprocess.PIPE, text=True)
        procs.append(p)
        outs.append(out)
    for p in procs:
        line = p.stdout.readline()
        if line.strip() != "READY":
            raise BenchFailure(f"load generator did not start: {line!r}")
    lead = float(traffic.get("preroll_s", 0)) if phase == "window" else 0.0
    t_zero = time.monotonic() + 0.25 + lead
    for p in procs:
        p.stdin.write(f"GO {t_zero!r}\n")
        p.stdin.flush()
    if on_go is not None:
        on_go(t_zero)
    limit = seconds + lead + float(traffic.get("drain_s", 0)) + 660
    for p in procs:
        if p.wait(timeout=limit) != 0:
            raise BenchFailure(f"load generator exited {p.returncode}")
    merged: dict = {}
    for w, out in enumerate(outs):
        with open(out) as f:
            part = json.load(f)
        base = len(merged.get("due", []))
        part["ev_slot"] = [s + base for s in part["ev_slot"]]
        for k, v in part.items():
            if isinstance(v, list):
                merged.setdefault(k, []).extend(v)
            elif k == "reconnects":
                merged[k] = merged.get(k, 0) + v
            else:
                merged[k] = v
    return merged


def load_reader(name: str):
    """The ``read`` function of a metric: ``benchmark/metrics/<name>.py``
    or, for a name with a suffix that says which end-to-end metric it moves
    (``step_device_ms.itl``), the one reader of the quantity,
    ``benchmark/metrics/step_device_ms.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", name.rsplit(".", 1)[0] + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(manifest: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(metrics: list[dict], ctx: dict) -> dict:
    out = {}
    for m in metrics:
        if m["name"] == "setup_s":
            value = ctx["setup_s"]
        else:
            value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_window(server, traffic, t_end, trace_dir, result) -> None:
    """Trace the window's last steady seconds through the program's own
    trace control (``/v2/trace/setting`` -> jax.profiler).  Stopping a
    trace stalls the server while the profiler writes it out, so the stop
    comes just before the window ends."""
    try:
        start = (t_end - float(traffic["trace_end_margin_s"])
                 - float(traffic["trace_seconds"]))
        time.sleep(max(0.0, start - time.monotonic()))
        a = time.monotonic()
        server.request("POST", "/v2/trace/setting",
                       {"trace_level": ["TIMESTAMPS"], "log_dir": trace_dir})
        b = time.monotonic()
        time.sleep(float(traffic["trace_seconds"]))
        c = time.monotonic()
        server.request("POST", "/v2/trace/setting", {"trace_level": ["OFF"]},
                       timeout=300)
        d = time.monotonic()
        result.update(start_call_s=b - a, traced_host_s=c - b,
                      stop_call_s=d - c)
    except Exception as exc:  # noqa: BLE001 — reported by the caller
        result["error"] = repr(exc)


def one_window(server, cfg, traffic, seed, seconds, cores, tmp, tag,
               trace_dir=None) -> dict:
    """Pre-roll + window, with the server's counters read at both ends."""
    snaps, trace_info, threads = {}, {}, []

    def on_go(t_zero):
        def snap():
            time.sleep(max(0.0, t_zero - time.monotonic()))
            snaps["before"] = server.snapshot()
        threads.append(threading.Thread(target=snap))
        if trace_dir:
            threads.append(threading.Thread(
                target=trace_window, args=(server, traffic, t_zero + seconds,
                                           trace_dir, trace_info)))
        for t in threads:
            t.start()

    load = run_loadgen(server, cfg, traffic, seed, seconds, "window", cores,
                       tmp, tag, on_go=on_go)
    snaps["after"] = server.snapshot()
    for t in threads:
        t.join(timeout=400)
    if trace_info.get("error"):
        raise BenchFailure(f"tracing failed: {trace_info['error']}")
    return {"load": load, "snaps": snaps, "trace_info": trace_info}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--artifacts", default=None,
                    help="keep logs and raw results here (debugging)")
    ap.add_argument("--sweep", default=None,
                    help="key=v1,v2,...: one set-up, one window per value "
                         "of a traffic parameter (defines a cell's "
                         "operating point; prints a line per value)")
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    cfg_path = os.path.join(ROOT, config["file"])
    cfg = traffic_mod.apply_rehearsal(traffic_mod.load_json(cfg_path),
                                      args.rehearse_cpu)
    traffic = traffic_mod.apply_rehearsal(traffic_mod.load_json(os.path.join(
        HERE, "traffic", cell["traffic"] + ".json")), args.rehearse_cpu)
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])
    tag = "REHEARSAL(cpu, proves nothing about the chip) " \
        if args.rehearse_cpu else ""
    if not os.path.isdir(os.path.join(ROOT, "client_tpu")):
        print("the system under test (client_tpu/) is not in this "
              "directory", file=sys.stderr)
        return 2

    t_launch = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="bench_")
    art = args.artifacts
    if art:
        os.makedirs(art, exist_ok=True)
    server = None
    try:
        n_lg = max(2, int(traffic.get("workers", 1)) + 1)
        srv_cores, lg_cores = split_cores(n_lg)
        os.sched_setaffinity(0, {lg_cores[-1]})
        say(f"{tag}cell {args.workload} seed {args.seed} seconds {seconds} "
            f"trace {args.trace}; os.cpu_count() {os.cpu_count()}, server "
            f"cores {srv_cores}, load generator cores {lg_cores}")
        server = Server(cfg_path, args.seed, srv_cores, cell["chips"],
                        traffic.get("max_model_len"), args.rehearse_cpu,
                        os.path.join(art or tmp, "server.log"),
                        traffic.get("server_args", []))
        # The reference builds its weights while the server warms up.
        import serve as serve_mod

        ref_kwargs = serve_mod.backend_kwargs(
            cfg, args.seed, traffic.get("max_model_len"))
        probe_path = os.path.join(tmp, "probe.json")
        verdict_path = os.path.join(tmp, "verdict.json")
        ref_env = base_env()
        ref_env.update(JAX_PLATFORMS="cpu",
                       JAX_ENABLE_COMPILATION_CACHE="false")
        ref_log = open(os.path.join(art or tmp, "reference.log"), "w")
        ref = spawn([sys.executable, os.path.join(HERE, "reference.py"),
                     cfg_path, probe_path, verdict_path,
                     json.dumps(ref_kwargs), "900",
                     ",".join(map(str, lg_cores))],
                    ref_env, stdout=ref_log, stderr=subprocess.STDOUT)
        server.wait_ready()
        device = dict(server.device or {})
        phases = {"backend_s": server.marks.get("backend", 0) - server.t_start,
                  "load_s": server.marks.get("model_ready", 0)
                  - server.marks.get("backend", 0),
                  "serving_s": server.marks["serving"] - server.t_start}
        t_w = time.monotonic()
        for r in range(int(traffic.get("warmup_rounds", 1))):
            before = len(server.compile_times)
            run_loadgen(server, cfg, traffic, args.seed, seconds, "warmup",
                        lg_cores, tmp, f"warm{r}")
            if r > 0 and len(server.compile_times) == before:
                break
        phases["warmup_s"] = time.monotonic() - t_w
        say(f"{tag}set-up phases: " + json.dumps(
            {k: round(v, 3) for k, v in phases.items()}))

        if args.sweep:
            key, values = args.sweep.split("=", 1)
            for v in values.split(","):
                tr = dict(traffic)
                tr[key] = json.loads(v)
                win = one_window(server, cfg, tr, args.seed, seconds,
                                 lg_cores, tmp, f"sweep_{v}")
                ctx = reduce_mod.context(cfg, tr, args.seed, seconds, win,
                                         server.compile_times, device, None)
                ctx["setup_s"] = 0.0
                both = cell_metrics(manifest, args.workload, "end_to_end") \
                    + cell_metrics(manifest, args.workload, "per_layer")
                vals = {k: m["value"] for k, m in
                        read_metrics(both, ctx).items()}
                waves = reduce_mod.waves_delta(ctx) or {}
                say(f"{tag}SWEEP {key}={v} attempted {ctx['attempted']} "
                    f"failed {ctx['failed']} " + json.dumps(vals)
                    + (" waves by bucket " + json.dumps(
                        {b: n for b, (n, _) in sorted(waves.items())})
                       if waves else ""))
            server.stop()
            return 0

        trace_dir = os.path.join(tmp, "trace") if args.trace else None
        win = one_window(server, cfg, traffic, args.seed, seconds, lg_cores,
                         tmp, "window", trace_dir)
        t_zero = win["load"]["t_zero"]
        setup_s = t_zero - t_launch
        probe = family_mod.load(cfg["family"]).probe(
            server, cfg, traffic, args.seed)
        with open(probe_path + ".tmp", "w") as f:
            json.dump(probe, f)
        os.replace(probe_path + ".tmp", probe_path)
        try:
            ref.wait(timeout=300)
        except subprocess.TimeoutExpired:
            pass
        ref_log.close()
        verdict = {"ok": False, "why": "the reference gave no verdict"}
        if os.path.exists(verdict_path):
            with open(verdict_path) as f:
                verdict = json.load(f)
        memory_after = server.request("GET", "/v2/memory")
        drain_s = server.stop()
        server_rc = server.proc.returncode

        trace = None
        if trace_dir:
            red_env = base_env()
            red_env.update(JAX_PLATFORMS="cpu",
                           JAX_ENABLE_COMPILATION_CACHE="false")
            out = os.path.join(tmp, "trace.json")
            t_r = time.monotonic()
            rc = spawn([sys.executable, os.path.join(HERE, "tracereduce.py"),
                        trace_dir, out] + (["--inspect"] if art else []),
                       red_env).wait(timeout=600)
            if rc != 0:
                raise BenchFailure(f"trace reduction exited {rc}")
            with open(out) as f:
                trace = json.load(f)
            say(f"{tag}trace of {trace.get('trace_bytes', 0)} bytes reduced "
                f"in {time.monotonic() - t_r:.1f}s")
            trace.update(win["trace_info"])
            if art:
                for pb in glob.glob(os.path.join(
                        trace_dir, "plugins", "profile", "*", "*.xplane.pb")):
                    shutil.copy(pb, os.path.join(art, "trace.xplane.pb"))

        ctx = reduce_mod.context(cfg, traffic, args.seed, seconds, win,
                                 server.compile_times, device, trace)
        ctx.update(setup_s=setup_s, memory=memory_after, phases=phases)
        peak = max([d.get("peak_bytes_in_use", 0)
                    for d in memory_after.get("devices", [])] + [0])
        device["memory_peak_bytes"] = int(peak)
        e2e = read_metrics(
            cell_metrics(manifest, args.workload, "end_to_end"), ctx)
        layer = read_metrics(
            cell_metrics(manifest, args.workload, "per_layer"), ctx)
        compiles = reduce_mod.compiles_in_window(ctx)
        correct = bool(verdict.get("ok")) and compiles == 0 \
            and server_rc == 0
        say(f"{tag}reference verdict: {json.dumps(verdict)}")
        say(f"{tag}compiles in window {compiles} (in the whole run "
            f"{len(server.compile_times)}, of which persistent-cache "
            f"misses {server.cache_misses}); server exit {server_rc} "
            f"after {drain_s:.1f}s; setup_s {setup_s:.3f}; reconnects "
            f"{win['load'].get('reconnects', 0)}")
        say(f"{tag}end-to-end of this run (trace {args.trace}): "
            + json.dumps({k: v["value"] for k, v in e2e.items()}))
        # Per-layer readers that need no trace also read an untraced run
        # (the .obs metrics among them): printed here, on an earlier line,
        # so that what tracing does to the host is visible.
        say(f"{tag}per-layer of this run (trace {args.trace}): " + json.dumps(
            {k: v["value"] for k, v in layer.items()}))
        gaps = reduce_mod.itl_gaps_ms(ctx)
        if gaps is not None:
            say(f"{tag}token gaps in the window: {gaps.size}, deciles ms "
                + json.dumps([round(reduce_mod.pct(gaps, q), 1)
                              for q in range(10, 100, 10)]))
        result = {"correct": correct, "attempted": ctx["attempted"],
                  "failed": ctx["failed"],
                  "metrics": layer if args.trace else e2e, "device": device}
        if args.trace and not args.rehearse_cpu and not (
                trace and trace.get("busy_s")):
            raise BenchFailure("the traced run saw no device activity")
        if args.trace and trace and trace.get("busy_s"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        # What decided ``correct``, each number beside its limit (the
        # family's verdict names both), last in the line and on stderr.
        result["compared"] = {
            **{k: v for k, v in verdict.items()
               if isinstance(v, (bool, int, float, str))},
            "compiles_in_window": compiles, "compiles_in_window_limit": 0,
            "server_exit": server_rc, "server_exit_limit": 0}
        print(f"{tag}compared: " + json.dumps(result["compared"]),
              file=sys.stderr, flush=True)
        if art:
            with open(os.path.join(art, "context.json"), "w") as f:
                json.dump({"phases": phases, "verdict": verdict,
                           "trace": trace, "e2e": e2e, "layer": layer,
                           "memory": memory_after,
                           "snaps": win["snaps"]}, f)
        if args.rehearse_cpu:
            result["rehearsal"] = True
            say(tag + json.dumps(result))
            return 0
        if device.get("platform") != "tpu":
            raise BenchFailure(f"not a TPU: {device}")
        say(json.dumps(result))
        return 0
    except BenchFailure as exc:
        print(f"FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        if server is not None and server.proc.poll() is None:
            server.stop()
        kill_all()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
