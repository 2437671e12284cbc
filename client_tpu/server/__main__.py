"""Server launcher: ``python -m client_tpu.server``.

The stand-alone process the reference's clients assume is already running
(tritonserver with ``--model-repository``; our engine is in-process, SURVEY.md
§7 step 3 — this wraps it in the two network frontends).

    python -m client_tpu.server --model-repository models/ \
        --http-port 8000 --grpc-port 8001
    python -m client_tpu.server --zoo simple,bert_base --warmup
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="client_tpu.server",
        description="TPU-native inference server (KServe v2 HTTP + gRPC)")
    ap.add_argument("--model-repository", metavar="DIR", default=None,
                    help="directory of <model>/config.pbtxt model configs")
    ap.add_argument("--zoo", metavar="NAMES", default=None,
                    help="comma-separated zoo models to serve "
                         "(default: all, when no --model-repository)")
    ap.add_argument("--http-port", type=int, default=8000)
    ap.add_argument("--grpc-port", type=int, default=8001)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--no-http", action="store_true")
    ap.add_argument("--no-grpc", action="store_true")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile every model's batch buckets at load")
    ap.add_argument("--no-jit", action="store_true",
                    help="skip XLA jit (host execution; for debugging)")
    ap.add_argument("--drain-deadline", type=float, default=30.0,
                    metavar="SECONDS",
                    help="max seconds to drain in-flight requests on "
                         "SIGTERM before forcing shutdown (default 30)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    # Set-up phases (the process's start, backend init, imports, model
    # load, warm-up, frontends) are spans of /v2/profile "startup", relative
    # to this moment.
    from client_tpu.observability import spans
    from client_tpu.observability.profiler import profiler

    profiler().startup_entry()

    # Opt-in structured logging (CLIENT_TPU_LOG=json): JSON lines on
    # stderr, with the event journal mirrored alongside normal log records.
    from client_tpu.observability.events import configure_logging

    configure_logging()

    from client_tpu.engine import TpuEngine
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models import build_repository

    jit = not args.no_jit
    zoo_names = None
    if args.zoo:
        from client_tpu.models import model_names

        zoo_names = [n.strip() for n in args.zoo.split(",") if n.strip()]
        unknown = sorted(set(zoo_names) - set(model_names()))
        if unknown:
            ap.error(f"unknown zoo model(s) {unknown}; "
                     f"available: {', '.join(model_names())}")
    if args.model_repository:
        repo = ModelRepository.from_directory(args.model_repository, jit=jit)
        if zoo_names:
            from client_tpu.models import _REGISTRY

            for name in zoo_names:
                repo.register(name, _REGISTRY[name])
    else:
        repo = build_repository(zoo_names, jit=jit)

    profiler().record_startup_since_last(spans.STARTUP_IMPORTS)
    engine = TpuEngine(repo, jit=jit, warmup=args.warmup)
    for entry in engine.repository_index():
        line = f"model {entry['name']}: {entry['state']}"
        if entry.get("reason"):
            line += f" ({entry['reason']})"
        print(line, file=sys.stderr, flush=True)
    failed = {n: why for n, why in engine.load_errors.items()
              if n in (zoo_names or ())}
    if failed:
        # tritonserver's --exit-on-error default: a model named on the
        # command line that did not load (build, placement or warmup
        # compile) is a failed start, not a server that serves on without
        # it.  A directory repository keeps "load the rest" — its index
        # above says which are missing.
        for name, why in failed.items():
            print(f"model {name} failed to load: {why}", file=sys.stderr,
                  flush=True)
        engine.shutdown()
        return 1

    t_frontends = time.monotonic_ns()
    servers = []
    http_servers = []
    grpc_servers = []
    if not args.no_http:
        from client_tpu.server import HttpInferenceServer

        http_srv = HttpInferenceServer(engine, host=args.host,
                                       port=args.http_port,
                                       verbose=args.verbose).start()
        http_servers.append(http_srv)
        servers.append(("http", http_srv.url))
    if not args.no_grpc:
        from client_tpu.server import GrpcInferenceServer

        grpc_srv = GrpcInferenceServer(engine, host=args.host,
                                       port=args.grpc_port).start()
        grpc_servers.append(grpc_srv)
        servers.append(("grpc", grpc_srv.url))
    if not servers:
        print("nothing to serve (--no-http and --no-grpc)", file=sys.stderr)
        return 2
    # Graceful drain on SIGTERM (the orchestrator's stop signal): flip
    # readiness, refuse new work, let in-flight requests finish inside
    # --drain-deadline, then exit 0.  Installed BEFORE the "serving" lines:
    # whoever reads those as "up" may send the stop signal the next moment,
    # and the default handler would kill the process undrained.
    from client_tpu.admission.drain import install_sigterm_handler

    drained = install_sigterm_handler(
        engine, http_servers=http_servers, grpc_servers=grpc_servers,
        deadline_s=args.drain_deadline)
    profiler().record_startup(spans.STARTUP_FRONTENDS, t_frontends,
                              time.monotonic_ns())
    for kind, url in servers:
        print(f"serving {kind} at {url}", file=sys.stderr, flush=True)
    try:
        while not drained.wait(timeout=3600):
            pass
        print("drained; exiting", file=sys.stderr, flush=True)
    except KeyboardInterrupt:
        print("shutting down", file=sys.stderr)
        engine.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
