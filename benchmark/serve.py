#!/usr/bin/env python3
"""The benchmark's launcher: the one child that holds the chip.

    python3 benchmark/serve.py --config benchmark/configs/gpt2_small.json \
        --seed 7 --cores 0,1,2 [--max-model-len 1024] [--rehearse-cpu] \
        [-- <extra launcher flags from the traffic file>]

Reads a configuration file, builds the backend it names at the widths the
file states (``register_model(name)(lambda: Backend(**widths))``), and then
calls the program's normal launcher, ``client_tpu.server.__main__.main``,
serving that configuration over HTTP only.  No program file is edited and
the server runs with the program's defaults.

Before the launcher starts this file

- pins the process to ``--cores``;
- names the device on stderr (``BENCH_DEVICE {...}``) and refuses a platform
  other than the TPU (the CPU only with ``--rehearse-cpu``, at the file's
  tiny sizes);
- asks JAX to report every backend compilation (a persistent-cache hit
  included) through ``jax.monitoring``: one ``BENCH_COMPILE`` line each on
  stderr, which the harness stamps with its arrival time.  The program's own
  counter misses the generative schedulers' compiles (PERF.md, PR 21);
- makes the device trace that the program's ``/v2/trace/setting`` starts
  leave the Python call tracer off (``python_tracer_level=0``): with it on,
  every Python call of the host path is instrumented and the traced seconds
  no longer resemble the untraced ones (PERF.md, section 6).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def parse_cores(spec: str) -> set[int]:
    return {int(c) for c in spec.split(",") if c}


def backend_kwargs(cfg: dict, seed: int, max_model_len: int | None) -> dict:
    """Constructor arguments for the backend, taken key by key from the
    configuration file (``serve.kwargs_from_config`` maps a constructor
    argument to the published key that holds its value)."""
    serve = cfg["serve"]
    kw = dict(serve.get("kwargs", {}))
    for arg, key in serve.get("kwargs_from_config", {}).items():
        kw[arg] = cfg[key]
    if serve.get("seed_kwarg"):
        kw[serve["seed_kwarg"]] = seed % (2 ** 32)
    if max_model_len is not None and serve.get("max_model_len_kwarg"):
        kw[serve["max_model_len_kwarg"]] = max_model_len
    return kw


def report_compiles() -> None:
    import jax.monitoring as monitoring

    def on_duration(event, duration_secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            print(f"BENCH_COMPILE {duration_secs:.4f}", file=sys.stderr,
                  flush=True)

    def on_event(event, **_):
        if event == CACHE_MISS_EVENT:
            print("BENCH_CACHE_MISS", file=sys.stderr, flush=True)

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def quiet_device_trace() -> None:
    import jax

    start = jax.profiler.start_trace

    def start_trace(log_dir, *args, **kwargs):
        if kwargs.get("profiler_options") is None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            kwargs["profiler_options"] = options
        return start(log_dir, *args, **kwargs)

    jax.profiler.start_trace = start_trace


def main() -> int:
    argv = sys.argv[1:]
    extra: list[str] = []
    if "--" in argv:
        cut = argv.index("--")
        argv, extra = argv[:cut], argv[cut + 1:]
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cores", default="")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--max-model-len", type=int, default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    if args.cores:
        os.sched_setaffinity(0, parse_cores(args.cores))
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    from traffic import apply_rehearsal, load_json

    cfg = apply_rehearsal(load_json(args.config), args.rehearse_cpu)
    serve = cfg["serve"]

    from client_tpu.engine.backend_init import ensure_backend

    devices = ensure_backend()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print("BENCH_DEVICE " + json.dumps(device), file=sys.stderr, flush=True)
    want = "cpu" if args.rehearse_cpu else "tpu"
    if device["platform"] != want or (
            not args.rehearse_cpu and device["count"] < args.chips):
        print(f"BENCH_FATAL wanted {args.chips} {want} device(s), found "
              f"{device}", file=sys.stderr, flush=True)
        return 3
    report_compiles()
    quiet_device_trace()

    import client_tpu.models as zoo

    zoo._import_all()  # the zoo registers its own models first; ours last
    mod_name, cls_name = serve["backend"].split(":")
    backend_cls = getattr(importlib.import_module(mod_name), cls_name)
    kw = backend_kwargs(cfg, args.seed, args.max_model_len)
    name = serve["model_name"]
    zoo.register_model(name, default=False)(
        lambda: backend_cls(name=name, **kw))
    print("BENCH_BACKEND " + json.dumps({"model": name, "kwargs": kw}),
          file=sys.stderr, flush=True)

    from client_tpu.server.__main__ import main as server_main

    return server_main(["--zoo", name, "--host", "127.0.0.1",
                        "--http-port", "0", "--no-grpc", *extra])


if __name__ == "__main__":
    sys.exit(main())
