#!/usr/bin/env python3
"""The long-context comparison the harness's CPU reference cannot afford
(ISSUE 28), once, outside the harness:

    python3 benchmark/testdata/check_long_context.py [--seed N]
        [--prompt 20470] [--tokens 64] [--rehearse-cpu]

A 20470-byte prompt (ten windows of summaries) answered with 64 bytes (a
window dump falls among them: 20480 = 10 x 2048) is served by the normal path
(``serve.py``: the program's launcher, the generative scheduler, SSE), sent
once and then again (the same bytes must come back).  When the server is
down, the harness's reference child (``reference.py``) is given the chip and
computes the plain reference (``benchmark/models/evabyte.py``: float32,
``precision=highest``, full context, no cache) teacher-forced on the served
bytes, judging them by the family's own ``check`` at its ``MARGIN``.  Exit 0 and a last line of JSON with
``ok`` true, or 1.  This parent never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

CONFIG = os.path.join(BENCH, "configs", "evabyte_6b5.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--prompt", type=int, default=20470)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    import family
    import run as harness
    import serve as serve_mod
    import traffic as traffic_mod

    cfg = traffic_mod.apply_rehearsal(traffic_mod.load_json(CONFIG),
                                      args.rehearse_cpu)
    limit = int(cfg["max_position_embeddings"])
    prompt, tokens = args.prompt, args.tokens
    if args.rehearse_cpu:                    # the same shape, tiny
        prompt, tokens = limit - 34, 10
    tmp = tempfile.mkdtemp(prefix="longctx_")
    out_dir = os.path.join(ROOT, "chiprun_out", "check_long_context")
    os.makedirs(out_dir, exist_ok=True)
    server = harness.Server(CONFIG, args.seed, [], 1, limit,
                            args.rehearse_cpu,
                            os.path.join(out_dir, "server.log"), [])
    try:
        server.wait_ready()
        t0 = time.monotonic()
        probe = family.load(cfg["family"]).probe(
            server, cfg, {"probe_prompt_lens": [prompt],
                          "probe_max_tokens": tokens}, args.seed)
        served_s = time.monotonic() - t0
        memory = server.request("GET", "/v2/memory")
    finally:
        server.stop()
        harness.kill_all()
    probe_path = os.path.join(tmp, "probe.json")
    verdict_path = os.path.join(tmp, "verdict.json")
    with open(probe_path, "w") as f:
        json.dump(probe, f)
    env = harness.base_env()
    env.update(JAX_PLATFORMS="cpu" if args.rehearse_cpu else "tpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    kwargs = serve_mod.backend_kwargs(cfg, args.seed, limit)
    # The harness's own reference child, here with the chip for its device
    # (and no cores to pin to): weights from the backend's initialiser as
    # float32, the family's forward pass and ``check``.
    rc = subprocess.call(
        [sys.executable, os.path.join(BENCH, "reference.py"), CONFIG,
         probe_path, verdict_path, json.dumps(kwargs), "60", ""],
        env=env, cwd=ROOT)
    verdict = {"ok": False, "why": f"the reference's child exited {rc}"}
    if os.path.exists(verdict_path):
        with open(verdict_path) as f:
            verdict = json.load(f)
    peak = max([d.get("peak_bytes_in_use", 0)
                for d in memory.get("devices", [])] + [0])
    verdict.update(prompt_bytes=prompt, decoded_bytes=tokens, seed=args.seed,
                   served_twice_s=served_s, server_peak_bytes=int(peak),
                   device=server.device)
    print(json.dumps(verdict), flush=True)
    return 0 if verdict.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
