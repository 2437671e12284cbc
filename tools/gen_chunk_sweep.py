"""Chunked-decode sweep: in-process generative tok/s at K in {1,2,4,8,16}.

The production posture fixes CLIENT_TPU_GEN_CHUNK=4 (the bench's labeled
headline mode).  This sweep measures, on live hardware, whether a deeper
fusion moves the knee — each K fuses K decode waves into one scanned
dispatch, so the per-dispatch overhead (0.8-1.5 ms when last measured,
2026-07-31) amortizes over K waves while TTFT/ITL burstiness grows with
K.  Reuses the bench's own probe (stability of methodology over novelty)
and appends every point to BENCH_HISTORY as it completes, so a killed
run keeps its points.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (  # noqa: E402
    _append_history,
    _bench_generative_once,
    _gen_chunk_env,
    _HIST_CTX,
    log,
    preflight,
)


def main() -> int:
    devices = preflight()
    _HIST_CTX.update({"platform": devices[0].platform,
                      "config": "gen-chunk-sweep-s64-t32"})
    out: dict = {}
    for chunk in (1, 2, 4, 8, 16):
        try:
            with _gen_chunk_env(chunk):
                res = _bench_generative_once(64, 32)
        except Exception as exc:  # noqa: BLE001 — per-point isolation
            res = {"error": repr(exc)[:200]}
        res["chunk"] = chunk
        out[f"chunk{chunk}"] = res
        _append_history({"probe": "gen_chunk_sweep", **res})
        log(f"chunk sweep k={chunk}: {json.dumps(res)}")
    print(json.dumps({"metric": "gen_chunk_sweep", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
