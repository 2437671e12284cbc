"""MFU denominator diagnostic (round 5).

The round-5 TPU capture measured BERT-b8 at 5.42 ms/step (16.7% MFU)
during the bench run but 2.2-2.3 ms on a quiet chip, while an in-jit
barriered-scan measurement claimed 0.66 ms (269 TFLOP/s — above the v5e
bf16 peak, so something in that method under-counts).  This script
separates the three confounded quantities on live hardware:

1. per-dispatch overhead (trivial-op chain — each step is a
   host->device round trip),
2. the dispatch-loop BERT step (what bench_bert_mfu measures: true step
   + whatever per-dispatch overhead back-to-back dispatch cannot hide),
3. the barriered in-jit scan step for BERT *and*, as a methodology
   control, for an 8192^3 matmul whose sustained time is independently
   known (~6.5 ms at ~167 TFLOP/s measured via a 256-long dependent
   chain).  If the scan control disagrees with the known matmul time,
   the scan method is broken and its BERT number is discarded.
   (Round-5 live run: the control FAILED — 1053 "TFLOP/s", above the
   197 peak, because XLA slices the ``o[:1,:1]`` signal down to a dot
   product.  Hence stage 4b below.)
4b. the **dependent-feedback scan**: next step's ids derive from a
   reduction over the FULL logits (ids' = (ids + clip(sum(logits),0,1))
   mod vocab), so no slicing/DCE escape exists and iterations
   serialize on a true data dependence — the same construction the
   matmul chain control validates.  This is the trusted in-jit device
   step; the dispatch loop bounds it from above (step + per-dispatch
   overhead that back-to-back dispatch fails to hide).

Emits one JSON line per completed stage (flushed immediately, so a
timeout kill preserves every finished stage), then a final line with the
full dict.  Run it with ``JAX_PLATFORMS=tpu``.
"""

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Shared denominator from the roofline module (NOT bench: importing the
# side-effect-heavy harness just for an analytic formula coupled this
# diagnostic to bench's env preflight).
from client_tpu.observability.roofline import (  # noqa: E402
    bert_flops_per_example,
)

OUT = {}


def stage(**kv):
    OUT.update(kv)
    print(json.dumps(kv), flush=True)


def timeit(fn, n=3):
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    d = jax.devices()[0]
    if d.platform == "cpu":
        # With JAX_PLATFORMS unset JAX falls back to the CPU when the TPU
        # fails to initialize; CPU step times must never masquerade as
        # the TPU denominator evidence.
        print(json.dumps({"status": "unavailable",
                          "reason": "no TPU device"}),
              flush=True)
        raise SystemExit(1)
    stage(device_kind=d.device_kind, jax=jax.__version__)

    # 1. trivial-op chained dispatch: pure transport+runtime overhead.
    triv = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.zeros(8, np.float32))
    np.asarray(triv(x))

    def chain100():
        r = x
        for _ in range(100):
            r = triv(r)
        np.asarray(r)

    stage(trivial_dispatch_ms=timeit(chain100) / 100 * 1e3)

    # 2. matmul ground truth: 256-long dependent chain, one executable.
    N = 8192
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (N, N), jnp.bfloat16)
    b = jax.random.normal(key, (N, N), jnp.bfloat16)

    ITERS = 256

    @jax.jit
    def longchain(a, b):
        def body(c, _):
            c = c @ b
            return c / jnp.float32(91.0).astype(c.dtype), None
        out, _ = lax.scan(body, a, None, length=ITERS)
        return out

    longchain(a, b).block_until_ready()
    t = timeit(lambda: longchain(a, b).block_until_ready(), n=2) / ITERS
    stage(matmul_chain_ms=t * 1e3, matmul_chain_tflops=2 * N ** 3 / t / 1e12)

    # 3. barriered-scan methodology control on the same matmul.
    @jax.jit
    def scanbar(a, b):
        def body(c, _):
            o = c @ b
            sig = jnp.sum(o[:1, :1].astype(jnp.float32))
            c2, _ = lax.optimization_barrier((c, sig))
            return c2, None
        out, _ = lax.scan(body, a, None, length=64)
        return out

    scanbar(a, b).block_until_ready()
    t = timeit(lambda: scanbar(a, b).block_until_ready(), n=2) / 64
    stage(matmul_scanbar_ms=t * 1e3,
          matmul_scanbar_tflops=2 * N ** 3 / t / 1e12)
    # if scanbar is much shorter than the chain, the barrier failed to
    # serialize and the scan method under-counts
    stage(scan_method_honest=(
        OUT["matmul_scanbar_ms"] > 0.7 * OUT["matmul_chain_ms"]))
    del a, b

    # 4. BERT: dispatch loop vs barriered scan.
    from client_tpu.engine.model import Model
    from client_tpu.models.bert import BertBackend

    backend = BertBackend(max_batch_size=8)
    backend.config.batch_buckets = [8]
    model = Model(backend)
    ids = np.random.randint(0, 30522, size=(8, 128), dtype=np.int32)
    mask = np.ones((8, 128), dtype=np.int32)
    inputs = {"input_ids": ids, "attention_mask": mask}
    model.execute(inputs, batch_size=8)
    fn = model.raw_apply()
    staged = {k: jax.device_put(v) for k, v in inputs.items()}
    np.asarray(fn(staged)["logits"])

    def disp100():
        r = None
        for _ in range(100):
            r = fn(staged)
        np.asarray(r["logits"])

    stage(bert_dispatch_ms=timeit(disp100) / 100 * 1e3)

    @jax.jit
    def bertscan(s):
        ids0, mask0 = s["input_ids"], s["attention_mask"]

        def body(carry, _):
            o = fn({"input_ids": carry, "attention_mask": mask0})
            sig = jnp.sum(o["logits"].astype(jnp.float32))
            c2, _ = lax.optimization_barrier((carry, sig))
            return c2, None
        out, _ = lax.scan(body, ids0, None, length=100)
        return out

    bertscan(staged).block_until_ready()
    stage(bert_scanbar_ms=(
        timeit(lambda: bertscan(staged).block_until_ready()) / 100 * 1e3))

    # 4b. dependent-feedback scan: ids for step i+1 are a function of a
    # full-tensor reduction of step i's logits, so the whole forward pass
    # is on the serial critical path and nothing can be sliced away.
    # SAME builder the bench headline uses (bench.make_bert_feedback_scan)
    # — this diag validates exactly the construction the headline trusts.
    from bench import make_bert_feedback_scan

    bertfeed, scan_len = make_bert_feedback_scan(
        fn, staged["attention_mask"])
    ids0 = staged["input_ids"]
    bertfeed(ids0).block_until_ready()
    stage(bert_feedback_ms=(
        timeit(lambda: bertfeed(ids0).block_until_ready())
        / scan_len * 1e3))

    flops = bert_flops_per_example() * 8
    stage(bert_dispatch_tflops=flops / (OUT["bert_dispatch_ms"] / 1e3) / 1e12,
          bert_scanbar_tflops=flops / (OUT["bert_scanbar_ms"] / 1e3) / 1e12,
          bert_feedback_tflops=flops / (OUT["bert_feedback_ms"] / 1e3) / 1e12)
    print(json.dumps(OUT), flush=True)


if __name__ == "__main__":
    main()
