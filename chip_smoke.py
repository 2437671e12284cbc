#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the served path starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU; exit 0 = proof

Drives the system once through the entry points a user has — the
``python -m client_tpu.server`` launcher and the HTTP / gRPC / shared-memory
clients — with BERT-base at its published widths (12 layers, hidden 768,
12 heads, ffn 3072, seq 128), a token stream, the SSD model over system
shared memory, and the two long-context models that run through the Pallas
flash kernel.  Weights are random, made from the seeds the zoo fixes.

Process rules (a chip belongs to one process at a time):

- this parent never imports JAX.  It talks to servers over real sockets
  and ends with ``assert "jax" not in sys.modules``;
- everything that touches the chip is a child, children run ONE AT A TIME,
  each with ``JAX_PLATFORMS=tpu`` (``tpu,cpu`` for the kernels child, which
  needs a host reference) so that a missing chip is JAX's hard error and
  never a CPU fallback, and each child's exit code is checked;
- every wait has a deadline and the whole run has one (1150 s).

Phases (run in this order; any failure → non-zero exit, no result line):

  C  kernels   child imports JAX, names the device, runs the Pallas kernels
               compiled by Mosaic against their XLA oracles at served shapes
  D  cache     launcher twice on ``simple,bert_base``: the persistent compile
               cache gains nothing the second time; cold/warm compile seconds
  A  server    the six-model server; every request over the wire; steady
               state compiles nothing; SIGTERM drains to exit 0
  B  oracle    ``tiny_gpt_oracle``: tiny_gpt built with ``attn_impl=
               "reference"`` (the XLA scatter/gather step): tokens equal
               phase A's, which the served step produced (on a TPU, unset:
               the Pallas decode-wave kernel)
  E  four_chip ``bert_base_mc`` on >= 4 devices, else "not run (N device)"

The last stdout line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

It writes only under the compile-cache directory and ``chiprun_out/``.
``--rehearse-cpu`` is for debugging this script's control flow on a machine
without a chip: it says so on every summary line, skips the models that
would take minutes under the Pallas interpreter, and is never the default.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
OVERALL_DEADLINE_S = 1150.0   # the contract allows 1200 s, compilation included
SERVER_READY_S = 600.0        # cold --warmup of six models is mostly compile
DRAIN_DEADLINE_S = 30.0       # the launcher's --drain-deadline default
REQUEST_S = 120.0

ZOO = ["simple", "bert_base", "ssd_mobilenet_v2_coco_quantized", "tiny_gpt",
       "bert_long", "tiny_gpt_long"]
# Served through the Pallas flash kernel at seq 2048; minutes per request
# under the interpreter, so a CPU rehearsal leaves them out.
LONG_MODELS = ("bert_long", "tiny_gpt_long")

BERT_SEQ, BERT_VOCAB, BERT_LABELS, BERT_HIDDEN = 128, 30522, 2, 768
GEN_PROMPTS = [[1, 2, 3], [5, 6, 7, 8, 9], [11], [13, 14, 15, 16, 17, 18, 19]]
GEN_TOKENS = 16

# Tolerances, with the reason for each.
#
# BERT runs in bfloat16 (8 mantissa bits: one rounding is up to 2^-9 ≈ 0.2 %
# relative).  The same inputs through the SAME compiled bucket must agree
# bit for bit (transport A vs transport B, first round vs repeat).  Through
# DIFFERENT buckets (a burst batched into 8/16 vs batch-1 serial) or a
# different partitioning (four chips: tp splits every matmul's contraction
# and all-reduces partial sums) XLA may tile and fuse differently, so a value
# can land on the other side of a bf16 rounding boundary in any of the 12
# layers; logits are O(1), and 5e-2 absolute is ~10 bf16 ulps of headroom.
BERT_CROSS_BUCKET_ATOL = 5e-2
# Flash attention vs the O(S^2) oracle, bf16 inputs: both accumulate in f32,
# both round the probabilities to bf16 before the PV matmul, but the online
# softmax rounds p relative to a running max — the CPU suite's own bound.
FLASH_BF16_ATOL = 2e-2
# float32 inputs: Mosaic and XLA each pick their own number of bf16 passes
# for an f32 matmul on the MXU; against an oracle at precision=highest the
# kernel's error is bounded by a single-pass product, ~2^-8 of O(1) scores.
FLASH_F32_ATOL = 2e-2
# The decode kernel does its dot products on the VPU in float32, so against
# the oracle at precision=highest only summation order differs.
DECODE_ATOL = 2e-5
# The latent decode kernel on a bfloat16 cache: scores are exact products
# summed in float32 on both sides (the case's scale is a power of two), the
# kernel rounds the probabilities to bfloat16 before the weighted sum and
# the oracle does not: 2^-9 of O(1) outputs, with headroom.
LATENT_ATOL = 2e-2
# The grouped matmul: bfloat16 operands (exact products), float32 sums in
# another order than the oracle's, over up to 7680 terms of O(1/sqrt(k)).
GROUPED_ATOL = 1e-3


class SmokeFailure(Exception):
    """A phase found something wrong; the run ends non-zero."""


# -- deadline and children ----------------------------------------------------

_T0 = time.monotonic()
_live_child: subprocess.Popen | None = None
_current_phase = "start"


def remaining() -> float:
    return OVERALL_DEADLINE_S - (time.monotonic() - _T0)


def wait_s(limit: float) -> float:
    """A wait of at most ``limit`` seconds that also respects the overall
    deadline (never below one second, so a late wait still polls once)."""
    return max(1.0, min(limit, remaining()))


def say(msg: str) -> None:
    print(msg, flush=True)


def _kill_group(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def _deadline_watchdog() -> None:
    while remaining() > 0:
        time.sleep(min(5.0, max(0.1, remaining())))
    say(f"FAILED: overall deadline of {OVERALL_DEADLINE_S:.0f}s exceeded in "
        f"phase {_current_phase}")
    if _live_child is not None:
        _kill_group(_live_child)
    os._exit(1)


def child_env(mode: "Mode", platforms: str | None = None, **extra) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = mode.platform if platforms is None else platforms
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def spawn(cmd: list[str], env: dict, **popen_kw) -> subprocess.Popen:
    """Start a chip-needing child.  One at a time: the previous one must be
    gone, or the new one fails or hangs waiting for the chip."""
    global _live_child
    if _live_child is not None and _live_child.poll() is None:
        raise SmokeFailure(
            f"child pid {_live_child.pid} is still alive; refusing to start "
            f"{' '.join(cmd[:4])}")
    _live_child = subprocess.Popen(cmd, env=env, cwd=ROOT,
                                   start_new_session=True, **popen_kw)
    return _live_child


class Mode:
    """Chip run (the default and the only proof) or CPU rehearsal."""

    def __init__(self, rehearse_cpu: bool):
        self.rehearsal = rehearse_cpu
        self.platform = "cpu" if rehearse_cpu else "tpu"
        self.tag = "REHEARSAL(cpu, proves nothing about the chip) " \
            if rehearse_cpu else ""
        self.zoo = [m for m in ZOO
                    if not (rehearse_cpu and m in LONG_MODELS)]


def cache_dir() -> str:
    """Where the children's compile cache lives: JAX's own variable when it
    is set, else the fixed in-tree default (engine/backend_init.py)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def cache_entries() -> set[str]:
    try:
        names = os.listdir(cache_dir())
    except FileNotFoundError:
        return set()
    # JAX's file cache keeps "<key>-cache" plus an "<key>-atime" stamp.
    return {n for n in names if not n.endswith("-atime")}


# -- the server child ---------------------------------------------------------

_SERVING = re.compile(r"^serving (http|grpc) at (\S+)")
_COMPILED = re.compile(r"compiled bucket=\S+ in ([0-9.]+)s")
# JAX_LOG_COMPILES=1: one such line per executable XLA is asked to build —
# every jit cache miss, including the generative waves that
# tpu_xla_compilations_total does not count.
_JAX_COMPILING = re.compile(r"Compiling \S+ with global shapes")
_BAD_LOG = ("Array has been deleted", "Traceback (most recent call last)")


class Server:
    """One ``python -m client_tpu.server`` child with its stderr tee'd to
    ``chiprun_out/chip_smoke/<tag>.log``."""

    def __init__(self, mode: Mode, tag: str, zoo: list[str], **env_extra):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.tag = tag
        self.log_path = os.path.join(OUT_DIR, f"{tag}.log")
        self.lines: list[str] = []
        self.urls: dict[str, str] = {}
        self._ready = threading.Event()
        self.t_start = time.monotonic()
        self.ready_s: float | None = None
        cmd = [sys.executable, "-m", "client_tpu.server",
               "--zoo", ",".join(zoo), "--host", "127.0.0.1",
               "--http-port", "0", "--grpc-port", "0", "--warmup",
               "--drain-deadline", str(DRAIN_DEADLINE_S)]
        self.proc = spawn(cmd, child_env(mode, JAX_LOG_COMPILES="1",
                                         **env_extra),
                          stderr=subprocess.PIPE, stdout=subprocess.DEVNULL,
                          text=True)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        with open(self.log_path, "w") as log:
            for line in self.proc.stderr:
                log.write(line)
                log.flush()
                self.lines.append(line)
                m = _SERVING.match(line)
                if m:
                    self.urls[m.group(1)] = m.group(2)
                    if len(self.urls) == 2:
                        self.ready_s = time.monotonic() - self.t_start
                        self._ready.set()
        self._ready.set()  # EOF: the process is gone, stop waiting

    def tail(self, n: int = 12) -> str:
        return "".join(self.lines[-n:]).rstrip()

    def wait_ready(self) -> None:
        if not self._ready.wait(wait_s(SERVER_READY_S)) \
                or len(self.urls) < 2:
            rc = self.proc.poll()
            text = "".join(self.lines)
            if "Unable to initialize backend" in text:
                why = ("JAX backend initialisation failed (no accelerator "
                       "for JAX_PLATFORMS=tpu)")
            elif rc is not None:
                why = f"launcher exited with code {rc} before serving"
            else:
                why = f"not serving after {SERVER_READY_S:.0f}s"
            self.kill()
            raise SmokeFailure(f"{self.tag}: {why}\n{self.tail()}")

    def compile_seconds(self) -> float:
        return sum(float(m.group(1)) for ln in self.lines
                   if (m := _COMPILED.search(ln)))

    def jax_compiles(self) -> int:
        time.sleep(0.5)  # let the reader thread drain what is in the pipe
        return sum(1 for ln in self.lines if _JAX_COMPILING.search(ln))

    def check_log_clean(self) -> None:
        for ln in self.lines:
            for bad in _BAD_LOG:
                if bad in ln:
                    raise SmokeFailure(
                        f"{self.tag}: server log has {bad!r} — see "
                        f"{self.log_path}")

    def stop(self) -> None:
        """SIGTERM → "drained; exiting" → exit code 0, inside the drain
        deadline."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=wait_s(DRAIN_DEADLINE_S + 15))
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure(
                f"{self.tag}: still alive {DRAIN_DEADLINE_S + 15:.0f}s after "
                f"SIGTERM\n{self.tail()}") from None
        self._reader.join(timeout=10)
        took = time.monotonic() - t0
        if rc != 0 or took > DRAIN_DEADLINE_S \
                or not any("drained; exiting" in ln for ln in self.lines):
            raise SmokeFailure(
                f"{self.tag}: SIGTERM gave exit code {rc} after {took:.1f}s "
                f"(want 0 and 'drained; exiting' inside "
                f"{DRAIN_DEADLINE_S:.0f}s)\n{self.tail()}")
        say(f"    SIGTERM -> drained; exiting -> exit 0 in {took:.1f}s")

    def kill(self) -> None:
        _kill_group(self.proc)


# -- request helpers (the parent's only view of the chip is the wire) ---------


def _finite(name: str, arr) -> None:
    if not np.all(np.isfinite(arr)):
        raise SmokeFailure(f"{name}: non-finite values")


def _same(name: str, a, b) -> None:
    if a.shape != b.shape or not np.array_equal(a, b):
        diff = (float(np.max(np.abs(a.astype(np.float64) - b)))
                if a.shape == b.shape else "shape")
        raise SmokeFailure(f"{name}: not bit-identical (max |diff| {diff})")


def _close(name: str, a, b, atol: float) -> float:
    err = float(np.max(np.abs(a.astype(np.float64) - b)))
    if not err <= atol:
        raise SmokeFailure(f"{name}: max |diff| {err:.3g} > {atol:g}")
    return err


def bert_inputs(n: int, seed: int = 21):
    """Seeded BERT requests: random wordpiece ids, a padded tail on every
    second row so the attention mask does something."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, BERT_VOCAB, (n, BERT_SEQ), dtype=np.int32)
    mask = np.ones((n, BERT_SEQ), np.int32)
    for row in range(1, n, 2):
        mask[row, BERT_SEQ - 8 * (row + 1):] = 0
    return ids, mask


def _inputs(mod, feeds: dict) -> list:
    """``{name: (array, wire dtype)}`` -> the client module's InferInputs
    (binary tensor data on HTTP)."""
    inputs = []
    for name, (arr, dtype) in feeds.items():
        inp = mod.InferInput(name, list(arr.shape), dtype)
        inp.set_data_from_numpy(arr)
        inputs.append(inp)
    return inputs


def _infer_arrays(mod, client, model: str, feeds: dict,
                  outs: list[str]) -> dict:
    res = client.infer(model, _inputs(mod, feeds),
                       outputs=[mod.InferRequestedOutput(o) for o in outs])
    return {o: res.as_numpy(o) for o in outs}


def _bert_feeds(ids, mask) -> dict:
    return {"input_ids": (ids, "INT32"), "attention_mask": (mask, "INT32")}


def check_simple(http, grpc, hc, gc) -> None:
    """The reference's conformance assert: exact add/sub values."""
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.full((1, 16), 3, np.int32)
    for label, mod, client in (("http", http, hc), ("grpc", grpc, gc)):
        out = _infer_arrays(mod, client, "simple",
                            {"INPUT0": (a, "INT32"), "INPUT1": (b, "INT32")},
                            ["OUTPUT0", "OUTPUT1"])
        _same(f"simple/{label} OUTPUT0", out["OUTPUT0"], a + b)
        _same(f"simple/{label} OUTPUT1", out["OUTPUT1"], a - b)


def bert_infer(mod, client, model: str, ids, mask) -> dict:
    out = _infer_arrays(mod, client, model, _bert_feeds(ids, mask),
                        ["logits", "pooled_output"])
    n = ids.shape[0]
    if out["logits"].shape != (n, BERT_LABELS) \
            or out["pooled_output"].shape != (n, BERT_HIDDEN):
        raise SmokeFailure(
            f"{model}: shapes {out['logits'].shape} / "
            f"{out['pooled_output'].shape}")
    _finite(f"{model} logits", out["logits"])
    _finite(f"{model} pooled_output", out["pooled_output"])
    return out


def check_bert(http, grpc, hc, gc) -> dict:
    """bert_base at published widths: batch 8 over both transports, then a
    burst of 16 concurrent batch-1 requests against the same 16 serially."""
    ids, mask = bert_inputs(8)
    over_http = bert_infer(http, hc, "bert_base", ids, mask)
    over_grpc = bert_infer(grpc, gc, "bert_base", ids, mask)
    _same("bert_base b8 http vs grpc logits", over_http["logits"],
          over_grpc["logits"])
    _same("bert_base b8 http vs grpc pooled", over_http["pooled_output"],
          over_grpc["pooled_output"])

    ids16, mask16 = bert_inputs(16, seed=22)
    serial = np.concatenate([
        bert_infer(http, hc, "bert_base", ids16[i:i + 1],
                   mask16[i:i + 1])["logits"] for i in range(16)])
    pending = [
        hc.async_infer("bert_base", _inputs(
            http, _bert_feeds(ids16[i:i + 1], mask16[i:i + 1])))
        for i in range(16)]
    burst = np.concatenate([p.get_result().as_numpy("logits")
                            for p in pending])
    _finite("bert_base burst logits", burst)
    err = _close("bert_base burst vs serial logits", burst, serial,
                 BERT_CROSS_BUCKET_ATOL)
    say(f"    bert_base: b8 http == grpc bit for bit; burst(16) vs serial "
        f"max |diff| {err:.2e} (<= {BERT_CROSS_BUCKET_ATOL:g})")
    return {"ids": ids, "mask": mask, "logits": over_http["logits"],
            "burst": burst}


def check_ssd_shm(http, hc) -> dict:
    """One 300x300x3 UINT8 image with inputs AND outputs in system shared
    memory; the same image inline must give the same bytes."""
    import client_tpu.utils.shared_memory as shm
    from client_tpu.utils import InferenceServerException

    model = "ssd_mobilenet_v2_coco_quantized"
    image = np.random.default_rng(300).integers(
        0, 256, (1, 300, 300, 3), dtype=np.uint8)
    outs = [("TFLite_Detection_PostProcess", (1, 10, 4)),
            ("TFLite_Detection_PostProcess:1", (1, 10)),
            ("TFLite_Detection_PostProcess:2", (1, 10)),
            ("TFLite_Detection_PostProcess:3", (1, 1))]
    sizes = [int(np.prod(s)) * 4 for _, s in outs]
    key = f"/chip_smoke_{os.getpid()}"
    in_h = shm.create_shared_memory_region("smoke_in", key + "_in",
                                           image.nbytes)
    out_h = shm.create_shared_memory_region("smoke_out", key + "_out",
                                            sum(sizes))
    try:
        shm.set_shared_memory_region(in_h, [image])
        hc.register_system_shared_memory("smoke_in", key + "_in",
                                         image.nbytes)
        hc.register_system_shared_memory("smoke_out", key + "_out",
                                         sum(sizes))
        inp = http.InferInput("normalized_input_image_tensor",
                              list(image.shape), "UINT8")
        inp.set_shared_memory("smoke_in", image.nbytes)
        req, offset = [], 0
        for (name, _), size in zip(outs, sizes):
            o = http.InferRequestedOutput(name)
            o.set_shared_memory("smoke_out", size, offset=offset)
            req.append(o)
            offset += size
        hc.infer(model, [inp], outputs=req)
        via_shm, offset = {}, 0
        for (name, shape), size in zip(outs, sizes):
            via_shm[name] = np.array(shm.get_contents_as_numpy(
                out_h, np.float32, shape, offset=offset))
            offset += size
    finally:
        for region in ("smoke_in", "smoke_out"):
            try:
                hc.unregister_system_shared_memory(region)
            except InferenceServerException:
                pass  # never registered: the failure above is the story
        shm.destroy_shared_memory_region(in_h)
        shm.destroy_shared_memory_region(out_h)
    inline = _infer_arrays(
        http, hc, model,
        {"normalized_input_image_tensor": (image, "UINT8")},
        [name for name, _ in outs])
    for name, shape in outs:
        _finite(f"ssd {name}", via_shm[name])
        _same(f"ssd {name} shm vs inline", via_shm[name],
              inline[name].reshape(shape))
    count = float(via_shm["TFLite_Detection_PostProcess:3"].reshape(-1)[0])
    if not 0 <= count <= 10:
        raise SmokeFailure(f"ssd detection count {count} outside [0, 10]")
    say(f"    ssd over system shm: 4 outputs finite, == inline; "
        f"{count:.0f} detections")
    return via_shm


def grpc_streams(grpc, url: str, model: str, prompts: list[list[int]],
                 n_tokens: int) -> list[list[int]]:
    """Concurrent greedy generations multiplexed on ONE gRPC bidi stream."""
    client = grpc.InferenceServerClient(url)
    tokens: dict[str, list[int]] = {str(i): [] for i in range(len(prompts))}
    done = {rid: threading.Event() for rid in tokens}
    errors: list[str] = []

    def on_response(result, error) -> None:
        if error is not None:
            errors.append(str(error))
            for ev in done.values():
                ev.set()
            return
        resp = result.get_response()
        if resp.outputs:
            tokens[resp.id].extend(
                int(t) for t in result.as_numpy("TOKEN").reshape(-1))
        final = resp.parameters.get("triton_final_response")
        if final is not None and final.bool_param:
            done[resp.id].set()

    try:
        client.start_stream(on_response)
        for rid, prompt in zip(tokens, prompts):
            inp = grpc.InferInput("INPUT_IDS", [len(prompt)], "INT32")
            inp.set_data_from_numpy(np.asarray(prompt, np.int32))
            client.async_stream_infer(model, [inp], request_id=rid,
                                      parameters={"max_tokens": n_tokens})
        for rid, ev in done.items():
            if not ev.wait(wait_s(REQUEST_S)):
                raise SmokeFailure(
                    f"{model}: stream {rid} not finished in {REQUEST_S:.0f}s "
                    f"({len(tokens[rid])} tokens so far)")
        if errors:
            raise SmokeFailure(f"{model}: stream error: {errors[0]}")
    finally:
        client.stop_stream()
        client.close()
    for rid, toks in tokens.items():
        if len(toks) != n_tokens:
            raise SmokeFailure(
                f"{model}: stream {rid} gave {len(toks)} tokens, "
                f"want {n_tokens}")
    return [tokens[rid] for rid in tokens]


def sse_stream(url: str, model: str, prompt: list[int],
               n_tokens: int) -> list[int]:
    """One generation over HTTP server-sent events."""
    import http.client

    host, port = url.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port),
                                      timeout=wait_s(REQUEST_S))
    try:
        conn.request("POST", f"/v2/models/{model}/generate_stream",
                     body=json.dumps({
                         "inputs": [{"name": "INPUT_IDS", "datatype": "INT32",
                                     "shape": [len(prompt)], "data": prompt}],
                         "parameters": {"max_tokens": n_tokens}}).encode())
        resp = conn.getresponse()
        raw = resp.read().decode()   # http.client de-chunks
    finally:
        conn.close()
    if resp.status != 200:
        raise SmokeFailure(f"{model} SSE: HTTP {resp.status}: {raw[:200]}")
    tokens: list[int] = []
    for event in raw.split("\n\n"):
        if not event.startswith("data: "):
            continue
        doc = json.loads(event[len("data: "):])
        if "error" in doc:
            raise SmokeFailure(f"{model} SSE: {doc['error']}")
        for out in doc["outputs"]:
            if out["name"] == "TOKEN":
                tokens.extend(int(t) for t in out["data"])
    if len(tokens) != n_tokens:
        raise SmokeFailure(
            f"{model} SSE: {len(tokens)} tokens, want {n_tokens}")
    return tokens


def check_streams(grpc, urls: dict, model: str, vocab: int = 512) -> dict:
    """4 concurrent greedy streams == each prompt alone, token for token
    (different wave buckets, same tokens), plus one stream over SSE."""
    batched = grpc_streams(grpc, urls["grpc"], model, GEN_PROMPTS,
                           GEN_TOKENS)
    solo = [grpc_streams(grpc, urls["grpc"], model, [p], GEN_TOKENS)[0]
            for p in GEN_PROMPTS]
    for i, (b, s) in enumerate(zip(batched, solo)):
        if not all(0 <= t < vocab for t in b):
            raise SmokeFailure(f"{model}: token outside [0, {vocab}): {b}")
        if b != s:
            raise SmokeFailure(
                f"{model}: prompt {i} batched != solo\n  batched {b}\n"
                f"  solo    {s}")
    sse = sse_stream(urls["http"], model, GEN_PROMPTS[0], GEN_TOKENS)
    if sse != batched[0]:
        raise SmokeFailure(
            f"{model}: SSE != gRPC for prompt 0\n  sse  {sse}\n"
            f"  grpc {batched[0]}")
    say(f"    {model}: 4 concurrent gRPC streams x {GEN_TOKENS} tokens == "
        f"solo, SSE == gRPC; prompt 0 -> {batched[0][:6]}...")
    return {"batched": batched}


def check_long(http, grpc, hc, urls: dict) -> dict:
    """One request each through the two models served by the flash kernel."""
    rng = np.random.default_rng(2048)
    ids = rng.integers(0, BERT_VOCAB, (1, 2048), dtype=np.int32)
    mask = np.ones((1, 2048), np.int32)
    mask[0, 1900:] = 0
    out = _infer_arrays(http, hc, "bert_long", _bert_feeds(ids, mask),
                        ["logits"])
    _finite("bert_long logits", out["logits"])
    if out["logits"].shape != (1, BERT_LABELS):
        raise SmokeFailure(f"bert_long logits shape {out['logits'].shape}")
    prompt = [int(t) for t in rng.integers(0, 512, 300)]  # prompt bucket 512
    toks = grpc_streams(grpc, urls["grpc"], "tiny_gpt_long", [prompt], 8)[0]
    if not all(0 <= t < 512 for t in toks):
        raise SmokeFailure(f"tiny_gpt_long: token outside vocab: {toks}")
    say(f"    bert_long (seq 2048, flash): logits "
        f"{np.round(out['logits'][0], 4).tolist()}; tiny_gpt_long "
        f"(300-token prompt, flash prefill): {toks}")
    return {"bert_long": out["logits"], "tiny_gpt_long": toks}


def metric_total(url: str, family: str) -> float:
    import urllib.request

    with urllib.request.urlopen(f"http://{url}/metrics",
                                timeout=wait_s(30)) as resp:
        text = resp.read().decode()
    total = 0.0
    for line in text.splitlines():
        if line.startswith(family + "{") or line.startswith(family + " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


# -- phases -------------------------------------------------------------------


def phase_kernels(ctx: dict) -> None:
    """Phase C, and the device line: the one child that imports JAX itself."""
    mode: Mode = ctx["mode"]
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", "kernels"]
    if mode.rehearsal:
        cmd.append("--rehearse-cpu")
    # tpu,cpu: the kernels child wants a host-side float32 reference for
    # BERT.  A missing chip is still JAX's hard error — every platform the
    # variable lists must initialize.
    proc = spawn(cmd, child_env(mode, None if mode.rehearsal else "tpu,cpu"),
                 stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=wait_s(600))
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        raise SmokeFailure("kernels child: no result in 600s") from None
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "kernels.log"), "w") as f:
        f.write(out + "\n--- stderr ---\n" + err)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    device = None
    if lines and lines[0].startswith("{"):
        device = json.loads(lines[0]).get("device")
    if device is not None:
        ctx["device"] = device
        # The run's first line: what JAX found.
        say(f"{mode.tag}device: platform={device['platform']} "
            f"device_kind={device['kind']} count={device['count']}")
    say(f"{mode.tag}phase C kernels")
    for ln in lines[1:]:
        say("    " + ln)
    if proc.returncode != 0:
        if "Unable to initialize backend" in err:
            raise SmokeFailure(
                "JAX backend initialisation failed: no accelerator for "
                "JAX_PLATFORMS=tpu — " + next(
                    (ln.strip() for ln in err.splitlines()
                     if "Unable to initialize backend" in ln), ""))
        raise SmokeFailure(
            f"kernels child exited with code {proc.returncode}\n"
            + "\n".join(err.splitlines()[-15:]))
    if device is None:
        raise SmokeFailure("kernels child printed no device line")
    if device["platform"] != mode.platform:
        raise SmokeFailure(
            f"device platform is {device['platform']!r}, want "
            f"{mode.platform!r}")


def phase_cache(ctx: dict) -> None:
    """Phase D: a second launch compiles from the persistent cache."""
    mode: Mode = ctx["mode"]
    counts, secs, ready = [], [], []
    before = cache_entries()
    for launch in (1, 2):
        srv = Server(mode, f"phaseD_launch{launch}", ["simple", "bert_base"])
        try:
            srv.wait_ready()
            secs.append(srv.compile_seconds())
            ready.append(srv.ready_s)
            srv.stop()
        finally:
            srv.kill()
        counts.append(len(cache_entries()))
    added = counts[0] - len(before)
    say(f"    cache dir {cache_dir()}: {len(before)} entries before, "
        f"launch 1 added {added}"
        f"{' (cold for these models)' if added else ' (already warm)'}, "
        f"launch 2 added {counts[1] - counts[0]}")
    # Set-up time, not a metric: how long --warmup spent compiling.
    say(f"    warmup compile seconds (simple,bert_base): launch 1 "
        f"{secs[0]:.1f}s, launch 2 {secs[1]:.1f}s; launch-to-serving "
        f"{ready[0]:.1f}s then {ready[1]:.1f}s")
    if counts[0] == 0:
        raise SmokeFailure(
            f"compile cache {cache_dir()} is empty after a --warmup launch")
    if counts[1] != counts[0]:
        raise SmokeFailure(
            f"second launch added {counts[1] - counts[0]} cache entries "
            "(same zoo, same flags: every executable should have hit)")


def phase_server(ctx: dict) -> None:
    """Phase A: the six-model server, every request over real sockets."""
    import client_tpu.grpc as grpc
    import client_tpu.http as http

    mode: Mode = ctx["mode"]
    srv = Server(mode, "phaseA_server", mode.zoo)
    try:
        srv.wait_ready()
        say(f"    launcher serving after {srv.ready_s:.1f}s "
            f"(warmup compile {srv.compile_seconds():.1f}s, set-up time)")
        hc = http.InferenceServerClient(srv.urls["http"], concurrency=16,
                                        network_timeout=REQUEST_S)
        gc = grpc.InferenceServerClient(srv.urls["grpc"])
        try:
            _serve_and_check(ctx, srv, http, grpc, hc, gc)
        finally:
            hc.close()
            gc.close()
        srv.check_log_clean()
        srv.stop()
    finally:
        srv.kill()


def _serve_and_check(ctx, srv: Server, http, grpc, hc, gc) -> None:
    mode: Mode = ctx["mode"]
    index = {e["name"]: e for e in hc.get_model_repository_index()}
    for name in mode.zoo:
        entry = index.get(name)
        if entry is None or entry["state"] != "READY":
            raise SmokeFailure(
                f"model {name} is not READY: "
                f"{(entry or {}).get('reason', 'absent from the index')}")
    profile = hc.get_profile()
    roof = profile["roofline"]
    memory = hc.get_memory()
    limit = memory["devices"][0]["bytes_limit"]
    say(f"    {len(mode.zoo)} models READY; /v2/profile device_kind="
        f"{roof['device_kind']!r} peaks={roof['peaks']}; /v2/memory "
        f"bytes_limit={limit} on {len(memory['devices'])} device(s)")
    # On record: the single-chip zoo device_puts everything to device 0.
    say("    bytes_in_use per device: "
        f"{ {d['device']: d['bytes_in_use'] for d in memory['devices']} }")
    if not mode.rehearsal:
        # How a parent that is off JAX proves the server is on the chip.
        if "tpu" not in roof["device_kind"].lower():
            raise SmokeFailure(
                f"/v2/profile device_kind {roof['device_kind']!r} is not a "
                "TPU")
        if not isinstance(roof["peaks"], dict) \
                or roof["peaks"].get("source") != "registry":
            raise SmokeFailure(
                f"/v2/profile peaks are {roof['peaks']!r}, want the "
                "registry row")
        if not limit > 0:
            raise SmokeFailure(f"/v2/memory bytes_limit is {limit}")

    def round_of_requests() -> dict:
        check_simple(http, grpc, hc, gc)
        got = {"bert": check_bert(http, grpc, hc, gc),
               "ssd": check_ssd_shm(http, hc),
               "gen": check_streams(grpc, srv.urls, "tiny_gpt")}
        if not mode.rehearsal:
            got["long"] = check_long(http, grpc, hc, srv.urls)
        return got

    say("  round 1")
    first = round_of_requests()
    compiles = metric_total(srv.urls["http"], "tpu_xla_compilations_total")
    jax_compiles = srv.jax_compiles()
    say("  round 2 (steady state: the same shapes again)")
    second = round_of_requests()
    moved = metric_total(srv.urls["http"],
                         "tpu_xla_compilations_total") - compiles
    jax_moved = srv.jax_compiles() - jax_compiles
    say(f"    steady state: tpu_xla_compilations_total {compiles:.0f} -> "
        f"+{moved:.0f}; XLA compilations logged by JAX {jax_compiles} -> "
        f"+{jax_moved}")
    if moved or jax_moved:
        raise SmokeFailure(
            f"the repeat round compiled: +{moved:.0f} counted, "
            f"+{jax_moved} logged by JAX (see {srv.log_path})")
    _same("bert_base b8 round 1 vs round 2", first["bert"]["logits"],
          second["bert"]["logits"])
    if first["gen"] != second["gen"]:
        raise SmokeFailure("tiny_gpt tokens changed between rounds")
    if not mode.rehearsal and first["long"]["tiny_gpt_long"] \
            != second["long"]["tiny_gpt_long"]:
        raise SmokeFailure("tiny_gpt_long tokens changed between rounds")
    # Established, not fixed here: does XLA's cost model say anything on
    # this backend (observability/roofline.py swallows every failure).
    after = hc.get_profile("bert_base")
    bert_model = next(iter(after["models"].values()), {})
    rollup = bert_model.get("roofline") or {}
    reasons = sorted({b["roofline"].get("reason", "?")
                      for b in bert_model.get("buckets", [])
                      if (b.get("roofline") or {}).get("cost_model")
                      == "unavailable"})
    say(f"    bert_base cost_model_coverage="
        f"{rollup.get('cost_model_coverage')} mfu={rollup.get('mfu')} "
        f"bound={rollup.get('bound')}"
        + (f"; cost model unavailable: {reasons}" if reasons else ""))
    ctx["bert"] = first["bert"]
    ctx["gen_tokens"] = first["gen"]["batched"]


def phase_oracle(ctx: dict) -> None:
    """Phase B: two implementations of the decode step, token for token.
    Phase A's server ran with no setting, so on the chip its tokens came
    from the served step, the Pallas decode-wave kernel; this server is
    told to serve the oracle, the XLA scatter/gather/dense-softmax step.
    (A rehearsal on the CPU serves the XLA step in phase A too, and this
    phase then compares it with itself: it says so.)"""
    import client_tpu.grpc as grpc

    mode: Mode = ctx["mode"]
    if "gen_tokens" not in ctx:
        raise SmokeFailure("not run: needs phase A's tokens")
    srv = Server(mode, "phaseB_oracle", ["tiny_gpt_oracle"])
    try:
        srv.wait_ready()
        got = check_streams(grpc, srv.urls, "tiny_gpt_oracle")["batched"]
        for i, (oracle, served) in enumerate(zip(got, ctx["gen_tokens"])):
            if oracle != served:
                raise SmokeFailure(
                    f"served decode != oracle decode for prompt {i}\n"
                    f"  served (kernel) {served}\n  oracle (XLA)    {oracle}")
        served_by = ("XLA step, the same implementation twice"
                     if mode.rehearsal else "Pallas kernel")
        say(f"    served decode tokens ({served_by}, phase A) == oracle "
            f"decode tokens (XLA scatter/gather, this phase) "
            f"({len(got)} prompts x {GEN_TOKENS})")
        srv.check_log_clean()
        srv.stop()
    finally:
        srv.kill()


def phase_four_chip(ctx: dict) -> None:
    """Phase E: one multi-chip model, when there are four chips to put it
    on.  (The kernels child did the kv_shards=4 ring == psum check.)"""
    import client_tpu.http as http

    mode: Mode = ctx["mode"]
    count = ctx.get("device", {}).get("count", 0)
    if count < 4:
        say(f"    four_chip: not run ({count} device)")
        return
    if "bert" not in ctx:
        raise SmokeFailure("not run: needs phase A's bert_base logits")
    srv = Server(mode, "phaseE_four_chip", ["bert_base_mc"])
    try:
        srv.wait_ready()
        hc = http.InferenceServerClient(srv.urls["http"],
                                        network_timeout=REQUEST_S)
        try:
            out = bert_infer(http, hc, "bert_base_mc", ctx["bert"]["ids"],
                             ctx["bert"]["mask"])
            memory = hc.get_memory()
        finally:
            hc.close()
        err = _close("bert_base_mc vs single-chip bert_base logits",
                     out["logits"], ctx["bert"]["logits"],
                     BERT_CROSS_BUCKET_ATOL)
        per_device = {d["device"]: d["bytes_in_use"]
                      for d in memory["devices"]}
        say(f"    bert_base_mc vs bert_base: max |diff| {err:.2e} "
            f"(<= {BERT_CROSS_BUCKET_ATOL:g}); bytes_in_use per device "
            f"{per_device}")
        holding = [d for d, b in per_device.items() if b > 0]
        if len(holding) < 4:
            raise SmokeFailure(
                f"weights live on {len(holding)} device(s), want 4: "
                f"{per_device}")
        srv.check_log_clean()
        srv.stop()
    finally:
        srv.kill()


PHASES = [
    ("C kernels", phase_kernels),
    ("D cache", phase_cache),
    ("A server", phase_server),
    ("B oracle", phase_oracle),
    ("E four_chip", phase_four_chip),
]


def run_phases(phases, ctx: dict) -> int:
    """Run every phase in order; return the process exit code.  A failure
    is reported and the later phases still run (one chip call should say
    everything that is wrong), but nothing turns a failure into a zero."""
    global _current_phase
    mode: Mode = ctx["mode"]
    failed = []
    for name, fn in phases:
        _current_phase = name
        t0 = time.monotonic()
        if "device" in ctx:
            # Until the first phase has named the device, nothing is
            # printed: the device line is the run's first.
            say(f"{mode.tag}phase {name}")
        try:
            fn(ctx)
        except SmokeFailure as exc:
            failed.append(name)
            say(f"{mode.tag}phase {name}: FAILED after "
                f"{time.monotonic() - t0:.1f}s — {exc}")
            if name.startswith("C") and "device" not in ctx:
                # No device: nothing below can tell us more, and every
                # further child would only repeat the backend error.
                break
        else:
            say(f"{mode.tag}phase {name}: ok in "
                f"{time.monotonic() - t0:.1f}s")
    if failed:
        say(f"{mode.tag}FAILED: {', '.join(failed)} "
            f"({time.monotonic() - _T0:.0f}s)")
        return 1
    say(f"{mode.tag}all phases ok in {time.monotonic() - _T0:.0f}s")
    device = ctx["device"]
    result = {"ok": True, "device": {"platform": device["platform"],
                                     "kind": device["kind"],
                                     "count": device["count"]}}
    if mode.rehearsal:
        # Not a proof: a rehearsal's result line can never read ok.
        result = {**result, "ok": False, "rehearsal": True}
    print(json.dumps(result), flush=True)
    return 0


# -- the kernels child (the only code here that imports JAX) ------------------


def kernels_child(rehearsal: bool) -> int:
    """Pallas kernels against their XLA oracles at the shapes the zoo
    serves, compiled (not interpreted), on the device JAX reports."""
    import functools

    from client_tpu.engine.backend_init import ensure_backend, pallas_interpret

    devices = ensure_backend()
    dev = devices[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)
    if not rehearsal and dev.platform != "tpu":
        print(f"kernels: device platform is {dev.platform!r}, not tpu")
        return 1
    interpret = pallas_interpret()
    if not rehearsal and interpret:
        print("kernels: pallas_interpret() is True on a tpu platform")
        return 1

    import jax
    import jax.numpy as jnp

    from client_tpu.ops.decode_kernel import (
        decode_wave_attention,
        reference_decode_attention,
    )
    from client_tpu.ops.flash_attention import (
        flash_attention,
        reference_attention,
    )

    failures = []

    def mosaic(fn, *args) -> bool:
        """The lowering carries a Mosaic custom call: compiled, not
        interpreted."""
        return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()

    def report(name: str, ok: bool, detail: str) -> None:
        print(f"{name}: {'ok' if ok else 'FAILED'} — {detail}", flush=True)
        if not ok:
            failures.append(name)

    # Rehearsal shrinks the sequence (the interpreter is slow); the chip
    # run uses the served shapes.
    s_long = 256 if rehearsal else 2048
    blocks = (64, 128) if rehearsal else (512, 1024)  # models/bert.py caps

    def flash_case(name, shape, dtype, causal, with_bias, atol):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in ks)
        bias = None
        if with_bias:
            keep = jnp.arange(shape[1])[None, :] < shape[1] - 100
            bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)
            bias = jnp.broadcast_to(bias, (shape[0], shape[1]))
        fn = functools.partial(flash_attention, causal=causal,
                               block_q=blocks[0], block_k=blocks[1],
                               interpret=interpret)
        got = np.asarray(fn(q, k, v, bias), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference_attention(q, k, v, bias,
                                                  causal=causal), np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(np.all(np.isfinite(got))) and err <= atol
        compiled = mosaic(fn, q, k, v, bias)
        if not rehearsal and not compiled:
            ok = False
        report(name, ok, f"shape {shape} {jnp.dtype(dtype).name} blocks "
               f"{blocks}: max |diff| {err:.2e} (<= {atol:g}), "
               f"mosaic custom call {'present' if compiled else 'ABSENT'}")

    # bert_long: [B, 2048, 12, 64] bf16 with the padding bias.
    flash_case("flash_attention(bias)", (2, s_long, 12, 64), jnp.bfloat16,
               False, True, FLASH_BF16_ATOL)
    # tiny_gpt_long prefill: [1, n, 4, 64] f32, causal.
    flash_case("flash_attention(causal)", (1, s_long, 4, 64), jnp.float32,
               True, False, FLASH_F32_ATOL)

    def decode_case(name, layers, rows, seq, bsz, lens_list, h=4):
        d = 64         # tiny_gpt: d_model 256 / 4 heads
        ks = jax.random.split(jax.random.PRNGKey(11), 5)
        k_a = jax.random.normal(ks[0], (layers, rows, seq, h * d))
        v_a = jax.random.normal(ks[1], (layers, rows, seq, h * d))
        q, kn, vn = (jax.random.normal(kk, (bsz, h, d)) for kk in ks[2:])
        # Distinct real rows, plus one padded lane parked on the dummy row.
        rows_ix = np.arange(bsz, dtype=np.int32) * 2 % (rows - 1)
        rows_ix[-1] = rows - 1
        lens = np.asarray(lens_list, np.int32)
        lens[-1] = 0
        layer = layers - 1
        fn = functools.partial(decode_wave_attention, layer=layer,
                               interpret=interpret)
        args = (k_a, v_a, q, kn, vn, jnp.asarray(rows_ix), jnp.asarray(lens))
        compiled = mosaic(fn, *args)
        fk, fv, fo = (np.asarray(x) for x in fn(*args))
        with jax.default_matmul_precision("highest"):
            rk, rv, ro = (np.asarray(x) for x in reference_decode_attention(
                *args, layer=layer))
        live = np.ones(bsz, bool)
        live[-1] = False  # the padded lane is junk in both, by design
        err = float(np.max(np.abs(fo[live] - ro[live])))
        # The arena IS the model state: every row bitwise-preserved except
        # the scattered positions, which hold exactly the new K/V.
        bitwise = np.array_equal(fk, rk) and np.array_equal(fv, rv)
        k_in = np.asarray(k_a)
        touched = np.zeros(k_in.shape[:3], bool)
        touched[layer, rows_ix, lens] = True
        preserved = np.array_equal(fk[~touched], k_in[~touched])
        ok = err <= DECODE_ATOL and bitwise and preserved \
            and bool(np.all(np.isfinite(fo[live])))
        if not rehearsal and not compiled:
            ok = False
        report(name, ok, f"arena [{layers},{rows},{seq},{h * d}] wave {bsz}: "
               f"max |diff| {err:.2e} (<= {DECODE_ATOL:g}), arena == oracle "
               f"bitwise {bitwise}, untouched rows preserved {preserved}, "
               f"mosaic custom call {'present' if compiled else 'ABSENT'}")

    # tiny_gpt: 4 layers, 64 streams + dummy row, 128 positions.
    decode_case("decode_wave_attention(tiny_gpt)", 4, 65, 128, 8,
                [7, 0, 127, 64, 1, 8, 100, 0])
    if not rehearsal:
        # tiny_gpt_long: 16 streams + dummy row, 2048 positions.
        decode_case("decode_wave_attention(tiny_gpt_long)", 4, 17, 2048, 4,
                    [300, 2047, 1024, 0])
        # GPT-2's heads (12 x 64 on a 768-lane row), 1024 positions: the
        # benchmark's geometry; lengths around the 512-position block edge.
        decode_case("decode_wave_attention(gpt2)", 2, 17, 1024, 8,
                    [700, 0, 1023, 511, 512, 513, 1, 0], h=12)

    def latent_case(name, layers, rows, seq, bsz, heads, rank, rope_dim,
                    lens_list):
        """``latent_wave_attention`` (one shared row a position, bfloat16)
        against its oracle: the output, the arena bitwise, untouched rows."""
        from client_tpu.ops.decode_kernel import (
            latent_row_width,
            latent_wave_attention,
            reference_latent_attention,
        )

        w = latent_row_width(rank, rope_dim)
        ks = jax.random.split(jax.random.PRNGKey(17), 3)
        c_a = jax.random.normal(ks[0], (layers, rows, seq, w)).astype(
            jnp.bfloat16)
        # The query as a model hands it over: ``[B, W, H]``, scaled, in the
        # arena's dtype, so kernel and oracle multiply the same numbers.
        q = (jax.random.normal(ks[1], (bsz, w, heads)) / 32).astype(
            jnp.bfloat16)
        new = jax.random.normal(ks[2], (bsz, w))
        rows_ix = np.arange(bsz, dtype=np.int32) * 2 % (rows - 1)
        rows_ix[-1] = rows - 1
        lens = np.asarray(lens_list, np.int32)
        lens[-1] = 0
        layer, kw = layers - 1, {"value_dim": rank}
        fn = functools.partial(latent_wave_attention, layer=layer,
                               interpret=interpret, **kw)
        args = (c_a, q, new, jnp.asarray(rows_ix), jnp.asarray(lens))
        compiled = mosaic(fn, *args)
        fc, fo = (np.asarray(x) for x in fn(*args))
        with jax.default_matmul_precision("highest"):
            rc, ro = (np.asarray(x) for x in reference_latent_attention(
                *args, layer=layer, **kw))
        live = np.ones(bsz, bool)
        live[-1] = False
        err = float(np.max(np.abs(fo[live] - ro[live])))
        bitwise = np.array_equal(fc, rc)
        c_in = np.asarray(c_a)
        touched = np.zeros(c_in.shape[:3], bool)
        touched[layer, rows_ix, lens] = True
        preserved = np.array_equal(fc[~touched], c_in[~touched])
        ok = err <= LATENT_ATOL and bitwise and preserved \
            and bool(np.all(np.isfinite(fo[live])))
        if not rehearsal and not compiled:
            ok = False
        report(name, ok, f"arena [{layers},{rows},{seq},{w}] bfloat16, wave "
               f"{bsz} x {heads} heads: max |diff| {err:.2e} (<= "
               f"{LATENT_ATOL:g}), arena == oracle bitwise {bitwise}, "
               f"untouched rows preserved {preserved}, mosaic custom call "
               f"{'present' if compiled else 'ABSENT'}")

    def grouped_case(name, experts, k, n, pairs, tile_m):
        """``grouped_matmul`` against its dense oracle on one sorted
        layout: a skewed routing with one expert untouched."""
        from client_tpu.ops.grouped_matmul import (
            capacity_rows,
            grouped_matmul,
            plan_groups,
            reference_grouped_matmul,
        )

        rng = np.random.default_rng(19)
        expert = rng.integers(0, experts + 2, pairs).clip(0, experts)
        expert[expert == 1] = 0          # expert 1 untouched, 0 busiest
        rows = capacity_rows(pairs, experts, tile_m)
        plan = plan_groups(jnp.asarray(expert, jnp.int32), experts, tile_m,
                           rows)
        ks = jax.random.split(jax.random.PRNGKey(23), 2)
        x = jax.random.normal(ks[0], (pairs, k)).astype(jnp.bfloat16)
        xs = jnp.zeros((rows + 1, k), jnp.bfloat16).at[plan["dest"]].set(
            x)[:rows]
        wts = (jax.random.normal(ks[1], (experts, k, n)) / np.sqrt(k)
               ).astype(jnp.bfloat16)
        fn = functools.partial(grouped_matmul, tile_m=tile_m,
                               interpret=interpret)
        args = (xs, wts, plan["tile_expert"], plan["n_tiles"])
        compiled = mosaic(fn, *args)
        used = int(plan["n_tiles"][0]) * tile_m
        got = np.asarray(fn(*args))[:used]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference_grouped_matmul(
                xs, wts, plan["padded"]))[:used]
        err = float(np.max(np.abs(got - want)))
        sizes = np.asarray(plan["sizes"])
        ok = err <= GROUPED_ATOL and bool(np.all(np.isfinite(got))) \
            and sizes[1] == 0 and int(sizes.sum()) == int(
                (expert < experts).sum())
        if not rehearsal and not compiled:
            ok = False
        report(name, ok, f"{pairs} pairs over {experts} experts of "
               f"[{k},{n}] bfloat16 (groups {sizes.tolist()}): max |diff| "
               f"{err:.2e} (<= {GROUPED_ATOL:g}), mosaic custom call "
               f"{'present' if compiled else 'ABSENT'}")

    if rehearsal:
        latent_case("latent_wave_attention(tiny)", 2, 9, 64, 4, 4, 32, 8,
                    [7, 63, 16, 0])
        grouped_case("grouped_matmul(tiny)", 4, 32, 256, 24, 8)
    else:
        # The latent cell's widths: 128 heads on a 512 + 64 -> 640-lane row,
        # 4096 positions, lengths around the 512-row block edge; 16 experts
        # of 7680 x 4096 (gate | up) and 2048 x 7680 (down).
        latent_case("latent_wave_attention(128 heads x 576)", 2, 17, 4096, 8,
                    128, 512, 64, [700, 0, 4095, 511, 512, 513, 1, 0])
        # The hybrid cell's: 32 heads in a tile of 128 lanes, slots of 8192
        # rows (sixteen blocks for three buffers), lanes without a live row
        # between long ones, a full slot, lengths around a block's edge.
        latent_case("latent_wave_attention(32 heads x 576, 8192 rows)", 2,
                    17, 8192, 8, 32, 512, 64,
                    [3300, 0, 8191, 0, 1024, 1025, 5119, 0])
        grouped_case("grouped_matmul(16 x 7680 x 4096)", 16, 7680, 4096,
                     1024, 16)
        grouped_case("grouped_matmul(16 x 2048 x 7680)", 16, 2048, 7680,
                     1024, 16)

    if len(devices) >= 4:
        from client_tpu.parallel.kv_shard import (
            arena_row_layout,
            kv_mesh,
            shard_arena,
            sharded_decode_attention,
        )

        mesh = kv_mesh(4)
        total, free, _dummy = arena_row_layout(8, 4)
        h, d, seq, bsz = 4, 64, 128, 4
        ks = jax.random.split(jax.random.PRNGKey(13), 5)
        arena = shard_arena(
            {"k": jax.random.normal(ks[0], (2, total, seq, h * d)),
             "v": jax.random.normal(ks[1], (2, total, seq, h * d)),
             "tok": jnp.zeros(total, jnp.int32)}, mesh)
        q, kn, vn = (jax.random.normal(kk, (bsz, h, d)) for kk in ks[2:])
        rows_ix = jnp.asarray([free[0], free[3], free[5], free[7]],
                              jnp.int32)   # one lane per shard
        lens = jnp.asarray([5, 127, 0, 64], jnp.int32)
        outs = {}
        for combine in ("ring", "psum"):
            fn = jax.jit(functools.partial(
                sharded_decode_attention, mesh, layer=1,
                interpret=interpret, combine=combine))
            outs[combine] = np.asarray(
                fn(arena["k"], arena["v"], q, kn, vn, rows_ix, lens)[2])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference_decode_attention(
                arena["k"], arena["v"], q, kn, vn, rows_ix, lens,
                layer=1)[2])
        same = np.array_equal(outs["ring"], outs["psum"])
        err = float(np.max(np.abs(outs["ring"] - want)))
        report("kv_shards=4 ring == psum", same and err <= DECODE_ATOL,
               f"remote-DMA ring vs XLA psum bitwise {same}; vs single-chip "
               f"oracle max |diff| {err:.2e} (<= {DECODE_ATOL:g})")
    else:
        print(f"kv_shards=4 ring == psum: not run ({len(devices)} device)",
              flush=True)

    # BERT at published widths, depth cut to 2 layers: the chip's bf16
    # program against the same program on the host CPU.
    cpu = None if rehearsal else jax.devices("cpu")[0]
    if cpu is not None:
        from client_tpu.models.bert import BertBackend

        backend = BertBackend(n_layers=2)
        apply = backend._build_apply()
        params = backend._init_params()
        ids, mask = bert_inputs(2)
        feeds = {"input_ids": ids, "attention_mask": mask}
        on_chip = jax.jit(apply)(jax.device_put(params, dev), feeds)
        with jax.default_device(cpu):
            on_host = jax.jit(apply)(jax.device_put(params, cpu),
                                     jax.device_put(feeds, cpu))
        err = float(np.max(np.abs(np.asarray(on_chip["logits"])
                                  - np.asarray(on_host["logits"]))))
        report("bert(2 of 12 layers, full width) tpu vs cpu",
               err <= BERT_CROSS_BUCKET_ATOL,
               f"logits max |diff| {err:.2e} "
               f"(<= {BERT_CROSS_BUCKET_ATOL:g})")

    return 1 if failures else 0


# -- entry --------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=["kernels"], default=None,
                    help="internal: run as the kernels child")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug this script's control flow on the CPU; "
                         "proves nothing about the chip")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "client_tpu")):
        print("chip_smoke.py: no client_tpu package next to this script — "
              "it drives the repository it lives in", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    if args.phase == "kernels":
        return kernels_child(args.rehearse_cpu)
    # gRPC's C core logs connection teardown ("Got goaway") at INFO on
    # stderr whenever a server child exits; keep the output to our lines.
    os.environ.setdefault("GRPC_VERBOSITY", "ERROR")
    threading.Thread(target=_deadline_watchdog, daemon=True).start()
    ctx = {"mode": Mode(args.rehearse_cpu)}
    try:
        rc = run_phases(PHASES, ctx)
    finally:
        if _live_child is not None:
            _kill_group(_live_child)
    assert "jax" not in sys.modules, "the parent imported JAX"
    return rc


if __name__ == "__main__":
    sys.exit(main())
