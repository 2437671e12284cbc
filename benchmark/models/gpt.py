"""Family ``gpt``: a GPT-2 style decoder served over ``generate_stream``.

The forward pass is written from the published equations, with the
departures the configuration file lists; nothing here is used by the server.

Tolerance (stated here, with the reason).  The decoder is judged teacher-
forced on the server's own emitted tokens: four streams sent together, and
the same four sent one at a time.  Each emitted token's reference logit must
be within ``MARGIN`` = 0.04 of that row's maximum.  Logits are ~N(0,1) over
50257 entries (maximum near 4.3, the best two typically 0.2 apart); the TPU
multiplies float32 matrices in bfloat16 passes by default, which put an
emitted token's logit at most 0.012 below the reference's best in this PR's
chip runs, so 0.04 is about three times the worst seen.  A decoder computed
in bfloat16 throughout moves a logit by 0.02-0.05 and fails; a token from a
wrong position, a wrong cache row or a stale arena is several units below.

Concurrent and solo streams must agree token for token except at a near-tie:
where they part they share their prefix, so both tokens were chosen from the
same reference row, and the reference's gap between its best two logits
there must be at most ``MARGIN`` (with random weights the largest logit
changes on rounding between wave buckets; after a near-tie the streams
legitimately differ).  A cross-stream mix-up in the arena parts them where
the gap is wide, and fails.
"""

from __future__ import annotations

import http.client
import json
import math
import threading

import numpy as np

import family
import roofline

MARGIN = 0.04


# -- wire ---------------------------------------------------------------------

def encode_request(cfg, model, rows, prompt_len, output_len, rng) -> bytes:
    """SSE generate request: binary prompt ids, greedy decoding."""
    wire = cfg["wire"]
    ids = np.ascontiguousarray(
        rng.integers(0, int(cfg[wire["vocab"]]), int(prompt_len)), "<i4")
    head = {"inputs": [
        {"name": wire["input_ids"], "shape": [int(ids.size)],
         "datatype": "INT32",
         "parameters": {"binary_data_size": ids.nbytes}}],
        "parameters": {"max_tokens": int(output_len), "seed": 0}}
    return family.http_request(f"/v2/models/{model}/generate_stream", head,
                               ids.tobytes())


def probe(server, cfg, traffic, seed) -> dict:
    """Four prompts (``probe_prompt_lens`` of the traffic file), streamed
    together and then one at a time."""
    rng = np.random.default_rng([int(seed), 99])
    wire = cfg["wire"]
    model = cfg["serve"]["model_name"]
    prompts = [rng.integers(0, int(cfg[wire["vocab"]]), int(n)).tolist()
               for n in traffic["probe_prompt_lens"]]
    max_tokens = int(traffic.get("probe_max_tokens", 6))

    def stream(prompt, out, i):
        c = http.client.HTTPConnection(server.host, server.port, timeout=300)
        try:
            c.request("POST", f"/v2/models/{model}/generate_stream",
                      json.dumps({"inputs": [
                          {"name": wire["input_ids"], "shape": [len(prompt)],
                           "datatype": "INT32", "data": prompt}],
                          "parameters": {"max_tokens": max_tokens,
                                         "seed": 0}}))
            text = c.getresponse().read().decode()
        finally:
            c.close()
        toks = []
        for ev in text.split("\n\n"):
            if ev.startswith("data: "):
                d = json.loads(ev[6:])
                if "error" in d:
                    out[i] = {"error": d["error"]}
                    return
                toks += [o["data"][0] for o in d["outputs"]
                         if o["name"] == "TOKEN"]
        out[i] = toks

    concurrent: dict = {}
    ts = [threading.Thread(target=stream, args=(p, concurrent, i))
          for i, p in enumerate(prompts)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=400)
    solo: dict = {}
    for i, p in enumerate(prompts):
        stream(p, solo, i)
    n = len(prompts)
    return {"prompts": prompts, "max_tokens": max_tokens,
            "concurrent": [concurrent.get(i, []) for i in range(n)],
            "solo": [solo.get(i, []) for i in range(n)]}


# -- the plain reference --------------------------------------------------------

def forward(p, ids, n_heads, last, eps=1e-5):
    """GPT-2 style decoder, full context, no cache: pre-LayerNorm blocks,
    learned positions, causal softmax attention, tanh-gelu MLP, final
    LayerNorm, untied head; no biases (configuration file, departures).
    ``ids`` [n] -> logits of the ``last`` positions, [last, vocab]."""
    import jax
    import jax.numpy as jnp

    n = ids.shape[0]
    dm = p["embed"].shape[1]
    d = dm // n_heads
    x = p["embed"][ids] + p["pos"][:n]
    causal = jnp.tril(jnp.ones((n, n), bool))
    for lp in p["layers"]:
        h = family.layer_norm(x, lp["ln1g"], lp["ln1b"], eps)
        q = (h @ lp["wq"]).reshape(n, n_heads, d)
        k = (h @ lp["wk"]).reshape(n, n_heads, d)
        v = (h @ lp["wv"]).reshape(n, n_heads, d)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(n, dm) @ lp["wo"]
        h2 = family.layer_norm(x, lp["ln2g"], lp["ln2b"], eps)
        x = x + family.gelu_tanh(h2 @ lp["w1"]) @ lp["w2"]
    return family.layer_norm(x[n - last:], p["lnfg"], p["lnfb"],
                             eps) @ p["head"]


def check(params, probe, backend) -> dict:
    streams = probe["concurrent"] + probe["solo"]
    if any(isinstance(s, dict) for s in streams):
        return {"ok": False, "why": f"a probe stream failed: {streams}"}
    rows_of: dict = {}      # (prompt, emitted) -> one logits row per token

    def rows(prompt, emitted):
        key = (tuple(prompt), tuple(emitted))
        if key not in rows_of and emitted:
            seq = np.asarray(list(prompt) + list(emitted), np.int32)
            rows_of[key] = np.asarray(forward(
                params, seq[:-1], backend.n_heads, len(emitted)))
        return rows_of.get(key, [])

    worst, n_tok = 0.0, 0
    for emitted_by in (probe["concurrent"], probe["solo"]):
        for prompt, emitted in zip(probe["prompts"], emitted_by):
            for row, tok in zip(rows(prompt, emitted), emitted):
                worst = max(worst, float(row.max() - row[tok]))
                n_tok += 1
    parted = []     # the reference's top-two gap wherever the twins part
    for prompt, c, s in zip(probe["prompts"], probe["concurrent"],
                            probe["solo"]):
        for j, (a, b) in enumerate(zip(c, s)):
            if a != b:
                top = np.sort(rows(prompt, c)[j])[-2:]
                parted.append(float(top[1] - top[0]))
                break
    lens_ok = all(len(e) == probe["max_tokens"] for e in streams)
    twins_ok = all(g <= MARGIN for g in parted)
    return {"ok": bool(worst <= MARGIN and lens_ok and twins_ok),
            "worst_margin_below_max": worst, "margin": MARGIN,
            "tokens_checked": n_tok,
            "concurrent_equals_solo": probe["concurrent"] == probe["solo"],
            "parted_at_reference_gaps": parted,
            "all_tokens_arrived": bool(lens_ok)}


# -- operations and bytes of a step ------------------------------------------

def _dims(cfg: dict):
    return (cfg["n_embd"], cfg["n_inner"], cfg["n_layer"],
            cfg["vocab_size"])


def prefill_step(cfg: dict, lanes: int, bucket: int, weight_bytes: int = 4):
    """One batched prefill: ``lanes`` prompts padded to ``bucket`` tokens;
    the head runs on each lane's last position only.  (flops, bytes)."""
    d, f, n_layers, v = _dims(cfg)
    t = lanes * bucket
    per_layer = (2 * t * (4 * d * d + 2 * d * f)
                 + 4 * lanes * (bucket * (bucket + 1) // 2) * d)
    flops = n_layers * per_layer + 2 * lanes * d * v
    w = n_layers * (4 * d * d + 2 * d * f) + d * v
    nbytes = (w * weight_bytes
              + t * d * weight_bytes           # gathered embedding rows
              + bucket * d * weight_bytes      # positions
              + n_layers * 2 * t * d * 4       # K and V rows written (f32)
              + t * 4)
    return float(flops), float(nbytes)


def decode_step(cfg: dict, lanes: int, context: float,
                weight_bytes: int = 4):
    """One decode wave: ``lanes`` streams advance one token, each reading
    ``context`` valid key/value positions.  (flops, bytes)."""
    d, f, n_layers, v = _dims(cfg)
    per_layer = 2 * lanes * (4 * d * d + 2 * d * f) + 4 * lanes * context * d
    flops = n_layers * per_layer + 2 * lanes * d * v
    w = n_layers * (4 * d * d + 2 * d * f) + d * v
    nbytes = (w * weight_bytes
              + n_layers * 2 * lanes * context * d * 4   # K, V read (f32)
              + n_layers * 2 * lanes * d * 4             # K, V written
              + lanes * d * weight_bytes)
    return float(flops), float(nbytes)


def step_mix(ctx):
    """Prefill cells (``step_module`` jit_prefill): one step per prompt
    bucket the window's prompts fell into, at the configured lanes.  Decode
    cells: one step per wave bucket the window ran, at the mean context."""
    import reduce

    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if traffic["step_module"] == "jit_prefill":
        if not reduce.prefill_lane_fill(ctx):
            return None
        lanes = int(cfg["serve"]["prefill_lanes"])
        cap = int(traffic["max_model_len"])
        r = ctx["req"]
        lens = r["prompt_len"][r["in_window"] & r["ok"]]
        buckets = np.asarray([roofline.next_bucket(int(n), cap)
                              for n in lens])
        return [(float((buckets == b).sum()),
                 prefill_step(cfg, lanes, int(b)))
                for b in np.unique(buckets)]
    w, c = reduce.waves_delta(ctx), reduce.mean_context(ctx)
    if not w or c is None:
        return None
    return [(float(n), decode_step(cfg, b, c)) for b, (n, _) in w.items()]
