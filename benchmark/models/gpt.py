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


def prefill_step(cfg: dict, positions: float, pairs: float, lanes: float,
                 programs: float, weight_bytes: int = 4):
    """``programs`` one-shot prefills at the prompts' own sizes:
    ``positions`` prompt tokens in ``lanes`` live lanes scoring ``pairs``
    causal (query, key) pairs a layer; the bucket's and the padded lanes'
    positions are not counted.  Every weight read once a program, the head on
    each live lane's last position only.  (flops, bytes)."""
    d, f, n_layers, v = _dims(cfg)
    flops = (n_layers * (2 * positions * (4 * d * d + 2 * d * f)
                         + 4 * pairs * d) + 2 * lanes * d * v)
    w = n_layers * (4 * d * d + 2 * d * f) + d * v
    nbytes = (programs * w * weight_bytes
              + positions * d * weight_bytes        # gathered embedding rows
              + n_layers * 2 * positions * d * 4    # K and V rows written
              + positions * 4)
    return float(flops), float(nbytes)


def prefill_work(ctx):
    """The window's one-shot prefills, (flops, bytes) of all of them: the
    programs and their live lanes by the program's counters (the count of
    gen.prefill_dispatch, ``prefill_lanes_live``), a live lane's positions
    and causal pairs by the harness's table of prompt lengths (the program
    counts no position of a one-shot prefill).  None without the table."""
    import reduce

    n, prompts = reduce.prefill_counts(ctx), reduce.window_prompts(ctx)
    if n is None or prompts is None or not n["programs"]:
        return None
    return prefill_step(
        ctx["cfg"], n["lanes"] * float(prompts.mean()),
        n["lanes"] * float(reduce.causal_pairs(prompts).mean()), n["lanes"],
        n["programs"])


def step_mix(ctx):
    """The window's decode waves as one mean step, at the mean **live** lanes
    a wave held and the mean context a live lane read (the program's counters
    ``fetched_lanes_live``, ``fetched_positions_valid`` over
    ``fetched_waves``); a bucket's padded lanes read the dummy row and are
    not counted."""
    import progspans

    w = progspans.window(ctx)
    c = w["counters"] if w else {}
    waves, lanes = c.get("fetched_waves"), c.get("fetched_lanes_live")
    if not waves or not lanes:
        return None
    return [(float(waves), decode_step(
        ctx["cfg"], lanes / waves, c["fetched_positions_valid"] / lanes))]
