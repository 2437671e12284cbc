"""Family ``kimi_linear``: a hybrid decoder, three KDA (gated delta rule)
layers to one latent-attention layer without positions, sparse experts; one
chip's share of an expert-parallel group, served over ``generate_stream``.

The forward pass below is written from the equations of ISSUE 34 / PERF.md
section 4 and the public config's keys.  Nothing here is used by the server
and nothing of ``client_tpu/ops`` is used here; the only thing taken from the
program is the weights (data: ``reference.py`` asks the backend's seeded,
bfloat16-rounded values for float32, so the reference holds exactly what the
chip holds).

With x ``[n, d]``, RMSNorm ``x / rms(x) * g`` and SwiGLU ``W_d(silu(W_g h) *
W_u h)``:

- *Block*: ``x += Mixer(N1(x))``; ``x += FFN(N2(x))``; final RMSNorm; logits
  ``x W_head``.  ``FFN`` is a SwiGLU in the leading dense layer, the expert
  layer after.
- *KDA layer*, per head h, ``d_k = d_v``: ``[q | k | v] = silu(conv(x
  W_qkv))`` (causal, depthwise, ``taps`` positions, zeros before position 0);
  ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``; ``g = -exp(A_log[h]) *
  softplus(x W_fa W_fb + dt_bias)``; ``beta = sigmoid(x W_b)``.  The state
  ``S [d_k, d_v]`` is zero before position 0 and walks **token by token**
  under one ``lax.scan``: ``S' = Diag(exp(g_t)) S``; ``u = beta_t (v_t - S'^T
  k_t)``; ``S = S' + k_t u^T``; ``o_t = S^T q_t``.  Output ``W_o
  [RMSNorm_head(o_t) * sigmoid(x W_ga W_gb)]``.  Nothing is chunked, nothing
  is cached and the projection is not rounded before the convolution.
- *Latent layer*: per head ``q_nope = x W_qn``, ``q_r = x W_qr`` (``W_q`` by
  its columns); ``[c_kv | k_r] = x W_kva``; ``c = RMSNorm(c_kv)``; ``k_nope = c
  W_kb[h]^T``, ``v = c W_vb[h]``; one causal softmax over ``(q_nope . k_nope +
  q_r . k_r) / sqrt(nope + rope)``; **no position enters**.  Nothing absorbed.
- *Expert layer*: ``s = sigmoid(x W_g)`` over all ``n_experts``; the ``top_k``
  largest of ``s + b`` (``b`` the gate's selection bias); weights ``s_i / sum
  s_i * routed_scaling_factor``; ``y = shared(x) + sum_i w_i E_i(x)`` **over
  the chosen experts that this share holds** (``first .. first +
  len(egu)``): what the absent experts would add is left out, as in the
  program (the departure the configuration file states).

Tolerance (stated here, with the reasons).  The probe's streams (prompts of
1, 2 and 5 prefill pieces and ``probe_max_tokens`` waves behind each, sent
together and then each alone, so every judged token went through **both
caches**) ask the server for their **record**: for every position the timed
programs consumed, which held experts each expert layer chose, and ``1 +
samples`` logits of the row each token was chosen from.  The reference is
teacher-forced on a stream's own tokens and **follows its served routing**
(``follow``).  Top-k routing is discontinuous: where the ``top_k``-th and the
next selection scores lie nearer than the rounding of the router's input, the
program may choose another expert than the reference, the row's output moves
by a whole expert's term (0.13 rms), and **a recurrent state carries that
term to every later row of its stream**: on the chip one position in sixteen
flips in some layer, and a reference that chose for itself read logits 0.03-
0.05 rms off the served ones, ten times what the precision puts there
(PERF.md section 6, PR 34).  Followed, what is left is the precision, and
four limits judge it, each between its two readings at the published widths
(my chip runs, PR 34; PERF.md section 6 has them run by run):

- ``TIE`` = 0.004 score units: an expert that the served choice and the
  reference's own disagree about lies that near the edge between the
  ``top_k``-th and the next selection score (``s + b``), at **every**
  position, the prompt's included, so following cannot hide a wrong router.
  Served: at most 0.0015 over nine runs (one position in fifteen flips in
  some layer); e4m3 operands 0.024-0.033.
- ``LOGIT_RMS_ALONE`` = 0.0038 and ``LOGIT_RMS_TOGETHER`` = 0.0075: the rms of
  served logit less reference logit over the record's logits of every judged
  row, taken apart over the streams sent alone (waves of one lane) and those
  sent together (waves of four), because **the two round differently**: the
  pieces of a prompt are bit for bit the same either way, but from the first
  wave on the twins' logits part by 0.004-0.005 rms, and against the
  reference a stream alone reads 0.0030-0.0036 and the same stream with three
  others 0.0056-0.0066, seed after seed (compiled for the chip, a wave of
  one lane holds no dot at all: XLA makes every product a multiply and sum
  on the vector unit and drops the roundings to bfloat16 of the ``[1, 128]``,
  ``[1, 4096]`` and ``[1, 12288]`` operands, which the four-lane program
  keeps).  Served (bfloat16 operands into float32 sums, bfloat16 rows and
  convolution tail, a float32 state and decay), a run: alone 0.00321-0.00340,
  together 0.00578-0.00627.  **A bfloat16 state: alone 0.00440 (fails),
  together 0.00663** (it adds 0.0029 rms to either, which shows beside 0.0033
  and not beside 0.0060).  e4m3 operands: 0.0214 and 0.0848 (fails both).  A
  bfloat16 decay (``g`` and ``exp(g)`` both rounded) reads what the served
  program reads, 0.00331 and 0.00606 through the harness and 0.00328 beside
  0.00326 on one seed: it moves a logit by under 0.0005 rms, less than the
  served readings' own spread, so no limit on outputs can tell it from the
  served program and none here pretends to.
- ``LOGIT_MAX`` = 0.06: the worst single logit.  Served 0.020-0.024 (4608
  logits, four standard deviations of those sent together); e4m3 0.14-0.33; a
  state or a tail not cleared, a misplaced row, a padded position that moved
  the state: tenths to units.
- ``MARGIN`` = 0.04: each emitted token's reference logit under its row's
  best.  Served at most 0.018 (by the logits' error, a run in millions
  passes 0.04); e4m3 0.07-0.31.

Streams sent together and alone are each judged whole, against the forward
pass that follows their own record, so where twins part both tokens are
within ``MARGIN`` of their own row's best; a cross-stream mix-up moves logits
by units.  A record that does not hold a row for every position fails.
"""

from __future__ import annotations

import http.client
import json
import math
import threading

import numpy as np

import family

MARGIN = 0.04
LOGIT_RMS_ALONE = 0.0038
LOGIT_RMS_TOGETHER = 0.0075
LOGIT_MAX = 0.06
TIE = 0.004

_pangu = family.load("pangu_moe")
_evabyte = family.load("evabyte")
_gpt = family.load("gpt")
encode_request = _gpt.encode_request
take_every_core = _evabyte.take_every_core
rms_norm, swiglu = _pangu.rms_norm, _pangu.swiglu
kernel_share = _pangu.kernel_share


# -- wire -----------------------------------------------------------------------

def probe(server, cfg, traffic, seed) -> dict:
    """``gpt``'s probe (``probe_prompt_lens`` prompts streamed together, then
    one at a time), each stream asking for its **record** (request parameter
    ``record``): the final event brings ``RECORD [positions, expert layers +
    1 + logit samples]``, what the timed programs leave behind their tokens for
    every position they consumed (models/latent_moe.py)."""
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
                          "parameters": {"max_tokens": max_tokens, "seed": 0,
                                         "record": True}}))
            text = c.getresponse().read().decode()
        finally:
            c.close()
        toks, record = [], []
        for ev in text.split("\n\n"):
            if not ev.startswith("data: "):
                continue
            d = json.loads(ev[6:])
            if "error" in d:
                out[i] = ({"error": d["error"]}, [])
                return
            for o in d["outputs"]:
                if o["name"] == "TOKEN":
                    toks.append(o["data"][0])
                elif o["name"] == "RECORD":
                    record = np.reshape(o["data"], o["shape"]).tolist()
        out[i] = (toks, record)

    together: dict = {}
    ts = [threading.Thread(target=stream, args=(p, together, i))
          for i, p in enumerate(prompts)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=400)
    solo: dict = {}
    for i, p in enumerate(prompts):
        stream(p, solo, i)
    got = [[d.get(i, ([], [])) for i in range(len(prompts))]
           for d in (together, solo)]
    return {"prompts": prompts, "max_tokens": max_tokens,
            "concurrent": [t for t, _ in got[0]],
            "concurrent_record": [r for _, r in got[0]],
            "solo": [t for t, _ in got[1]],
            "solo_record": [r for _, r in got[1]]}


# -- the plain reference --------------------------------------------------------

def l2norm(x):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def kda_layer(lp, x, *, eps):
    """KDA, position by position: x ``[n, d]`` -> ``[n, d]``."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    h = rms_norm(x, lp["ln1"], eps)
    taps = lp["conv"].shape[0]
    heads = lp["a_log"].shape[0]
    ext = jnp.concatenate([jnp.zeros((taps - 1, lp["wqkv"].shape[1])),
                           h @ lp["wqkv"]])
    mixed = jax.nn.silu(sum(lp["conv"][j] * ext[j:j + n]
                            for j in range(taps)))
    q, k, v = (part.reshape(n, heads, -1)
               for part in jnp.split(mixed, 3, axis=-1))
    d_k = q.shape[-1]
    q, k = l2norm(q) / math.sqrt(d_k), l2norm(k)
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        (h @ lp["wfa"]) @ lp["wfb"] + lp["dt_bias"]).reshape(n, heads, d_k)
    beta = jax.nn.sigmoid(h @ lp["wb"])

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d_k, v.shape[-1])),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid((h @ lp["wga"]) @ lp["wgb"])
    return (rms_norm(o, lp["onorm"], eps).reshape(n, -1) * gate) @ lp["wo"]


def latent_layer(lp, x, *, n_heads, eps, q_block=256):
    """Latent attention without positions, nothing absorbed: x ``[n, d]`` ->
    ``[n, d]``."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    h = rms_norm(x, lp["ln1"], eps)
    q_nope = (h @ lp["wqn"]).reshape(n, n_heads, -1)
    q_r = (h @ lp["wqr"]).reshape(n, n_heads, -1)
    rank = lp["kvln"].shape[0]
    kv = h @ lp["wkva"]
    c, k_r = rms_norm(kv[:, :rank], lp["kvln"], eps), kv[:, rank:]
    k_nope = jnp.einsum("sr,hnr->shn", c, lp["wkb"])
    v = jnp.einsum("sr,hrv->shv", c, lp["wvb"])
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_r.shape[-1])
    pos = jnp.arange(n)
    out = []
    for lo in range(0, n, q_block):
        s = (jnp.einsum("qhd,khd->hqk", q_nope[lo:lo + q_block], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_r[lo:lo + q_block], k_r))
        seen = pos[None, :] <= pos[lo:lo + q_block, None]
        s = jnp.where(seen[None], s * scale, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out).reshape(n, -1) @ lp["wo"]


def expert_layer(lp, h, *, top_k, scale, first, follow=None):
    """The share's expert layer for normed tokens h ``[n, d]``.  ``follow``
    ``[n]`` (int32 words of the served record, bit e = held expert ``first +
    e`` was chosen): the held experts take part as the word says, and the
    absent ones fill the other places by their own scores (which absent
    expert is chosen changes nothing here but ``sum s_i``, by less than the
    scores' gap).  Returns (y, chosen ``[n, top_k]``, flips ``[n]``: how far
    from the edge between the top_k-th and the next selection score the
    farthest expert lies that the followed choice and the reference's own
    disagree about; 0 where they agree)."""
    import jax
    import jax.numpy as jnp

    s = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    pick = s + np.asarray(lp["router_bias"])
    order = np.argsort(-pick, axis=-1, kind="stable")
    own = order[:, :top_k]
    held = lp["egu"].shape[0]
    flips = np.zeros(len(pick))
    chosen = own
    if follow is not None:
        want = (np.asarray(follow, np.int64)[:, None] >> np.arange(held)) & 1
        forced = pick.copy()
        forced[:, first:first + held] += np.where(want, 4.0, -4.0)
        chosen = np.argsort(-forced, axis=-1, kind="stable")[:, :top_k]
        edge = np.take_along_axis(
            pick, order[:, top_k - 1:top_k + 1], axis=-1).mean(-1)
        ours = np.zeros(pick.shape, bool)
        np.put_along_axis(ours, own, True, axis=-1)
        theirs = np.zeros(pick.shape, bool)
        np.put_along_axis(theirs, chosen, True, axis=-1)
        flips = np.where(ours != theirs, np.abs(pick - edge[:, None]),
                         0.0).max(-1)
    s = np.take_along_axis(s, chosen, axis=-1)
    weights = s / s.sum(-1, keepdims=True) * scale
    y = np.array(swiglu(h, lp["sgu"], lp["sd"]))
    for e in range(held):
        tok, slot = np.nonzero(chosen == first + e)
        if tok.size:
            out = swiglu(jnp.asarray(h)[tok], jnp.asarray(lp["egu"][e]),
                         jnp.asarray(lp["ed"][e]))
            np.add.at(y, tok, np.asarray(out) * weights[tok, slot][:, None])
    return jnp.asarray(y), chosen, flips


def forward(p, ids, last, *, n_heads, top_k, scale, first, eps, follow=None,
            q_block=256):
    """Full context, no cache, no pieces.  ``ids`` [n] -> (logits of the
    ``last`` positions ``[last, vocab]``, chosen experts ``[expert layers, n,
    top_k]``, flips ``[n]``: the largest over the expert layers).  ``follow``
    ``[n, expert layers]``: the served record's words.  A layer is a KDA
    layer where it has ``wqkv``, a latent layer where not."""
    import jax.numpy as jnp

    n = ids.shape[0]
    x = jnp.asarray(p["embed"])[ids]
    chosen, flips, moe = [], np.zeros(n), 0
    for lp in p["layers"]:
        lp = {k: (v if k in ("egu", "ed") else jnp.asarray(v))
              for k, v in lp.items()}
        x = x + (kda_layer(lp, x, eps=eps) if "wqkv" in lp else
                 latent_layer(lp, x, n_heads=n_heads, eps=eps,
                              q_block=q_block))
        h = rms_norm(x, lp["ln2"], eps)
        if "wgu" in lp:
            y = swiglu(h, lp["wgu"], lp["wd"])
        else:
            y, picked, flip = expert_layer(
                lp, h, top_k=top_k, scale=scale, first=first,
                follow=None if follow is None else follow[:, moe])
            chosen.append(picked)
            flips = np.maximum(flips, flip)
            moe += 1
        x = x + y
    logits = rms_norm(x[n - last:], jnp.asarray(p["lnf"]), eps) @ jnp.asarray(
        p["head"])
    return logits, np.stack(chosen), flips


def backend_forward(params, backend, ids, last, follow=None, q_block=256):
    """``forward`` at the sizes a backend object states."""
    return forward(params, ids, last, n_heads=backend.n_heads,
                   top_k=backend.top_k, scale=backend.routed_scale,
                   first=backend.first_expert, eps=backend.rms_eps,
                   follow=follow, q_block=q_block)


def record_columns(record, expert_layers):
    """A stream's served record ``[positions, stream_record]`` (int32,
    models/latent_moe.py) -> (the expert layers' words ``[positions, expert
    layers]``, the served logits ``[positions, 1 + samples]`` float32: the
    emitted token's, then those of ids ``0 .. samples - 1``)."""
    record = np.asarray(record, np.int32)
    return record[:, :expert_layers], np.ascontiguousarray(
        record[:, expert_layers:]).view(np.float32)


def judge(probe, rows_fn, expert_layers, margin=MARGIN,
          logit_rms_alone=LOGIT_RMS_ALONE,
          logit_rms_together=LOGIT_RMS_TOGETHER, logit_max=LOGIT_MAX,
          tie=TIE):
    """``rows_fn(prompt, emitted, words)`` gives the reference's logits row
    of each emitted token, teacher-forced on the stream's own tokens and
    following its served routing, and the flips ``[prompt + emitted - 1]`` of
    every position.  Every stream is judged whole, those sent together and
    their twins sent alone (a twin whose tokens and routing equal its
    sibling's shares its forward pass); the logits' rms error is taken over
    the streams sent together and over those sent alone apart, because the
    two run in waves of different buckets, which round differently."""
    streams = probe["concurrent"] + probe["solo"]
    if any(isinstance(s, dict) for s in streams):
        return {"ok": False, "why": f"a probe stream failed: {streams}"}
    below, flips, short = [], [], 0
    off = {"together": [], "alone": []}
    for prompt, pair in zip(probe["prompts"], zip(
            zip(probe["concurrent"], probe["concurrent_record"]),
            zip(probe["solo"], probe["solo_record"]))):
        seen = None
        for how, (toks, record) in zip(off, pair):
            if (len(toks) != probe["max_tokens"]
                    or np.shape(record)[0] != len(prompt) + len(toks) - 1):
                short += 1
                continue
            words, served = record_columns(record, expert_layers)
            key = (list(toks), words.tobytes())
            if seen is None or seen[0] != key:
                rows, flip = rows_fn(list(prompt), list(toks), words)
                rows = np.asarray(rows, np.float64)
                at = rows[np.arange(len(toks)), toks]
                want = np.concatenate(
                    [at[:, None], rows[:, :served.shape[1] - 1]], axis=1)
                seen = (key, (rows.max(-1) - at, want, np.asarray(flip)))
            under, want, flip = seen[1]
            below.extend(under)
            off[how].append((served[len(prompt) - 1:] - want).reshape(-1))
            flips.extend(flip)
    if short or not below:
        return {"ok": False, "streams_short": short,
                "why": "a stream's tokens or record did not arrive whole"}
    below, flips = np.asarray(below), np.asarray(flips)
    rms = {how: float(np.sqrt(np.mean(np.concatenate(errs) ** 2)))
           for how, errs in off.items()}
    worst = float(max(np.abs(e).max() for errs in off.values()
                      for e in errs))
    ok = (below.max() <= margin and rms["alone"] <= logit_rms_alone
          and rms["together"] <= logit_rms_together and worst <= logit_max
          and flips.max() <= tie)
    return {"ok": bool(ok), "streams_short": short,
            "worst_margin_below_max": float(below.max()), "margin": margin,
            "logit_rms_error_alone": rms["alone"],
            "logit_rms_alone": logit_rms_alone,
            "logit_rms_error_together": rms["together"],
            "logit_rms_together": logit_rms_together,
            "logit_rms_error_by_stream": {
                how: [float(np.sqrt(np.mean(e ** 2))) for e in errs]
                for how, errs in off.items()},
            "logit_worst_error": worst, "logit_max": logit_max,
            "worst_flip_from_the_edge": float(flips.max()), "tie": tie,
            "tokens_checked": int(below.size),
            "logits_compared": int(sum(e.size for errs in off.values()
                                       for e in errs)),
            "rows_off_the_best": int((below > 0).sum()),
            "positions_flipped": int((flips > 0).sum()),
            "positions_followed": int(flips.size),
            "concurrent_equals_solo": probe["concurrent"] == probe["solo"]}


def check(params, probe, backend) -> dict:
    take_every_core()

    def rows_fn(prompt, emitted, words):
        seq = np.asarray(prompt + emitted, np.int32)
        logits, _, flips = backend_forward(params, backend, seq[:-1],
                                           len(emitted), follow=words)
        return logits, flips

    layers = sum("router" in lp for lp in params["layers"])
    return judge(probe, rows_fn, layers)


# -- operations and bytes of a step ------------------------------------------

def _dims(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    layers = cfg["num_hidden_layers"]
    n_kda = sum(1 for i in lin["kda_layers"] if i <= layers)
    h, hk, dk = cfg["num_attention_heads"], lin["num_heads"], lin["head_dim"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d, kvr = cfg["hidden_size"], cfg["kv_lora_rank"]
    rank = dk                                   # the low-rank pairs (assumed)
    latent = (d * h * (nope + rp) + d * (kvr + rp)
              + kvr * h * (nope + cfg["v_head_dim"])
              + h * cfg["v_head_dim"] * d)
    kda = (4 * d * hk * dk + 2 * (d * rank + rank * hk * dk) + d * hk
           + 3 * hk * dk * lin["short_conv_kernel_size"])
    return {"d": d, "heads": h, "row": kvr + rp, "rank": kvr,
            "kda_heads": hk, "kda_dim": dk,
            "taps": lin["short_conv_kernel_size"],
            "latent": latent, "kda": kda, "n_kda": n_kda,
            "n_latent": layers - n_kda,
            "dense": 3 * d * cfg["intermediate_size"],
            "shared": 3 * d * cfg["moe_intermediate_size"]
            * cfg["num_shared_experts"],
            "router": d * int(cfg["serve"]["kwargs"]["n_experts"]),
            "n_dense": cfg["first_k_dense_replace"],
            "n_moe": layers - cfg["first_k_dense_replace"],
            "held": cfg["num_experts"], "vocab": cfg["vocab_size"]}


def kda_update(cfg: dict, lanes: float):
    """One layer's ``kda_wave_update``: the live lanes' states (``heads x d_k
    x d_v`` float32) read once and written once; q, k, v, g, beta in and o
    out, float32; a state element is decayed, enters ``S'^T k``, takes ``k
    u^T`` and enters ``S^T q`` (7 operations).  (flops, bytes)."""
    m = _dims(cfg)
    state = m["kda_heads"] * m["kda_dim"] * m["kda_dim"]
    vectors = m["kda_heads"] * (5 * m["kda_dim"] + 1)
    return (float(7 * lanes * state),
            float(lanes * (2 * state + vectors) * 4))


def latent_attention(cfg: dict, lanes: float, context: float):
    """One layer's ``latent_wave_attention`` (``pangu_moe``'s count: live
    rows of ``kv_lora_rank + qk_rope_head_dim`` values read once for all
    heads, bfloat16).  (flops, bytes)."""
    m = _dims(cfg)
    flops = 2 * lanes * context * m["heads"] * (m["row"] + m["rank"])
    nbytes = (lanes * (context + 1) * m["row"] * 2
              + lanes * m["heads"] * (m["row"] + m["rank"]) * 4)
    return float(flops), float(nbytes)


def expert_ffn(cfg: dict, pairs: float, touched: float, part: str = "both"):
    """One expert layer's grouped matmuls (``pangu_moe``'s count: the
    ``touched`` experts' matrices read once, bfloat16).  (flops, bytes)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    up = (2 * pairs * 2 * d * f,
          touched * 2 * d * f * 2 + pairs * (d * 2 + 2 * f * 4))
    down = (2 * pairs * f * d,
            touched * f * d * 2 + pairs * (f * 2 + d * 4))
    flops, nbytes = {"up": up, "down": down,
                     "both": (up[0] + down[0], up[1] + down[1])}[part]
    return float(flops), float(nbytes)


def cache_bytes(cfg: dict, lanes: float, positions: float):
    """What a wave's two caches move: (the recurrent states of ``lanes`` live
    lanes, read and written in every KDA layer; the latent rows of
    ``positions`` live positions, read in every latent layer), bytes."""
    m = _dims(cfg)
    state = m["kda_heads"] * m["kda_dim"] * m["kda_dim"] * 4
    return (float(lanes * m["n_kda"] * 2 * state),
            float(positions * m["n_latent"] * m["row"] * 2))


def decode_step(cfg: dict, lanes: float, context: float, pairs: float,
                touched: float):
    """One decode wave: ``lanes`` streams advance one token; each reads
    ``context`` latent rows a latent layer and reads and writes its state
    and convolution tail a KDA layer; ``pairs`` (token, expert) pairs and
    ``touched`` experts' matrices an expert layer (means a layer).  Weights
    are bfloat16 but the float32 router; what one operation hands the next
    is not counted.  (flops, bytes)."""
    m = _dims(cfg)
    a_f, _ = latent_attention(cfg, lanes, context)
    a_b = lanes * (context + 1) * m["row"] * 2
    k_f, k_b = kda_update(cfg, lanes)
    tail = lanes * 2 * (m["taps"] - 1) * 3 * m["kda_heads"] * m["kda_dim"] * 2
    e_f, e_b = expert_ffn(cfg, pairs, touched)
    flops = (m["n_latent"] * (2 * lanes * m["latent"] + a_f)
             + m["n_kda"] * (2 * lanes * m["kda"] + k_f)
             + m["n_dense"] * 2 * lanes * m["dense"]
             + m["n_moe"] * (2 * lanes * (m["shared"] + m["router"]) + e_f)
             + 2 * lanes * m["d"] * m["vocab"])
    nbytes = (m["n_latent"] * (m["latent"] * 2 + a_b)
              + m["n_kda"] * (m["kda"] * 2 + k_b + tail)
              + m["n_dense"] * m["dense"] * 2
              + m["n_moe"] * (m["shared"] * 2 + m["router"] * 4 + e_b)
              + m["d"] * m["vocab"] * 2 + lanes * m["d"] * 2)
    return float(flops), float(nbytes)


def piece_step(cfg: dict, positions: float, pairs_window: float,
               pairs_global: float, programs: float, heads: float = 0.0):
    """``programs`` piece programs that consumed ``positions`` valid prompt
    positions and scored ``pairs_window + pairs_global`` (query, key) pairs
    (summed over the latent layers), ``heads`` of them with a head
    (``prefill_heads``): ``cohere_moe``'s rules.  Useful work only: two
    operations a weight and valid position for the KDA layers' projections
    (**the chunked scan over the state is left out**: a floor), the latent
    layers' projections, the dense layer, the router, the shared expert and
    the ``8 / 256 x 32`` held experts a position chooses; a pair costs a
    head its score over ``qk_nope + qk_rope`` features and its value over
    ``v_head_dim``; the head's product for one row a program that ran
    it.  Every held weight read once a program, one lane or two (the touched
    share taken as 1), the head's where it ran; states and cache rows are
    left out of the bytes.  (flops, bytes)."""
    m = _dims(cfg)
    pairs = pairs_window + pairs_global     # no window layers here
    chosen_here = (cfg["num_experts_per_token"]
                   / int(cfg["serve"]["kwargs"]["n_experts"]) * m["held"])
    expert = 3 * m["d"] * cfg["moe_intermediate_size"]
    per_position = (m["n_latent"] * m["latent"] + m["n_kda"] * m["kda"]
                    + m["n_dense"] * m["dense"]
                    + m["n_moe"] * (m["shared"] + m["router"]
                                    + chosen_here * expert))
    a_pair = m["heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                           + cfg["v_head_dim"])
    flops = (2 * positions * per_position + 2 * pairs * a_pair
             + 2 * heads * m["d"] * m["vocab"])
    nbytes = (programs * (
        (m["n_latent"] * m["latent"] + m["n_kda"] * m["kda"]
         + m["n_dense"] * m["dense"]) * 2
        + m["n_moe"] * ((m["shared"] + m["held"] * expert) * 2
                        + m["router"] * 4))
        + heads * m["d"] * m["vocab"] * 2)
    return float(flops), float(nbytes)


def prefill_work(ctx):
    """The window's piece programs by its counters, (flops, bytes) of all of
    them (``piece_step`` through ``reduce.pieces_work``), or None.  The program
    counts no attention pairs for this backend: the harness's table of
    prompts gives them, the triangle in a latent layer."""
    import reduce

    m = _dims(ctx["cfg"])
    return reduce.pieces_work(ctx, piece_step, n_global=m["n_latent"])


def wave_means(ctx):
    """Means over the window's decode waves, from the program's counters:
    (live lanes a wave, context rows a live lane, pairs held here an expert
    layer, held experts touched an expert layer, waves), or None."""
    import progspans

    w = progspans.window(ctx)
    if w is None:
        return None
    c = w["counters"]
    waves, lanes = c.get("fetched_waves", 0), c.get("fetched_lanes_live", 0)
    if not waves or not lanes or "expert_pairs_local" not in c:
        return None
    n_moe = _dims(ctx["cfg"])["n_moe"]
    return (lanes / waves, c["fetched_positions_valid"] / lanes,
            c["expert_pairs_local"] / waves / n_moe,
            c["experts_touched"] / waves / n_moe, waves)


def step_mix(ctx):
    """Decode cells: the window's waves as one mean step."""
    m = wave_means(ctx)
    if m is None:
        return None
    return [(float(m[4]), decode_step(ctx["cfg"], *m[:4]))]


def wave_rows(cfg: dict) -> int:
    """Rows of the sorted layout of a full wave's grouped matmuls (the
    program's ``capacity_rows`` at its wave tile)."""
    lanes = int(cfg["serve"]["kwargs"]["max_streams"])
    tile = int(cfg["serve"]["expert_tile_rows"])
    held = int(cfg["num_experts"])
    worst = lanes * min(int(cfg["num_experts_per_token"]), held) \
        + held * (tile - 1)
    return -(-worst // tile) * tile
