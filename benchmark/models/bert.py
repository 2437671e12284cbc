"""Family ``bert``: a BERT encoder served over ``infer`` (request/response).

No cell of the manifest uses it today: a 110M-parameter encoder at sequence
128 fills 2% of a v5e and no honest deployment of it reaches the driver's
memory floor (PERF.md, Open questions).  The module stays because it was
proven on the chip in PR 23 and a cell of this family then needs data files
only.

Tolerance: server logits and pooled output within ``ATOL`` = 5e-2 of the
float32 reference.  The server computes in bfloat16 (8 mantissa bits, one
rounding up to 2^-9 relative) through 12 layers; outputs are O(1) (a tanh
pooler and a 768-wide head); the worst seen on the chip was 0.024 pooled and
0.005 logits (PR 23).  A dropped layer or a wrong mask moves the outputs by
1e-1 and more.
"""

from __future__ import annotations

import math

import numpy as np

import family

ATOL = 5e-2


def encode_request(cfg, model, rows, prompt_len, output_len, rng) -> bytes:
    """KServe v2 HTTP binary request for a [rows, seq] id/mask pair, the
    first ``prompt_len`` positions of each row valid; outputs come back
    binary."""
    wire = cfg["wire"]
    seq = int(cfg[wire["seq_len"]])
    mask = np.zeros((rows, seq), "<i4")
    mask[:, :int(prompt_len)] = 1
    ids = np.ascontiguousarray(
        rng.integers(0, int(cfg[wire["vocab"]]), (rows, seq)) * mask, "<i4")
    head = {"inputs": [
        {"name": wire["input_ids"], "shape": list(ids.shape),
         "datatype": "INT32", "parameters": {"binary_data_size": ids.nbytes}},
        {"name": wire["attention_mask"], "shape": list(mask.shape),
         "datatype": "INT32",
         "parameters": {"binary_data_size": mask.nbytes}}],
        "parameters": {"binary_data_output": True}}
    return family.http_request(f"/v2/models/{model}/infer", head,
                               ids.tobytes() + mask.tobytes())


def probe(server, cfg, traffic, seed) -> dict:
    """Four rows with full, half, quarter and 9 valid positions."""
    rng = np.random.default_rng([int(seed), 99])
    wire = cfg["wire"]
    seq = int(cfg[wire["seq_len"]])
    ids = rng.integers(0, int(cfg[wire["vocab"]]), (4, seq))
    mask = np.ones((4, seq), np.int64)
    for i, n in enumerate((seq, seq // 2, seq // 4, 9)):
        mask[i, n:] = 0
    body = {"inputs": [
        {"name": wire["input_ids"], "shape": [4, seq],
         "datatype": "INT32", "data": (ids * mask).ravel().tolist()},
        {"name": wire["attention_mask"], "shape": [4, seq],
         "datatype": "INT32", "data": mask.ravel().tolist()}]}
    resp = server.request(
        "POST", f"/v2/models/{cfg['serve']['model_name']}/infer", body)
    outs = {o["name"]: np.asarray(o["data"], np.float32).reshape(
        o["shape"]).tolist() for o in resp["outputs"]}
    return {"ids": (ids * mask).tolist(), "mask": mask.tolist(),
            "logits": outs["logits"], "pooled_output": outs["pooled_output"]}


def forward(p, ids, mask, n_heads, eps=1e-12):
    """BERT encoder as published (post-LayerNorm blocks, absolute
    positions, tanh pooler over [CLS]) plus a linear classifier; no
    token-type embedding (a configuration lists it under departures).  ``p``
    holds float32 arrays: fused ``wqkv`` [h, 3h] laid out (3, heads, d)."""
    import jax
    import jax.numpy as jnp

    b, s = ids.shape
    h = p["tok_embed"].shape[1]
    d = h // n_heads
    bias = (mask.astype(jnp.float32) - 1.0) * 1e9
    x = p["tok_embed"][ids] + p["pos_embed"][None, :s]
    x = family.layer_norm(x, p["embed_ln"]["scale"], p["embed_ln"]["bias"],
                          eps)
    for lp in p["layers"]:
        qkv = (x @ lp["wqkv"]["w"] + lp["wqkv"]["b"]).reshape(
            b, s, 3, n_heads, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        sc = sc + bias[:, None, None, :]
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        att = ctx.reshape(b, s, h) @ lp["wo"]["w"] + lp["wo"]["b"]
        x = family.layer_norm(x + att, lp["ln1"]["scale"], lp["ln1"]["bias"],
                              eps)
        y = family.gelu_tanh(x @ lp["w1"]["w"] + lp["w1"]["b"])
        y = y @ lp["w2"]["w"] + lp["w2"]["b"]
        x = family.layer_norm(x + y, lp["ln2"]["scale"], lp["ln2"]["bias"],
                              eps)
    pooled = jnp.tanh(x[:, 0] @ p["pooler"]["w"] + p["pooler"]["b"])
    logits = pooled @ p["classifier"]["w"] + p["classifier"]["b"]
    return pooled, logits


def check(params, probe, backend) -> dict:
    ids = np.asarray(probe["ids"], np.int32)
    mask = np.asarray(probe["mask"], np.int32)
    pooled, logits = forward(params, ids, mask, backend.n_heads)
    d_log = float(np.max(np.abs(np.asarray(logits)
                                - np.asarray(probe["logits"], np.float32))))
    d_pool = float(np.max(np.abs(np.asarray(pooled) - np.asarray(
        probe["pooled_output"], np.float32))))
    return {"ok": bool(d_log <= ATOL and d_pool <= ATOL),
            "max_abs_diff_logits": d_log, "max_abs_diff_pooled": d_pool,
            "atol": ATOL}


def encoder_step(cfg: dict, batch: int, weight_bytes: int = 2):
    """One encoder step on ``batch`` rows of the served sequence length.
    (flops, bytes)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_layers, s = cfg["num_hidden_layers"], cfg["max_position_embeddings"]
    t = batch * s
    per_layer = 2 * t * (4 * h * h + 2 * h * f) + 4 * batch * s * s * h
    flops = n_layers * per_layer + 2 * batch * h * h + 2 * batch * h * 2
    w = n_layers * (4 * h * h + 2 * h * f) + h * h + 2 * h
    nbytes = (w * weight_bytes                 # every layer's weights once
              + t * h * weight_bytes           # gathered embedding rows
              + s * h * weight_bytes           # position table
              + 2 * t * 4                      # ids and mask in
              + batch * (h + 2) * 4)           # pooled output and logits
    return float(flops), float(nbytes)


def step_mix(ctx):
    """One step per batch bucket the batcher ran in the window."""
    import reduce
    import roofline

    d = reduce.stats_delta(ctx)
    if not d or not d["batches"]:
        return None
    cap = int(ctx["cfg"]["serve"]["kwargs"]["max_batch_size"])
    return [(n, encoder_step(ctx["cfg"], roofline.next_bucket(k, cap)))
            for k, n in d["batches"].items()]
