"""Family ``pangu_moe``: a sparse-expert decoder with multi-head latent
attention, one chip's share of an expert-parallel group, served over
``generate_stream``.

The forward pass below is written from the equations of ISSUE 32 / PERF.md
section 4 and the public config's keys.  Nothing here is used by the server;
the only thing taken from the program is the weights (data: ``reference.py``
asks the backend's seeded, bfloat16-rounded values for float32, so the
reference holds exactly what the chip holds).

With x ``[n, d]``, RMSNorm ``x / rms(x) * g``, rotary positions (rotate-half)
and SwiGLU ``W_d(silu(W_g h) * W_u h)``:

- *Block*: ``x += N2(MLA(N1(x)))``; ``x += N4(FFN(N3(x)))``; final RMSNorm;
  logits ``x W_head``.  ``FFN`` is a SwiGLU in a leading dense layer, the
  expert layer after.
- *MLA*: ``c_q = RMSNorm(x W_qa)``; per head ``q_nope = c_q W_qn``, ``q_rope =
  rope(c_q W_qr)`` (``W_qb`` by its columns); ``[c_kv | k_r] = x W_kva``;
  ``c = RMSNorm(c_kv)``; ``k_r = rope(k_r)``, shared by every head; per head
  ``k_nope = c W_kb[h]^T``, ``v = c W_vb[h]``; one causal softmax over
  ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``; ``o = concat_h(p
  v) W_o``.  Nothing is absorbed and there is no cache.
- *Expert layer*: ``s = sigmoid(x W_g)`` over all ``n_experts``; the ``top_k``
  largest; weights ``s_i / sum s_i * routed_scaling_factor``; ``y = shared(x)
  + sum_i w_i E_i(x)`` **over the chosen experts that this share holds**
  (``first .. first + len(egu)``): what the absent experts would add is left
  out, as in the program, and the partial result goes on (the departure the
  configuration file states).  ``follow`` replaces the layer's own choice of
  experts by a given one (the tests compare logits with the routing forced
  equal and free).

Tolerance (stated here, with the reasons).  As ``evabyte.py``: teacher-forced
on the server's own emitted tokens, the probe's streams sent together and the
same streams alone; each emitted token's reference logit must be within a
margin of its row's maximum, and twins may part only where the reference's
best two logits are that near.  **New here: top-k routing is discontinuous.**
Where a token's ``top_k``-th and next router logits lie nearer than the served
precision resolves, program and reference may choose different experts, and if
either is held here the layer's output moves by a whole expert's term
(``w E(x)``, a third of the layer's pre-norm output at these widths), which no
limit on rounding covers.  The reference therefore reports, for every judged
row, the smallest gap between the ``top_k``-th and the next router logit over
the expert layers **where one of the two is held here** (``inf`` where neither
is).  A row whose gap is under ``TIE`` is a *routing tie* and is judged by
``MARGIN_TIE``; every other row by ``MARGIN``.

The readings the limits lie between, at the published widths (PERF.md section
6, PR 32; logits are ~N(0,1) over 19200 ids, maximum near 4.05, the best two
0.16 apart in the median):

- ``MARGIN`` = 0.1.  The served precision (bfloat16 operands into float32
  sums, a bfloat16 cache) moves a logit by 0.016 rms; an emitted token's logit
  was at most 0.024 below the reference's best on the chip (0.007 emulated on
  the CPU over 96 positions; 0.028 at rows that only *attend* to a flipped
  row): a quarter of the limit.  The nearest precision below, 8-bit floats
  (e4m3) for the same operands and cache, moves a logit by 0.23 rms and puts
  an emitted token 0.58 below the best on rows whose routing agrees and 1.03
  on the worst row, 43 of 96 tokens changed: six to ten times the limit.
- ``TIE`` = 0.03 router-logit units.  bfloat16 activations move the
  difference of two router logits by about 0.005 (7% of token-layers flip
  their ``top_k``-th expert, an eighth of them a held one, all at gaps under
  0.02): six of its standard deviations.  About a quarter of the rows are
  ties by it.
- ``MARGIN_TIE`` = 0.5.  With the held expert at the edge *forced* to flip in
  the float32 reference (every row whose gap is under 0.02, then 0.05), the
  row's logits move by 0.13 rms (0.94 at most) and the flipped model's token
  lies 0.19, then 0.36 below the reference's best at the worst row; e4m3 reads
  0.65 at tie rows (and has failed by ``MARGIN`` long before).

A dropped expert term, a wrong group, a stale or misplaced cache row or a
missed rotary position moves logits by tenths to units on every row and fails.
"""

from __future__ import annotations

import math

import numpy as np

import family

# An emitted token's reference logit below its row's best: the limit for rows
# whose routing is not in doubt, and for rows where it is (one held expert's
# term flipped).  TIE is in router-logit units.
MARGIN = 0.1
MARGIN_TIE = 0.5
TIE = 0.03

_evabyte = family.load("evabyte")
_gpt = family.load("gpt")
encode_request = _gpt.encode_request
probe = _gpt.probe
rope = _evabyte.rope
take_every_core = _evabyte.take_every_core


# -- the plain reference --------------------------------------------------------

def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def swiglu(h, wgu, wd):
    import jax

    f = wgu.shape[-1] // 2
    return (jax.nn.silu(h @ wgu[:, :f]) * (h @ wgu[:, f:])) @ wd


def attention(lp, x, *, n_heads, theta, eps, q_block=256):
    """MLA, nothing absorbed: x ``[n, d]`` -> ``[n, d]`` (before N2)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    h = rms_norm(x, lp["ln1"], eps)
    c_q = rms_norm(h @ lp["wqa"], lp["qln"], eps)
    q_nope = (c_q @ lp["wqn"]).reshape(n, n_heads, -1)
    q_rope = rope((c_q @ lp["wqr"]).reshape(n, n_heads, -1), theta)
    rank = lp["kvln"].shape[0]
    kv = h @ lp["wkva"]
    c = rms_norm(kv[:, :rank], lp["kvln"], eps)
    k_r = rope(kv[:, None, rank:], theta)[:, 0]
    k_nope = jnp.einsum("sr,hnr->shn", c, lp["wkb"])
    v = jnp.einsum("sr,hrv->shv", c, lp["wvb"])
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
    pos = jnp.arange(n)
    out = []
    for lo in range(0, n, q_block):
        s = (jnp.einsum("qhd,khd->hqk", q_nope[lo:lo + q_block], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_rope[lo:lo + q_block], k_r))
        seen = pos[None, :] <= pos[lo:lo + q_block, None]
        s = jnp.where(seen[None], s * scale, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out).reshape(n, -1) @ lp["wo"]


def expert_layer(lp, h, *, top_k, scale, first, follow=None):
    """The share's expert layer for normed tokens h ``[n, d]``: ``shared(h)
    + sum w_i E_i(h)`` over the chosen experts among ``first .. first +
    len(lp["egu"])``.  Returns (y, chosen ``[n, top_k]``, tie gaps ``[n]``:
    the top_k-th router logit less the next, ``inf`` where neither of the two
    experts is held here)."""
    import jax
    import jax.numpy as jnp

    logits = np.asarray(h @ lp["router"])
    order = np.argsort(-logits, axis=-1, kind="stable")
    chosen = order[:, :top_k] if follow is None else np.asarray(follow)
    held = lp["egu"].shape[0]
    edge = order[:, top_k - 1:top_k + 1]
    near = np.take_along_axis(logits, edge, axis=-1)
    ours = ((edge >= first) & (edge < first + held)).any(-1)
    gaps = np.where(ours, near[:, 0] - near[:, 1], np.inf)
    s = np.asarray(jax.nn.sigmoid(jnp.take_along_axis(
        jnp.asarray(logits), jnp.asarray(chosen), axis=-1)))
    weights = s / s.sum(-1, keepdims=True) * scale
    y = np.array(swiglu(h, lp["sgu"], lp["sd"]))
    for e in range(held):
        tok, slot = np.nonzero(chosen == first + e)
        if tok.size:
            out = swiglu(jnp.asarray(h)[tok], jnp.asarray(lp["egu"][e]),
                         jnp.asarray(lp["ed"][e]))
            np.add.at(y, tok, np.asarray(out) * weights[tok, slot][:, None])
    return jnp.asarray(y), chosen, gaps


def forward(p, ids, last, *, n_heads, top_k, scale, first, theta, eps,
            follow=None, q_block=256):
    """Full context, no cache, no pieces.  ``ids`` [n] -> (logits of the
    ``last`` positions ``[last, vocab]``, chosen experts ``[expert layers, n,
    top_k]``, tie gaps ``[n]``: the smallest over the expert layers).
    ``follow`` gives the experts to use instead, in ``chosen``'s shape."""
    import jax.numpy as jnp

    n = ids.shape[0]
    x = jnp.asarray(p["embed"])[ids]
    chosen, gaps, moe = [], np.full(n, np.inf), 0
    for lp in p["layers"]:
        lp = {k: (v if k in ("egu", "ed") else jnp.asarray(v))
              for k, v in lp.items()}
        x = x + rms_norm(attention(lp, x, n_heads=n_heads, theta=theta,
                                   eps=eps, q_block=q_block), lp["ln2"], eps)
        h = rms_norm(x, lp["ln3"], eps)
        if "wgu" in lp:
            y = swiglu(h, lp["wgu"], lp["wd"])
        else:
            y, picked, gap = expert_layer(
                lp, h, top_k=top_k, scale=scale, first=first,
                follow=None if follow is None else follow[moe])
            chosen.append(picked)
            gaps = np.minimum(gaps, gap)
            moe += 1
        x = x + rms_norm(y, lp["ln4"], eps)
    logits = rms_norm(x[n - last:], jnp.asarray(p["lnf"]), eps) @ jnp.asarray(
        p["head"])
    return logits, np.stack(chosen), gaps


def backend_forward(params, backend, ids, last, follow=None, q_block=256):
    """``forward`` at the sizes a backend object states."""
    return forward(params, ids, last, n_heads=backend.n_heads,
                   top_k=backend.top_k, scale=backend.routed_scale,
                   first=backend.first_expert, theta=backend.rope_theta,
                   eps=backend.rms_eps, follow=follow, q_block=q_block)


def judge(probe, rows_fn, margin=MARGIN, margin_tie=MARGIN_TIE, tie=TIE):
    """``evabyte.judge`` with two limits: ``rows_fn(prompt, emitted)`` gives
    the reference's logits row and routing-tie gap for each emitted token,
    teacher-forced.  One forward pass a prompt: the stream sent with the
    others is judged whole; its twin, sent alone, on the same rows as far as
    the two agree and at the token where they part."""
    streams = probe["concurrent"] + probe["solo"]
    if any(isinstance(s, dict) for s in streams):
        return {"ok": False, "why": f"a probe stream failed: {streams}"}
    worst = {False: 0.0, True: 0.0}      # by whether the row is a tie
    count = {False: 0, True: 0}
    parted, bad = [], 0

    def row_ok(row, tok, gap):
        tied = bool(gap < tie)
        below = float(row.max() - row[tok])
        worst[tied] = max(worst[tied], below)
        count[tied] += 1
        return below <= (margin_tie if tied else margin)

    for prompt, c, s in zip(probe["prompts"], probe["concurrent"],
                            probe["solo"]):
        rows, gaps = rows_fn(list(prompt), list(c)) if c else ([], [])
        rows = np.asarray(rows)
        for row, gap, tok in zip(rows, gaps, c):
            bad += not row_ok(row, tok, gap)
        for row, gap, a, b in zip(rows, gaps, c, s):
            bad += not row_ok(row, b, gap)
            if a != b:      # the reference's top-two gap where twins part
                top = np.sort(row)[-2:]
                parted.append((float(top[1] - top[0]), bool(gap < tie)))
                break
    lens_ok = all(len(e) == probe["max_tokens"] for e in streams)
    twins_ok = all(g <= (margin_tie if tied else margin)
                   for g, tied in parted)
    return {"ok": bool(bad == 0 and lens_ok and twins_ok),
            "worst_margin_below_max": worst[False], "margin": margin,
            "worst_margin_below_max_at_ties": worst[True],
            "margin_tie": margin_tie, "tie": tie,
            "tokens_checked": count[False] + count[True],
            "routing_tie_rows": count[True],
            "rows_over_their_margin": bad,
            "concurrent_equals_solo": probe["concurrent"] == probe["solo"],
            "parted_at_reference_gaps": [g for g, _ in parted],
            "all_tokens_arrived": bool(lens_ok)}


def check(params, probe, backend) -> dict:
    take_every_core()

    def rows_fn(prompt, emitted):
        seq = np.asarray(prompt + emitted, np.int32)
        logits, _, gaps = backend_forward(params, backend, seq[:-1],
                                          len(emitted))
        return logits, gaps[len(seq) - 1 - len(emitted):]

    return judge(probe, rows_fn)


# -- operations and bytes of a step ------------------------------------------

def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d, qr, kvr = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = (d * qr + qr * h * (nope + rp) + d * (kvr + rp)
            + kvr * h * (nope + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)
    return {"d": d, "heads": h, "row": kvr + rp, "rank": kvr, "attn": attn,
            "dense": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "shared": 3 * d * cfg["moe_intermediate_size"]
            * cfg["n_shared_experts"],
            "router": d * int(cfg["serve"]["kwargs"]["n_experts"]),
            "n_dense": cfg["first_k_dense_replace"],
            "n_moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "held": cfg["n_routed_experts"], "vocab": cfg["vocab_size"]}


def latent_attention(cfg: dict, lanes: float, context: float):
    """One layer's ``latent_wave_attention``: every live row (``kv_lora_rank
    + qk_rope_head_dim`` values, not the padded row) read once for all heads
    and one written, bfloat16; scores over the row and the weighted sum over
    its latent part, a head.  (flops, bytes)."""
    m = _dims(cfg)
    flops = 2 * lanes * context * m["heads"] * (m["row"] + m["rank"])
    nbytes = (lanes * (context + 1) * m["row"] * 2
              + lanes * m["heads"] * (m["row"] + m["rank"]) * 4)
    return float(flops), float(nbytes)


def expert_ffn(cfg: dict, pairs: float, touched: float, part: str = "both"):
    """One expert layer's grouped matmuls: ``pairs`` rows through an expert
    each, the matrices of the ``touched`` experts read once, bfloat16, the
    rows in (bfloat16) and out (float32).  ``part``: ``"up"`` (gate and up,
    two thirds of an expert), ``"down"``, or ``"both"``.  (flops, bytes)."""
    m = _dims(cfg)
    d, f = m["d"], cfg["moe_intermediate_size"]
    up = (2 * pairs * 2 * d * f,
          touched * 2 * d * f * 2 + pairs * (d * 2 + 2 * f * 4))
    down = (2 * pairs * f * d,
            touched * f * d * 2 + pairs * (f * 2 + d * 4))
    flops, nbytes = {"up": up, "down": down,
                     "both": (up[0] + down[0], up[1] + down[1])}[part]
    return float(flops), float(nbytes)


def decode_step(cfg: dict, lanes: float, context: float, pairs: float,
                touched: float):
    """One decode wave: ``lanes`` streams advance one token, each reading
    ``context`` cache rows a layer; ``pairs`` (token, expert) pairs and
    ``touched`` experts' matrices an expert layer (means a layer).  Weights
    are bfloat16 but the float32 router; what one operation hands the next
    (the absorbed queries, the heads' outputs) is not counted.  (flops,
    bytes)."""
    m = _dims(cfg)
    layers = m["n_dense"] + m["n_moe"]
    a_f, _ = latent_attention(cfg, lanes, context)
    a_b = lanes * (context + 1) * m["row"] * 2     # the rows, not q and o
    e_f, e_b = expert_ffn(cfg, pairs, touched)
    flops = (layers * (2 * lanes * m["attn"] + a_f)
             + m["n_dense"] * 2 * lanes * m["dense"]
             + m["n_moe"] * (2 * lanes * (m["shared"] + m["router"]) + e_f)
             + 2 * lanes * m["d"] * m["vocab"])
    nbytes = (layers * (m["attn"] * 2 + a_b)
              + m["n_dense"] * m["dense"] * 2
              + m["n_moe"] * (m["shared"] * 2 + m["router"] * 4 + e_b)
              + m["d"] * m["vocab"] * 2 + lanes * m["d"] * 2)
    return float(flops), float(nbytes)


def piece_step(cfg: dict, positions: float, pairs_window: float,
               pairs_global: float, programs: float, heads: float = 0.0):
    """``programs`` piece programs that consumed ``positions`` valid prompt
    positions and scored ``pairs_window + pairs_global`` (query, key) pairs
    (summed over the layers), ``heads`` of them with a head
    (``prefill_heads``): ``cohere_moe``'s rules.  Useful work only: two
    operations a weight and valid position for the latent projections, the
    dense layer, the router, the shared expert and the ``8 / 256 x 16``
    held experts a position chooses; a pair costs a head its score over
    ``qk_nope + qk_rope`` features and its value over ``v_head_dim`` (the
    plain form a piece runs, the cheaper of the two for many queries); the
    head's product for one row a program that ran it.  Every held weight
    read once a program (the touched share taken as 1), the head's where it
    ran; cache rows are left out of the bytes.  (flops, bytes)."""
    m = _dims(cfg)
    pairs = pairs_window + pairs_global     # no window layers here
    layers = m["n_dense"] + m["n_moe"]
    chosen_here = (cfg["num_experts_per_tok"]
                   / int(cfg["serve"]["kwargs"]["n_experts"]) * m["held"])
    per_position = (layers * m["attn"] + m["n_dense"] * m["dense"]
                    + m["n_moe"] * (m["shared"] + m["router"]
                                    + chosen_here * m["expert"]))
    a_pair = m["heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                           + cfg["v_head_dim"])
    flops = (2 * positions * per_position + 2 * pairs * a_pair
             + 2 * heads * m["d"] * m["vocab"])
    nbytes = (programs * (
        (layers * m["attn"] + m["n_dense"] * m["dense"]) * 2
        + m["n_moe"] * ((m["shared"] + m["held"] * m["expert"]) * 2
                        + m["router"] * 4))
        + heads * m["d"] * m["vocab"] * 2)
    return float(flops), float(nbytes)


def prefill_work(ctx):
    """The window's piece programs by its counters, (flops, bytes) of all of
    them (``piece_step`` through ``reduce.pieces_work``), or None.  The program
    counts no attention pairs for this backend: the harness's table of
    prompts gives them, the triangle in every layer."""
    import reduce

    m = _dims(ctx["cfg"])
    return reduce.pieces_work(ctx, piece_step,
                              n_global=m["n_dense"] + m["n_moe"])


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
    program's ``capacity_rows`` at its wave tile, ``serve.expert_tile_rows``
    in the file): what tells a wave's operations from a prefill piece's in
    the trace."""
    lanes = int(cfg["serve"]["kwargs"]["max_streams"])
    tile = int(cfg["serve"]["expert_tile_rows"])
    held = int(cfg["n_routed_experts"])
    worst = lanes * min(int(cfg["num_experts_per_tok"]), held) \
        + held * (tile - 1)
    return -(-worst // tile) * tile


def kernel_share(ctx, parts, old_calls_share: float = 1.0):
    """A kernel's roofline share from the trace.  ``parts``: ``[(predicate
    on a group's name, (flops, bytes) of one call)]``.  The least seconds of
    every call the trace holds of the groups of ``jit_decode`` that a
    predicate accepts (``reduce.kernel_groups``: the layers are not under a
    ``scan``, so each layer's kernel is an operation of its own, and all of
    them are one group) over those groups' device time.  Nothing where the
    trace holds no event of the kernel.  ``old_calls_share`` serves a trace
    reduced before PR 39 only: the share of the steps that ran the program
    the operations are of."""
    import reduce
    import roofline

    found = [(seconds, calls, cost) for match, cost in parts
             for seconds, calls in reduce.kernel_groups(
                 ctx, match, old_calls_share)]
    if not found:
        return None
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    least = sum(calls * roofline.min_seconds(*cost, peaks)[0]
                for _, calls, cost in found)
    return 100.0 * least / sum(seconds for seconds, _, _ in found)
