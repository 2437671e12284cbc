"""Family ``granite_hybrid``: a dense decoder with a recurrent state, nine
Mamba-2 layers to one attention layer without positions, a SwiGLU feed-forward
inside every layer, four scalar multipliers and a tied head; the model whole
on one chip, served over ``generate_stream``.

The forward pass below is written from the equations of ISSUE 59 / PERF.md
section 4 and the public config's keys (``ibm-granite/granite-4.0-h-micro``,
``model_type`` ``granitemoehybrid``, ``num_local_experts`` 0).  Nothing here is
used by the server and nothing of ``client_tpu/ops`` is used here; the only
thing taken from the program is the weights (data: ``reference.py`` asks the
backend's seeded, bfloat16-rounded values for float32, so the reference holds
exactly what the chip holds).  ``jax.numpy`` float32 at ``precision=highest``,
no cache, no pieces, no chunked form, no kernels; a layer's weights go to the
host's device one layer at a time (12.8 GB of float32 stay numpy's).

With RMSNorm ``N(x; g) = x / rms(x) * g`` (eps 1e-5), no bias but the
convolution's::

    x = embedding_multiplier * E[ids]                                 (12)
    every layer l, layer_types[l] in mamba | attention:
      x = x + residual_multiplier * Mixer_l(N(x; ln))                 (0.22)
      [g | u] = N(x; ln2) W_in;  x = x + residual_multiplier * (silu(g) * u) W_out
    logits = N(x; lnf) E^T / logits_scaling                           (8; tied)

- *mamba*: ``nemotron_h``'s Mamba-2 layer (``mamba_layer`` there: the
  projection's three column blocks, the causal depthwise convolution with its
  bias under a silu, ``dt = softplus(dt + dt_bias)`` unclamped, the state
  ``S [P, N]`` a head walked **token by token** under one ``lax.scan``, ``y =
  S C + D x``, the gate before the norm) at **one** group: B and C are shared
  by all 64 heads and the gated norm runs over all 4096 channels.
- *attention*: ``q, k, v = h W_q, h W_k, h W_v`` (32 query heads over 8
  key/value heads of 64, query head i on key head ``i // 4``), **nothing
  rotated**, a dense causal softmax of ``q k^T * attention_multiplier``
  (1/64, **not** ``1 / sqrt(64)``), out ``o W_o``.

Tolerance (stated here, with the reasons).  ``kimi_linear``'s comparison
(``judge`` there, with no expert layer to follow, as ``ouro``): the probe's
streams (``probe_prompt_lens``; 64 waves behind each) are sent together and
then each alone and ask for their **record**: ``1 + 8`` logits of the row each
token was chosen from, as the timed programs computed them (prefill by pieces
of 512, then waves through the arena).  The reference is teacher-forced on a
stream's own tokens.  The limits stand between the served program's readings
and the controls' at the published widths (my chip runs, PR 59:
``testdata/granite_hybrid_controls.py`` through the whole harness; PERF.md
section 6 has the readings):

- ``LOGIT_RMS_ALONE`` and ``LOGIT_RMS_TOGETHER``: the rms of served logit less
  reference logit over the record's logits of every judged row, apart over
  the streams sent alone (waves of one lane) and those sent together (waves
  of four).  Logits here are of scale 0.47 (unit rows against an embedding
  of scale 1/12, over 8).
- ``LOGIT_MAX``: the worst single logit.
- ``MARGIN``: each emitted token's reference logit under its row's best.

A record that does not hold a row for every position fails.
"""

from __future__ import annotations

import functools

import numpy as np

import family

# Set between the served program's readings (fourteen seeds) and the controls'
# (my chip runs, PR 59; PERF.md section 6).  The two precision controls are the nearest precisions
# below the configuration's: ``bf16_state`` (a float32 state) and ``e4m3``
# (bfloat16 products).  The rms limits refuse both and ``rotated``, the
# weakest control of the model (scores under 1/64 are small at the seeded
# scales); ``LOGIT_MAX`` stands between the served worst and ``e4m3``'s, since
# a maximum over 4608 logits has the longer tail over seeds.
MARGIN = 0.012               # served 0.0; residual_1 11.1 (a flip's margin is
#                              under twice LOGIT_MAX's served reading)
LOGIT_RMS_ALONE = 0.00175    # served 0.00096-0.00118; rotated 0.00266,
#                              bf16_state 0.00353, e4m3 0.0093
LOGIT_RMS_TOGETHER = 0.003   # served 0.00217-0.00238; rotated 0.00332,
#                              bf16_state 0.00409, e4m3 0.034
LOGIT_MAX = 0.025            # served 0.0075-0.0099; rotated 0.0148,
#                              bf16_state 0.0179 (under it), e4m3 0.122
# On a second seed (2147484777; alone | together | worst): rotated 0.00258 |
# 0.00332 | 0.0147, bf16_state 0.00340 | 0.00393 | 0.0218: each within 4% of
# its first rms readings, refused by both rms limits again.

_nemotron = family.load("nemotron_h")
_kimi = family.load("kimi_linear")
_pangu = family.load("pangu_moe")
_evabyte = family.load("evabyte")
_gpt = family.load("gpt")
encode_request = _gpt.encode_request
probe = _kimi.probe
take_every_core = _evabyte.take_every_core
rms_norm, swiglu = _pangu.rms_norm, _pangu.swiglu
kernel_share = _pangu.kernel_share
# Mamba-2, position by position (the one group is an argument).
mamba_layer = _nemotron.mamba_layer


# -- the plain reference --------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _attention_jit(n_heads, n_kv_heads, scale, eps, q_block):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(lp, x):
        n = x.shape[0]
        pos = jnp.arange(n)
        h = rms_norm(x, lp["ln"], eps)
        q = (h @ lp["wq"]).reshape(n, n_heads, -1)
        k = (h @ lp["wk"]).reshape(n, n_kv_heads, -1)
        v = (h @ lp["wv"]).reshape(n, n_kv_heads, -1)
        k, v = (jnp.repeat(t, n_heads // n_kv_heads, axis=1) for t in (k, v))
        out = []
        # Query blocks: a block's scores against the keys up to its last
        # query, under a dense mask.  Nothing is rotated.
        for lo in range(0, n, q_block):
            hi = min(lo + q_block, n)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
            seen = pos[None, :hi] <= pos[lo:hi, None]
            s = jnp.where(seen[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                  v[:hi]))
        return jnp.concatenate(out).reshape(n, -1) @ lp["wo"]

    return run


@functools.lru_cache(maxsize=None)
def _ffn_jit(eps):
    import jax

    return jax.jit(lambda lp, x: swiglu(rms_norm(x, lp["ln2"], eps),
                                        lp["wgu"], lp["wd"]))


def forward(p, ids, last, *, kinds, n_heads, n_kv_heads, embed_mult,
            residual_mult, attn_mult, logits_scale, eps, q_block=512):
    """Full context, no cache, no pieces.  ``ids`` [n] -> logits of the
    ``last`` positions ``[last, vocab]``.  ``kinds``: a layer each,
    ``"state"`` (mamba) or ``"rows"`` (attention)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        n = ids.shape[0]
        attention = _attention_jit(int(n_heads), int(n_kv_heads),
                                   float(attn_mult), float(eps), int(q_block))
        ffn = _ffn_jit(float(eps))
        embed = p["embed"]
        x = embed_mult * jnp.asarray(np.asarray(embed)[ids])
        for lp, kind in zip(p["layers"], kinds):
            lp = {k: jnp.asarray(v) for k, v in lp.items()}
            if kind == "state":
                # (One group: B and C shared by every head, the gated norm
                # over all channels.)
                mixed = mamba_layer(lp, x, groups=1, eps=eps)
            else:
                mixed = attention(lp, x)
            x = x + residual_mult * mixed
            x = x + residual_mult * ffn(lp, x)
        # The head is the embedding.
        rows = rms_norm(x[n - last:], jnp.asarray(p["lnf"]), eps)
        return rows @ jnp.asarray(embed).T / logits_scale


def backend_forward(params, backend, ids, last, q_block=512):
    """``forward`` at the sizes a backend object states, **as the model is
    published** (whatever a control of the comparison serves:
    ``testdata/granite_hybrid_controls.py`` keeps the published numbers in
    ``published``)."""
    pub = getattr(backend, "published", backend)
    return forward(params, ids, last, kinds=backend.layer_kinds,
                   n_heads=backend.n_heads, n_kv_heads=backend.n_kv_heads,
                   embed_mult=pub.embedding_multiplier,
                   residual_mult=pub.residual_multiplier,
                   attn_mult=pub.attn_scale,
                   logits_scale=pub.logits_scaling, eps=backend.rms_eps,
                   q_block=q_block)


def check(params, probe, backend) -> dict:
    import jax.numpy as jnp

    take_every_core()
    # (Every stream's pass takes the embedding as the head: on the host's
    # device once.)
    params = {**params, "embed": jnp.asarray(params["embed"])}

    def rows_fn(prompt, emitted, _words):
        seq = np.asarray(prompt + emitted, np.int32)
        logits = backend_forward(params, backend, seq[:-1], len(emitted))
        return logits, np.zeros(len(seq) - 1)

    verdict = _kimi.judge(probe, rows_fn, 0, margin=MARGIN,
                          logit_rms_alone=LOGIT_RMS_ALONE,
                          logit_rms_together=LOGIT_RMS_TOGETHER,
                          logit_max=LOGIT_MAX, tie=0.0)
    # (No router: nothing is followed and nothing can flip.)
    for key in ("worst_flip_from_the_edge", "tie", "positions_flipped",
                "positions_followed"):
        verdict.pop(key, None)
    return verdict


# -- operations and bytes of a step ------------------------------------------

def _dims(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    hm, pm = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    taps = cfg["mamba_d_conv"]
    d_inner = hm * pm
    conv = d_inner + 2 * groups * state
    return {"d": d, "heads": h, "head_dim": hd, "row": hk * hd,
            "layers": len(cfg["layer_types"]),
            "n_m": cfg["layer_types"].count("mamba"),
            "n_attn": cfg["layer_types"].count("attention"),
            "m_heads": hm, "m_dim": pm, "groups": groups, "state": state,
            "d_inner": d_inner, "conv": conv, "taps": taps,
            # The two projections of a mixer (what ``wave_dense`` reads).
            "mamba_proj": d * (d_inner + conv + hm) + d_inner * d,
            "mamba_small": conv * (taps + 1) + d_inner + 3 * hm,
            "attn": d * hd * (2 * h + 2 * hk),
            "ffn": 3 * d * f, "vocab": cfg["vocab_size"]}


def ssm_update(cfg: dict, lanes: float):
    """One layer's ``ssd_wave_update``: the live lanes' states (``heads x
    head_dim x state`` float32) read once and written once; x, B, C and dt in
    and y out, float32; a state element is decayed, takes ``dt x B``, and
    enters ``S C`` (5 operations).  (flops, bytes)."""
    m = _dims(cfg)
    state = m["m_heads"] * m["m_dim"] * m["state"]
    vectors = 2 * m["d_inner"] + 2 * m["groups"] * m["state"] + m["m_heads"]
    return (float(5 * lanes * state),
            float(lanes * (2 * state + vectors) * 4))


def decode_attention(cfg: dict, lanes: float, live_rows: float):
    """One layer's ``decode_wave_attention`` with grouped-query rows: each
    lane's live rows of K and of V (``Hkv x D`` = 512 values, bfloat16: 2 KB a
    row for the two) read once for all the heads and one row of each written;
    the useful products (a head's ``D`` features a score and a value).
    (flops, bytes)."""
    m = _dims(cfg)
    return (float(4 * lanes * live_rows * m["heads"] * m["head_dim"]),
            float(2 * lanes * (live_rows + 1) * m["row"] * 2))


def wave_dense(cfg: dict, lanes: float):
    """A wave's dense products: the two projections of every mixer, every
    layer's feed-forward and the tied head once, each weight read once
    (bfloat16), two operations a weight and live lane.  What the products
    read and write of activations is left out (a product's operands may never
    leave the chip's fast memory): a floor.  (flops, bytes)."""
    m = _dims(cfg)
    weights = (m["n_m"] * m["mamba_proj"] + m["n_attn"] * m["attn"]
               + m["layers"] * m["ffn"] + m["d"] * m["vocab"])
    return float(2 * lanes * weights), float(2 * weights)


def cache_bytes(cfg: dict, lanes: float, positions: float):
    """What a wave's two caches move: (the recurrent states of ``lanes`` live
    lanes, read and written in every mamba layer; the key and value rows of
    ``positions`` live positions, read in every attention layer), bytes."""
    m = _dims(cfg)
    state = m["m_heads"] * m["m_dim"] * m["state"] * 4
    return (float(lanes * m["n_m"] * 2 * state),
            float(positions * m["n_attn"] * 2 * m["row"] * 2))


def decode_step(cfg: dict, lanes: float, context: float):
    """One decode wave: ``lanes`` streams advance one token; each reads and
    writes its state and convolution tail a mamba layer and reads ``context``
    rows an attention layer.  Weights are bfloat16; what one operation hands
    the next is not counted.  (flops, bytes)."""
    m = _dims(cfg)
    d_f, d_b = wave_dense(cfg, lanes)
    s_f, s_b = ssm_update(cfg, lanes)
    a_f, a_b = decode_attention(cfg, lanes, context)
    tail = lanes * 2 * (m["taps"] - 1) * m["conv"] * 2
    return (float(d_f + m["n_m"] * s_f + m["n_attn"] * a_f),
            float(d_b + m["n_m"] * (m["mamba_small"] * 2 + s_b + tail)
                  + m["n_attn"] * a_b + lanes * m["d"] * 2))


def chunk_scan(cfg: dict, positions: float):
    """One mamba layer's chunked form over ``positions`` valid positions at
    the published chunk ``Q``: within a chunk a position scores the ``(Q + 1)
    / 2`` positions up to it (``C B^T``, ``state`` wide, once a group) and
    takes their ``dt x`` under the decay (``heads x head_dim`` wide); across
    chunks it reads the carried state (``S C``) and enters the chunk's
    (``B^T dt x``), ``heads x head_dim x state`` each; two operations a
    product's term.  The decays' exponentials and elementwise products are
    left out: a floor.  Operations."""
    m = _dims(cfg)
    inner = m["m_heads"] * m["m_dim"]
    return float(positions * (
        (cfg["mamba_chunk_size"] + 1) * (m["groups"] * m["state"] + inner)
        + 4 * inner * m["state"]))


def piece_step(cfg: dict, positions: float, pairs_window: float,
               pairs_global: float, programs: float, heads: float = 0.0):
    """``programs`` piece programs that consumed ``positions`` valid prompt
    positions and scored ``pairs_window + pairs_global`` (query, key) pairs
    (summed over the attention layers), ``heads`` of them with a head
    (``prefill_heads``): ``cohere_moe``'s rules.  Useful work only: two
    operations a weight and valid position for the mixers' projections and
    the feed-forwards; ``chunk_scan`` a mamba layer; four a pair, head and
    lane of 64 for the attention; the head's product for one row a program
    that ran it.  Every layer's weights read once a program, one lane or
    two, the head's where it ran; a lane's states read and written once a
    mamba layer, **for one lane a program** (the counters handed here do not
    say how many programs held two: a floor); cache rows are left out of the
    bytes.  (flops, bytes)."""
    m = _dims(cfg)
    pairs = pairs_window + pairs_global     # no window layers here
    weights = (m["n_m"] * m["mamba_proj"] + m["n_attn"] * m["attn"]
               + m["layers"] * m["ffn"])
    state = m["m_heads"] * m["m_dim"] * m["state"] * 4
    flops = (2 * positions * weights + m["n_m"] * chunk_scan(cfg, positions)
             + 4 * pairs * m["heads"] * m["head_dim"]
             + 2 * heads * m["d"] * m["vocab"])
    nbytes = (programs * (weights * 2 + m["n_m"] * 2 * state)
              + heads * m["d"] * m["vocab"] * 2)
    return float(flops), float(nbytes)


def prefill_work(ctx):
    """The window's piece programs by its counters, (flops, bytes) of all of
    them (``piece_step`` through ``reduce.pieces_work``), or None.  The
    attention pairs are the program's own (``prefill_pairs_global``: the
    backend's ``piece_pairs_by_kind``); where a kept context has none, the
    harness's table of prompts gives them, the triangle in an attention
    layer."""
    import reduce

    m = _dims(ctx["cfg"])
    return reduce.pieces_work(ctx, piece_step, n_global=m["n_attn"])


# The window's counters, or None where it fetched no wave.
_counters = _nemotron._counters


def wave_means(ctx):
    """Means over the window's decode waves, from the program's counters:
    (live lanes a wave, context positions a live lane, waves), or None."""
    c = _counters(ctx)
    if c is None:
        return None
    waves, lanes = c["fetched_waves"], c["fetched_lanes_live"]
    return lanes / waves, c["fetched_positions_valid"] / lanes, waves


def rows_per_wave(ctx):
    """``decode_attn_roofline``'s form: (0, rows a wave read in one attention
    layer, waves), or None: a lane's every position's row (counter
    ``fetched_rows_global``, all the attention layers'; the positions
    themselves where the program does not count it)."""
    c = _counters(ctx)
    if c is None:
        return None
    waves = c["fetched_waves"]
    rows = (c["fetched_rows_global"] / _dims(ctx["cfg"])["n_attn"]
            if c.get("fetched_rows_global")
            else c["fetched_positions_valid"])
    return 0.0, rows / waves, waves


def step_mix(ctx):
    """Decode cells: the window's waves as one mean step."""
    m = wave_means(ctx)
    if m is None:
        return None
    return [(float(m[2]), decode_step(ctx["cfg"], *m[:2]))]
