"""Family ``ouro``: a dense decoder whose layer stack runs several passes over
one set of weights (a looped language model), a key/value cache for every
pass; one pipeline stage's layers, served over ``generate_stream``.

The forward pass below is written from the equations of ISSUE 50 / PERF.md
section 4 and the public config's keys (``ByteDance/Ouro-2.6B``:
``total_ut_steps`` 4, ``early_exit_threshold`` 1).  Nothing here is used by the
server and nothing of ``client_tpu/ops`` is used here; the only thing taken
from the program is the weights (data: ``reference.py`` asks the backend's
seeded, bfloat16-rounded values for float32, so the reference holds exactly
what the chip holds).  ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, no cache, no pieces, no kernels.

With ``x = E[ids]`` ``[n, d]`` and RMSNorm ``N(x; g) = x / rms(x) * g`` (eps
1e-6), for pass ``t = 0 .. passes - 1`` and every layer (the same weights in
every pass)::

    a = N(x; ln1);  q, k, v = a Wq, a Wk, a Wv       (no biases; H heads of D)
    q, k = rope(q, pos), rope(k, pos)
    o = softmax(q k^T / sqrt(D), causal) v           (key head i // (H / Hkv))
    x = x + N(o Wo; ln2)
    m = N(x; ln3);  x = x + N((silu(m Wg) * (m Wu)) Wd; ln4)
    after the last layer:  x = N(x; lnf);  lambda_t = sigmoid(x w_e + b_e)

The token leaves at the first pass where the cumulated exit probability
reaches ``early_exit_threshold``: pass ``t < passes - 1`` exits with
``lambda_t prod_{j < t} (1 - lambda_j)``, the last pass takes the remaining
mass.  ``logits = x W_head`` of the pass it left at.  What the config does not
say (**assumed**, each from the family's report, "Scaling Latent Reasoning via
Looped Language Models", and its released modelling code; the configuration
file lists them with their reasons):

- RoPE is the rotate-half pairing over all ``D`` lanes of a head
  (``rope_theta`` 1e6, no scaling), the positions the same in every pass;
- the **sandwich**: a norm before *and after* the mixer and the feed-forward
  (``ln1 .. ln4``), the second on the sub-block's output before the add;
- the final norm ``lnf`` closes **every** pass and its output is the next
  pass's input (and the head's, and the gate's);
- the exit gate's form (one linear unit and a sigmoid on the normed state);
- every pass keeps keys and values of its own (the report's decoding with the
  last pass's cache shared is an approximation that changes the logits).

At the published threshold of 1 no cumulated probability short of the last
pass's reaches it, so every token's logits are the last pass's: that is what
the server runs, and ``forward`` returns the chosen pass so that the tests
hold the rule at a lower threshold too.

Tolerance (stated here, with the reasons).  ``kimi_linear``'s comparison
(``judge`` there, with no expert layer to follow): the probe's streams
(prompts of 40, 300, 512 and 1100 tokens: inside the first piece, inside a
piece, on a piece's edge, past two pieces; 64 waves behind each) are sent
together and then each alone and ask for their **record**: ``1 + 8`` logits of
the row each token was chosen from, as the timed programs computed them.  The
reference is teacher-forced on a stream's own tokens.  Four limits, each
between the served program's readings and a control's at the published widths
(my chip runs, PR 50: ``testdata/ouro_controls.py`` through the whole harness;
PERF.md section 6 has the readings run by run):

- ``LOGIT_RMS_ALONE`` = ``LOGIT_RMS_TOGETHER`` = 0.05: the rms of served logit
  less reference logit over the record's logits of every judged row, apart
  over the streams sent alone (waves of one lane) and those sent together
  (waves of four), as ``judge`` takes them (here the two read alike: the
  error is the 96 normed sub-blocks' a token, not a wave's rounding).  Served
  (bfloat16 operands into float32 sums, bfloat16 rows, a float32 residual
  stream): 0.019-0.024 either way, a stream 0.010-0.027 (logits of unit
  scale; five to seven times the other cells' 0.003-0.006, because every
  token passes 96 sub-blocks whose outputs are each normed to unit size
  before they are added: a product's rounding is not shrunk by a small
  residual branch).  **e4m3 operands: 0.507 and 0.535**; no RoPE 0.555 and
  0.567; a shared cache 0.863 and 0.864; no second norms 1.120; the final
  norm at the end alone 1.94 and 1.95; one pass 1.95 and 1.93.
- ``LOGIT_MAX`` = 0.2: the worst single logit.  Served 0.069-0.074 (4608
  logits a run); e4m3 1.88; unrotated 1.83; the other controls 2.7-6.6.
- ``MARGIN`` = 0.15: each emitted token's reference logit under its row's
  best.  Served at most 0.052-0.053 (19-27 of 512 rows are off the
  reference's best by a near tie); e4m3 2.31; unrotated 1.76; the others
  2.8-6.8.

Every control fails by all three kinds of limit; the nearest precision below
the stated one (e4m3) lies ten times over each and the served program two to
three times under.

A record that does not hold a row for every position fails.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import family

# Set between the served program's readings and the e4m3 control's (my chip
# runs, PR 50; the readings beside each limit in PERF.md section 6).
MARGIN = 0.15
LOGIT_RMS_ALONE = 0.05
LOGIT_RMS_TOGETHER = 0.05
LOGIT_MAX = 0.2

_kimi = family.load("kimi_linear")
_small = family.load("smallthinker")
_pangu = family.load("pangu_moe")
_evabyte = family.load("evabyte")
_gpt = family.load("gpt")
encode_request = _gpt.encode_request
probe = _kimi.probe
take_every_core = _evabyte.take_every_core
kernel_share = _pangu.kernel_share
rms_norm = _pangu.rms_norm
# Rotate-half over the whole head (assumed): x ``[n, H, D]``, lane ``i`` paired
# with lane ``i + D / 2``, frequency ``theta ** (-2 i / D)``.
rope = _small.rope_at


# -- the plain reference --------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _layer_jit(n_heads, n_kv_heads, theta, eps, q_block):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(lp, x):
        n = x.shape[0]
        pos = jnp.arange(n)
        a = rms_norm(x, lp["ln1"], eps)
        q = rope((a @ lp["wq"]).reshape(n, n_heads, -1), pos, theta)
        k = rope((a @ lp["wk"]).reshape(n, n_kv_heads, -1), pos, theta)
        v = (a @ lp["wv"]).reshape(n, n_kv_heads, -1)
        group = n_heads // n_kv_heads
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        scale = 1.0 / math.sqrt(q.shape[-1])
        out = []
        # Query blocks: a block's scores against the keys up to its last
        # query, under a dense mask.
        for lo in range(0, n, q_block):
            hi = min(lo + q_block, n)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
            seen = pos[None, :hi] <= pos[lo:hi, None]
            s = jnp.where(seen[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                  v[:hi]))
        o = jnp.concatenate(out).reshape(n, -1)
        # The sandwich (assumed): the sub-block's output is normed, then added.
        x = x + rms_norm(o @ lp["wo"], lp["ln2"], eps)
        m = rms_norm(x, lp["ln3"], eps)
        f = lp["wgu"].shape[1] // 2
        y = (jax.nn.silu(m @ lp["wgu"][:, :f]) * (m @ lp["wgu"][:, f:])
             ) @ lp["wd"]
        return x + rms_norm(y, lp["ln4"], eps)

    return run


def exit_pass(gates, threshold):
    """The exit rule: ``gates [passes, n]`` (``lambda_t`` of every pass and
    position) -> the pass each position leaves at ``[n]``: the first whose
    cumulated exit probability reaches ``threshold``; the last pass takes the
    remaining mass, so it is reached where no earlier one is."""
    gates = np.asarray(gates, np.float64)
    passes = gates.shape[0]
    stay = np.cumprod(np.concatenate(
        [np.ones((1, gates.shape[1])), 1.0 - gates[:-1]]), axis=0)
    cdf = np.cumsum(gates[:-1] * stay[:-1], axis=0)       # passes before last
    reached = cdf >= threshold
    return np.where(reached.any(axis=0), reached.argmax(axis=0), passes - 1)


def forward(p, ids, last, *, passes, n_heads, n_kv_heads, theta, eps,
            threshold=1.0, q_block=512):
    """Full context, no cache, no pieces.  ``ids`` [n] -> (logits of the
    ``last`` positions ``[last, vocab]``, each from the pass its position
    left at; that pass ``[n]``)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        n = ids.shape[0]
        layer = _layer_jit(int(n_heads), int(n_kv_heads), float(theta),
                           float(eps), int(q_block))
        layers = [{k: jnp.asarray(v) for k, v in lp.items()}
                  for lp in p["layers"]]
        lnf = jnp.asarray(p["lnf"])
        x = jnp.asarray(np.asarray(p["embed"])[ids])
        closed, gates = [], []
        for _ in range(passes):
            for lp in layers:                # the same weights in every pass
                x = layer(lp, x)
            # The final norm closes every pass and feeds the next (assumed).
            x = rms_norm(x, lnf, eps)
            closed.append(x[n - last:])
            gates.append(jax.nn.sigmoid(
                x @ jnp.asarray(p["exit_w"]) + jnp.asarray(p["exit_b"])[0]))
        chosen = exit_pass(np.stack([np.asarray(g) for g in gates]),
                           threshold)
        rows = jnp.stack(closed)[chosen[n - last:], jnp.arange(last)]
        return rows @ jnp.asarray(p["head"]), chosen


def backend_forward(params, backend, ids, last, threshold=1.0, q_block=512):
    """``forward`` at the sizes a backend object states, **as the model is
    published** (whatever a control of the comparison serves:
    ``testdata/ouro_controls.py``): ``total_ut_steps`` passes from the
    constructor's ``passes`` of the served class, which a control that runs
    fewer keeps in ``published_passes``."""
    return forward(params, ids, last,
                   passes=getattr(backend, "published_passes",
                                  backend.passes),
                   n_heads=backend.n_heads, n_kv_heads=backend.n_kv_heads,
                   theta=backend.rope_theta, eps=backend.rms_eps,
                   threshold=threshold, q_block=q_block)


def check(params, probe, backend) -> dict:
    import jax.numpy as jnp

    take_every_core()
    # (Every stream's pass takes the head: on the host's device once.)
    params = {**params, "head": jnp.asarray(params["head"])}

    def rows_fn(prompt, emitted, _words):
        seq = np.asarray(prompt + emitted, np.int32)
        logits, _ = backend_forward(params, backend, seq[:-1], len(emitted))
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
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"d": d, "heads": h, "head_dim": hd, "row": hk * hd,
            "layers": cfg["num_hidden_layers"],
            "passes": cfg["total_ut_steps"],
            "layer": d * hd * (2 * h + 2 * hk) + 3 * d * f,
            "vocab": cfg["vocab_size"]}


def dense_products(cfg: dict, lanes: float, passes: float):
    """A wave's dense products: every layer's seven matrices read once **a
    pass** (bfloat16; one set of weights on the device, ``passes`` reads of
    it), the head once; two operations a weight and live lane.  (flops,
    bytes)."""
    m = _dims(cfg)
    weights = passes * m["layers"] * m["layer"]
    head = m["d"] * m["vocab"]
    return (float(2 * lanes * (weights + head)), float(2 * (weights + head)))


def decode_attention(cfg: dict, lanes: float, live_rows: float):
    """One call of ``decode_wave_attention`` (a layer in a pass): each lane's
    live rows of K and of V (``Hkv x D`` values, bfloat16: 8 KB a row for the
    two) read once and one row of each written; the useful products (a
    head's ``D`` features a score and a value).  (flops, bytes)."""
    m = _dims(cfg)
    return (float(4 * lanes * live_rows * m["heads"] * m["head_dim"]),
            float(2 * lanes * (live_rows + 1) * m["row"] * 2))


def decode_step(cfg: dict, lanes: float, context: float, passes: float):
    """One decode wave: ``lanes`` live streams advance one token through
    ``passes`` x the layers; each reads ``context`` rows in every layer of
    every pass (live lanes, not the bucket).  What one operation hands the
    next is not counted.  (flops, bytes)."""
    m = _dims(cfg)
    d_f, d_b = dense_products(cfg, lanes, passes)
    a_f, a_b = decode_attention(cfg, lanes, context)
    calls = passes * m["layers"]
    return (float(d_f + calls * a_f),
            float(d_b + calls * a_b + lanes * m["d"] * 2))


def piece_step(cfg: dict, positions: float, pairs_window: float,
               pairs_global: float, programs: float, heads: float = 0.0):
    """``programs`` piece programs that consumed ``positions`` valid prompt
    positions and scored ``pairs_window + pairs_global`` (query, key) pairs
    (summed over the layers of every pass), ``heads`` of them with a head
    (``prefill_heads``): ``cohere_moe``'s rules.  Useful work only: two
    operations a weight and valid position for every layer's seven matrices
    **a pass**; four a pair, head and lane of 128 for the attention; the
    head's product for one row a program that ran it.  Every layer's weights
    read once a pass and program (``dense_products``' count of a wave), one
    lane or two, the head's where it ran; cache rows are left out of the
    bytes.  (flops, bytes)."""
    m = _dims(cfg)
    pairs = pairs_window + pairs_global     # no window layers here
    weights = m["passes"] * m["layers"] * m["layer"]
    flops = (2 * positions * weights + 4 * pairs * m["heads"] * m["head_dim"]
             + 2 * heads * m["d"] * m["vocab"])
    nbytes = programs * weights * 2 + heads * m["d"] * m["vocab"] * 2
    return float(flops), float(nbytes)


def prefill_work(ctx):
    """The window's piece programs by its counters, (flops, bytes) of all of
    them (``piece_step`` through ``reduce.pieces_work``), or None.  The program
    counts no attention pairs for this backend: the harness's table of
    prompts gives them, the triangle in every layer of every pass."""
    import reduce

    m = _dims(ctx["cfg"])
    return reduce.pieces_work(ctx, piece_step,
                              n_global=m["passes"] * m["layers"])


def _counters(ctx):
    import progspans

    w = progspans.window(ctx)
    if w is None:
        return None
    c = w["counters"]
    if not c.get("fetched_waves") or not c.get("fetched_lanes_live"):
        return None
    return c


def wave_means(ctx):
    """Means over the window's decode waves, from the program's counters:
    (live lanes a wave, context positions a live lane, passes a wave, waves),
    or None (a program that counts no passes)."""
    c = _counters(ctx)
    if c is None or not c.get("fetched_passes"):
        return None
    waves, lanes = c["fetched_waves"], c["fetched_lanes_live"]
    return (lanes / waves, c["fetched_positions_valid"] / lanes,
            c["fetched_passes"] / waves, waves)


def rows_per_wave(ctx):
    """``decode_attn_roofline``'s form: (0, rows a wave read in one call of
    the kernel, waves), or None: counter ``fetched_rows_global`` (every
    layer's in every pass) over the calls a wave makes."""
    m = wave_means(ctx)
    c = _counters(ctx)
    if m is None or not c.get("fetched_rows_global"):
        return None
    calls = m[2] * _dims(ctx["cfg"])["layers"]
    return 0.0, c["fetched_rows_global"] / calls / m[3], m[3]


def step_mix(ctx):
    """Decode cells: the window's waves as one mean step."""
    m = wave_means(ctx)
    if m is None:
        return None
    return [(float(m[3]), decode_step(ctx["cfg"], *m[:3]))]
