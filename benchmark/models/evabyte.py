"""Family ``evabyte``: a byte-level decoder with EVA chunked linear attention,
served over ``generate_stream``.

The forward pass below is written from the equations of ISSUE 28 / PERF.md
section 4 (EVA, arXiv:2302.04542 section 4, with the chunk's proposal mean a
learned vector) and the public config's keys, with explicit sets: an
``[n, n]`` token mask and an ``[n, n / c]`` chunk mask.  Nothing here is
used by the server; the only thing taken from the program is the weights
(data: ``reference.py`` casts the backend's seeded, bfloat16-rounded values
to float32, so the reference holds exactly what the chip holds).

Per head (d = head size, W = ``window_size``, c = ``chunk_size``; q and k
carry RoPE at their own positions), position i lies in window j = i // W and
attends with one softmax, scale 1/sqrt(d), to

- the exact keys/values of ``E_i = {m : m // W == j and m <= i}``;
- one summary per chunk t (positions tc .. tc+c-1) of a window before j:
  ``a_m = softmax_{m in t}(phi . k_m)``, ``v~_t = sum_m a_m v_m``,
  ``k~_t = mean_m k_m + mu``.

Block: ``x += Wo . eva(RMSNorm(x))``; ``x += W_down(silu(W_gate h) * W_up h)``,
``h = RMSNorm(x)``; RMSNorm is ``x / rms(x) * (1 + g)``; final RMSNorm; logits
``x W_head`` as ``[num_pred_heads, vocab]``.

Tolerance (stated here, with the reason).  As ``gpt.py``: teacher-forced on
the server's own emitted bytes, the probe's streams sent together and the
same streams alone; each emitted byte's **head-0** reference logit must be
within ``MARGIN`` = 0.04 of its row's maximum, and twins may part only where
the reference's best two logits are within ``MARGIN`` (a solo stream is
judged on its twin's rows as far as the two agree, and at the byte where they
part: one forward pass a prompt).  Head-0 logits are ~N(0,1) over 320 bytes
(maximum near 2.9, the best two 0.21 apart in the median).  The two readings
the limit lies between, at the published widths (PERF.md section 6, PR 28):
the served precision (bfloat16 operands into float32 sums, a bfloat16 cache
of rows and summaries) moves a logit by 0.0047 rms, so an emitted byte's
logit is at most 0.009 below the reference's best (1024 positions emulated
on the CPU; the chip runs' worst is in PERF.md): a quarter of the limit.
The nearest precision below, 8-bit floats (e4m3) for the same operands and
cache, moves a logit by 0.075 rms and puts an emitted byte 0.14-0.20 below
the best at the worst five of 512 positions, 66 of which change their byte:
five times the limit.  An 8-bit *cache alone* under bfloat16 matmuls reads
0.022-0.030 at the worst of 512 positions: this limit, and any limit on 128
sampled bytes, does **not** tell it from the served precision (a limit under
0.02 would fail one correct run in a hundred); the logit comparisons of
``tests/test_evabyte.py`` do.  A dropped summary term (``mu`` or ``phi``), a
stale window or a missed dump moves logits by tenths to units and fails.
"""

from __future__ import annotations

import math
import os

import numpy as np

import family

MARGIN = 0.04

_gpt = family.load("gpt")
# The wire is the decoder family's: SSE generate requests with binary ids,
# and the probe of ``probe_prompt_lens`` streamed together and then alone.
encode_request = _gpt.encode_request
probe = _gpt.probe


# -- the plain reference --------------------------------------------------------

def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, theta):
    """x [n, H, D] at positions 0..n-1, rotate-half pairing."""
    import jax.numpy as jnp

    n, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def eva_attention(q, k, v, phi, mu, window, chunk, q_block=256):
    """q, k, v [n, H, D] (RoPE applied), phi, mu [H, D] -> [n, H, D]: one
    softmax over the exact set and the remote chunks, by explicit masks,
    computed in blocks of queries."""
    import jax
    import jax.numpy as jnp

    n, h, d = q.shape
    nc = n // chunk                       # whole chunks (every remote one is)
    kc = k[: nc * chunk].reshape(nc, chunk, h, d)
    vc = v[: nc * chunk].reshape(nc, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("tmhd,hd->tmh", kc, phi), axis=1)
    v_sum = jnp.einsum("tmh,tmhd->thd", a, vc)
    k_sum = kc.mean(axis=1) + mu[None]
    pos = jnp.arange(n)
    chunk_window = (jnp.arange(nc) * chunk) // window
    out = []
    for lo in range(0, n, q_block):
        i = pos[lo:lo + q_block]
        tok_ok = ((pos[None, :] // window == i[:, None] // window)
                  & (pos[None, :] <= i[:, None]))            # [bq, n]
        ch_ok = chunk_window[None, :] < (i[:, None] // window)  # [bq, nc]
        s_tok = jnp.einsum("qhd,mhd->hqm", q[lo:lo + q_block], k)
        s_ch = jnp.einsum("qhd,thd->hqt", q[lo:lo + q_block], k_sum)
        s = jnp.concatenate([jnp.where(tok_ok[None], s_tok, -jnp.inf),
                             jnp.where(ch_ok[None], s_ch, -jnp.inf)], -1)
        w = jax.nn.softmax(s / math.sqrt(d), axis=-1)
        out.append(jnp.einsum("hqm,mhd->qhd", w[..., :n], v)
                   + jnp.einsum("hqt,thd->qhd", w[..., n:], v_sum))
    return jnp.concatenate(out)


def forward(p, ids, last, *, n_heads, window, chunk, theta, eps, n_pred,
            q_block=256):
    """Full context, no cache, no pieces.  ``ids`` [n] -> logits of the
    ``last`` positions, [last, n_pred, vocab]."""
    import jax
    import jax.numpy as jnp

    n = ids.shape[0]
    dm = p["embed"].shape[1]
    d = dm // n_heads
    x = jnp.asarray(p["embed"])[ids]
    layers = p["layers"]
    for li in range(layers["wq"].shape[0]):
        lp = {name: jnp.asarray(leaf[li]) for name, leaf in layers.items()}
        h = rms_norm(x, lp["ln1"], eps)
        q = rope((h @ lp["wq"]).reshape(n, n_heads, d), theta)
        k = rope((h @ lp["wk"]).reshape(n, n_heads, d), theta)
        v = (h @ lp["wv"]).reshape(n, n_heads, d)
        o = eva_attention(q, k, v, lp["phi"], lp["mu"], window, chunk,
                          q_block)
        x = x + o.reshape(n, dm) @ lp["wo"]
        h2 = rms_norm(x, lp["ln2"], eps)
        x = x + (jax.nn.silu(h2 @ lp["wg"]) * (h2 @ lp["wu"])) @ lp["wd"]
    logits = rms_norm(x[n - last:], jnp.asarray(p["lnf"]), eps) @ jnp.asarray(
        p["head"])
    return logits.reshape(last, n_pred, -1)


def backend_forward(params, backend, ids, last, q_block=256):
    """``forward`` at the sizes a backend object states."""
    return forward(params, ids, last, n_heads=backend.n_heads,
                   window=backend.window, chunk=backend.chunk,
                   theta=backend.rope_theta, eps=backend.rms_eps,
                   n_pred=backend.n_pred_heads, q_block=q_block)


def judge(probe, rows_fn, margin=MARGIN) -> dict:
    """The comparison: ``rows_fn(prompt, emitted)`` gives the reference's
    head-0 logits row for each emitted byte, teacher-forced.  One forward
    pass a prompt (a byte of a 2k prompt costs the CPU reference 3.2 GFLOP):
    the stream sent with the others is judged whole; its twin, sent alone,
    is judged on the same rows as far as the two agree and at the byte where
    they part (both were chosen from that row), not beyond."""
    streams = probe["concurrent"] + probe["solo"]
    if any(isinstance(s, dict) for s in streams):
        return {"ok": False, "why": f"a probe stream failed: {streams}"}
    worst, n_tok, parted = 0.0, 0, []
    for prompt, c, s in zip(probe["prompts"], probe["concurrent"],
                            probe["solo"]):
        rows = np.asarray(rows_fn(list(prompt), list(c))) if c else []
        for row, tok in zip(rows, c):
            worst = max(worst, float(row.max() - row[tok]))
            n_tok += 1
        for row, a, b in zip(rows, c, s):
            worst = max(worst, float(row.max() - row[b]))
            n_tok += 1
            if a != b:      # the reference's top-two gap where twins part
                top = np.sort(row)[-2:]
                parted.append(float(top[1] - top[0]))
                break
    lens_ok = all(len(e) == probe["max_tokens"] for e in streams)
    twins_ok = all(g <= margin for g in parted)
    return {"ok": bool(worst <= margin and lens_ok and twins_ok),
            "worst_margin_below_max": worst, "margin": margin,
            "tokens_checked": n_tok,
            "concurrent_equals_solo": probe["concurrent"] == probe["solo"],
            "parted_at_reference_gaps": parted,
            "all_tokens_arrived": bool(lens_ok)}


def take_every_core() -> None:
    """The harness pins the reference's child to the load generator's two
    cores so that building weights does not disturb the server's warm-up.
    By the time ``check`` runs the window is over and the server idle, and
    JAX has not started its CPU client yet (nothing before ``check`` touches
    a device): widen the process to every core it may use, so the client's
    thread pool is sized to them.  A 4.2k-byte probe is 14 TFLOP: 200 s on
    two cores (of the 300 s the harness waits), a quarter of that on
    thirteen (PERF.md section 6, PR 28)."""
    try:
        os.sched_setaffinity(0, range(os.cpu_count() or 1))
    except (AttributeError, OSError):
        pass


def check(params, probe, backend) -> dict:
    take_every_core()

    def rows_fn(prompt, emitted):
        seq = np.asarray(prompt + emitted, np.int32)
        return backend_forward(params, backend, seq[:-1],
                               len(emitted))[:, 0]

    return judge(probe, rows_fn)


# -- operations and bytes of a step ------------------------------------------

def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"] * cfg["num_pred_heads"],
            cfg["window_size"], cfg["chunk_size"])


def _weights(cfg: dict) -> int:
    d, f, n_layers, v, _, _ = _dims(cfg)
    return n_layers * (4 * d * d + 3 * d * f) + d * v


def piece_step(cfg: dict, lanes: int, valid: float, n_sum: float,
               weight_bytes: int = 2):
    """One prefill piece as the program runs it: ``lanes`` lanes of
    ``window_size`` positions (the matmuls run on every padded position;
    the head on one position a lane), each attending causally to its
    ``valid`` own positions and to ``n_sum`` summaries.  (flops, bytes);
    the cache rows read and written are bfloat16."""
    d, f, n_layers, v, w, c = _dims(cfg)
    t = lanes * w
    attn = 4 * lanes * (valid * (valid + 1) / 2 + valid * n_sum) * d
    flops = n_layers * (2 * t * (4 * d * d + 3 * d * f) + attn) \
        + 2 * lanes * d * v
    nbytes = (_weights(cfg) * weight_bytes
              + t * d * weight_bytes + t * 4           # embedding rows, ids
              + n_layers * 2 * lanes * (n_sum + min(valid, w)) * d * 2)
    return float(flops), float(nbytes)


def pieces_useful(cfg: dict, positions: float, pairs: float, pieces: float,
                  programs: float, weight_bytes: int = 2):
    """``programs`` piece programs by ``piece_step``'s terms at the prompts'
    own sizes: ``positions`` valid bytes in ``pieces`` lanes' pieces scoring
    ``pairs`` (query, key or summary) pairs a layer; a piece's padded
    positions are not counted.  Every weight read once a program, the head
    on one position a lane's piece, the embedding rows gathered; cache rows
    are left out of the bytes.  (flops, bytes)."""
    d, f, n_layers, v, _, _ = _dims(cfg)
    flops = (n_layers * (2 * positions * (4 * d * d + 3 * d * f)
                         + 4 * pairs * d) + 2 * pieces * d * v)
    nbytes = (programs * _weights(cfg) * weight_bytes
              + positions * (d * weight_bytes + 4))
    return float(flops), float(nbytes)


def prefill_work(ctx):
    """The window's piece programs, (flops, bytes) of all of them
    (``pieces_useful``), or None: programs, lanes' pieces and valid positions
    by the program's counters (the count of gen.prefill_dispatch,
    ``prefill_pieces``, ``prefill_positions_valid``); the pairs are the
    counted positions times the pairs a position of the harness's table of
    prompts scores (a prompt's piece j: the triangle over its own valid
    positions and a rectangle over the ``j x window_size / chunk_size``
    summaries before it), and left out where the context has no table."""
    import reduce

    n, prompts = reduce.prefill_counts(ctx), reduce.window_prompts(ctx)
    if n is None or not n["programs"]:
        return None
    _, _, _, _, w, c = _dims(ctx["cfg"])
    a_position = 0.0
    if prompts is not None:
        total = 0.0
        for p in np.asarray(prompts, np.float64):
            j = np.arange(int(-(-p // w)))
            valid = np.minimum(w, p - j * w)
            total += float((valid * (valid + 1) / 2
                            + valid * j * (w // c)).sum())
        a_position = total / float(np.sum(prompts))
    return pieces_useful(ctx["cfg"], n["positions"],
                         n["positions"] * a_position, n["pieces"],
                         n["programs"])


def piece_attention(cfg: dict, lanes: int, valid: float, n_sum: float):
    """The attention of one layer of one piece alone (the flash kernel):
    the causal triangle over the piece and the rectangle over the
    summaries; q, keys, values and the output once, bfloat16."""
    d, _, _, _, w, _ = _dims(cfg)
    flops = 4 * lanes * (valid * (valid + 1) / 2 + valid * n_sum) * d
    nbytes = lanes * (2 * w + 2 * (n_sum + w)) * d * 2
    return float(flops), float(nbytes)


def decode_step(cfg: dict, lanes: int, live_rows: float,
                weight_bytes: int = 2):
    """One decode wave: ``lanes`` streams advance one byte, each reading
    ``live_rows`` cache rows (summaries and window: **rows, not
    positions**) of bfloat16.  (flops, bytes)."""
    d, f, n_layers, v, _, _ = _dims(cfg)
    flops = n_layers * (2 * lanes * (4 * d * d + 3 * d * f)
                        + 4 * lanes * live_rows * d) + 2 * lanes * d * v
    nbytes = (_weights(cfg) * weight_bytes
              + n_layers * 2 * lanes * (live_rows + 1) * d * 2
              + lanes * d * weight_bytes)
    return float(flops), float(nbytes)


def decode_attention(cfg: dict, lanes: int, live_rows: float):
    """The decode kernel's one layer alone: each lane's live rows of K and
    of V read once, one row of each written, bfloat16; the useful products
    (one head's features a score, not the block-diagonal's H-fold)."""
    d = cfg["hidden_size"]
    return (float(4 * lanes * live_rows * d),
            float(2 * lanes * (live_rows + 1) * d * 2))


def dump_step(cfg: dict, lanes: int = 1):
    """One window dump: ``window_size`` rows of K and V read in every layer,
    ``window_size / chunk_size`` summaries of each written, bfloat16.
    (flops, bytes); the chunk scores and the pooling are 4 operations a
    feature."""
    d, _, n_layers, _, w, c = _dims(cfg)
    flops = n_layers * lanes * 4 * w * d
    nbytes = n_layers * lanes * 2 * (w + w // c) * d * 2
    return float(flops), float(nbytes)


def rows_per_wave(ctx):
    """Mean (summary rows, exact rows, waves) of the window's decode waves
    from the program's counters, or None."""
    import progspans

    w = progspans.window(ctx)
    if w is None:
        return None
    c = w["counters"]
    waves = c.get("fetched_waves", 0)
    if not waves or "fetched_rows_exact" not in c:
        return None
    return (c["fetched_rows_summary"] / waves, c["fetched_rows_exact"] / waves,
            waves)


def step_mix(ctx):
    """Decode cells: the window's waves as one mean step, at the mean live
    lanes a wave held and the mean live rows a lane read (the program's
    counters); padded lanes read nothing and are not counted."""
    import progspans

    rows = rows_per_wave(ctx)
    lanes = progspans.counter_ratio(ctx, "fetched_lanes_live",
                                    "fetched_waves")
    if rows is None or not lanes:
        return None
    return [(float(rows[2]),
             decode_step(ctx["cfg"], lanes, (rows[0] + rows[1]) / lanes))]
