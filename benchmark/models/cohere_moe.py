"""Family ``cohere_moe``: a parallel block (one LayerNorm read by the
attention, the router, the routed and the averaged shared experts; one add)
over three sliding-window RoPE layers to one full layer without positions,
128 query heads over 8 key/value heads, a tied head; served over
``generate_stream``.

The forward pass below is written from the equations of ISSUE 53 / PERF.md
section 4 and the public config's keys (``command-a-plus-05-2026``,
``model_type: cohere2_moe``).  Nothing here is used by the server and nothing
of ``client_tpu/ops`` is used here; the only thing taken from the program is
the weights (data: ``reference.py`` asks the backend's seeded,
bfloat16-rounded values for float32, so the reference holds exactly what the
chip holds).  With ``x = E[ids]`` ``[n, d]``, for layer l:

- ``h = (x - mean(x)) / sqrt(var(x) + eps) * g_l`` (``layer_norm_eps`` 1e-5,
  mean-subtracting, no bias); ``q, k, v = h W_q, h W_k, h W_v`` (no biases, no
  qk-norm), ``H`` query heads over ``Hkv`` key/value heads, query head i on
  key/value head ``i // (H / Hkv)``; scores ``q . k / sqrt(D)``.
- *Layer kinds* (``layer_types``, ``order_of_interleaved_layers:
  local_attn_first``): layers ``4k .. 4k+2`` are ``sliding_attention``: q and
  k take rotary positions in the **interleaved** pairing
  (``position_embedding_type: rope_gptj``: lanes ``2i, 2i+1`` a pair, theta
  50000, the whole head), key j for query t iff ``t - window < j <= t``; layer
  ``4k+3`` is ``full_attention``: **no positions**, key j iff ``j <= t``.
- ``s = sigmoid(h W_r)`` (float32), ``E`` the ``top_k`` largest, ``w_e = s_e /
  sum_E s`` (``expert_selection_fn: sigmoid``, ``norm_topk_prob``); ``routed =
  sum_E w_e W_d^e (silu(h W_g^e) * (h W_u^e))``, a loop over the held experts;
  ``shared = 1/4 sum_i S_d^i (silu(h S_g^i) * (h S_u^i))``: **four experts
  computed apart and averaged** (the program multiplies one pair of matrices
  that holds all four: a departure of the program's, not of this file).
- ``x = x + attn W_o + routed + shared`` (``use_parallel_block``); ``logits =
  LN(x; g_f) E^T * logit_scale`` (``tie_word_embeddings``).
- *Assumed* (the config does not say; the configuration file's ``assumed``):
  the window's edge (``sliding_window`` keys, the query's own among them);
  routed and shared are summed and only the shared experts are averaged among
  themselves; the sigmoid scores select without a bias or a scale; the
  embedding is unscaled.
- Nothing is a ring and nothing is cut into pieces; attention is computed in
  blocks of queries, a key/value head and half of its query heads at a time,
  and the experts one at a time, so that a 9000-token stream fits the host.
  Four things keep a verdict inside the 300 s the harness waits (a pass over
  18 000 prompt positions is 60 TFLOP in float32; the first form of this
  file took 258 s and more: PERF.md section 6): the weights are made arrays
  of the host's device once (``prepare``: a numpy matrix handed to a
  compiled product is copied every time); the probe's prompts are prefixes
  of one another (``probe``), so **one pass over the longest prompt** leaves
  every layer's keys and values for all of them, and every stream's own
  positions are computed in one more pass, side by side (``check``,
  ``forward``'s ``keep``, ``prompt`` and ``branches``); **the last layer
  computes its queries, its attention and its experts only for the rows
  whose logits are asked for** (its keys, values and router scores for every
  row: the later rows and the ``tie`` need them; nothing reads the other
  rows' outputs); and what XLA's CPU backend runs on a core or two (a
  block's softmax, an expert's few rows) runs a thread a task (``_pool``).

Tolerance (``kimi_linear``'s comparison, ``judge`` there, on what the timed
server produced): the probe's streams (5, 600, 4080, 4700 and 9000 tokens:
inside a piece, past one, at the window's edge, past it, past two rings; 32
tokens each) are sent together and then each alone and ask for their
**record**: which held experts each layer chose (16 bits a layer) and ``1 +
samples`` logits of the row each token was chosen from.  The reference is
teacher-forced on a stream's own tokens and follows the served routing
(``follow``); the limits below judge what is left, each between the served
program's readings and a control's at the published widths (my chip runs, PR
53; PERF.md section 6 has them run by run).  The logits here are those of a
tied head over unit-variance embeddings: their spread is ``sqrt(4096) = 64``,
so every limit on a logit is 64 times what it would be on logits of spread 1.
"""

from __future__ import annotations

import functools
import math
import os

import numpy as np

import family

# Each between the served program's largest reading over nine runs and the
# e4m3 control's (my chip runs, PR 53; PERF.md section 6), but ``MARGIN``:
# every emitted token was the reference's best in every run, the control's
# too (tied logits of spread 64 leave the best far ahead), so it is set where
# a wrong token would read (units) and tells no precision.
MARGIN = 1.0
LOGIT_RMS_ALONE = 0.25       # served 0.053-0.085, e4m3 0.62
LOGIT_RMS_TOGETHER = 0.5     # served 0.155-0.170, e4m3 2.41
LOGIT_MAX = 2.5              # served 0.54-0.77, e4m3 8.42
TIE = 0.003                  # served 0.0003-0.0006, e4m3 0.0112
WINDOW_LEAN = 0.5            # served within +-0.14, a window of 4095: below
# Logits of a row's first ids in a stream's record, beside its token's (the
# program's ``RECORD_LOGITS``).
SAMPLES = 8

_small = family.load("smallthinker")
_kimi = family.load("kimi_linear")
encode_request = _small.encode_request
take_every_core = _small.take_every_core
kernel_share = _small.kernel_share
window_edge = _small.window_edge


# -- the plain reference --------------------------------------------------------

def layer_norm(x, g, eps):
    """Mean-subtracting, no bias (``family.layer_norm`` with none)."""
    return family.layer_norm(x, g, 0.0, eps)


def rope_interleaved(x, pos, theta):
    """x ``[n, H, D]`` at positions ``pos [n]``: lanes ``(2i, 2i + 1)`` are
    the real and imaginary part of one number, turned by ``pos * theta ** (-2i
    / D)``."""
    import jax.numpy as jnp

    n, h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(n, h, d // 2, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                     axis=-1).reshape(n, h, d)


def _pool():
    """Threads for work that XLA's CPU backend runs on a core or two (a
    softmax over a block's scores, a product of a few dozen rows): one task
    a key/value head or an expert, as many at once as the process has
    cores."""
    global _POOL
    if _POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        _POOL = ThreadPoolExecutor(len(os.sched_getaffinity(0)))
    return _POOL


_POOL = None


@functools.lru_cache(maxsize=None)
def _attention_block(window, scale):
    """Query rows ``[r, D]`` at positions ``q_pos [r]`` (some query heads of
    one key/value head, a head's positions after another's) against the keys
    they may see ``[s, D]``, under a dense mask; the softmax's sum divides
    the weighted values, not the weights.  Compiled once a shape."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(q_b, k_b, v_b, q_pos, k_pos):
        ago = q_pos[:, None] - k_pos[None, :]
        seen = ago >= 0
        if window is not None:
            seen = seen & (ago < window)
        s = jnp.where(seen, (q_b @ k_b.T) * scale, -jnp.inf)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        return (e @ v_b) / e.sum(-1, keepdims=True)

    return block


def attention(lp, h, *, n_heads, n_kv_heads, window, rotate, theta,
              q_block=512, before=None, branches=None, q_from=0):
    """One layer's attention for normed rows h ``[n, d]`` -> (``[n - q_from,
    d]`` before the residual: the rows from ``q_from`` on, the layer's keys
    and values ``[P + n, Hkv, D]`` as the scores took them).  ``window``:
    ``None`` (every earlier position) or the band's keys; ``rotate``: whether
    q and k take positions.  ``before``: the keys and values of ``P`` earlier
    positions, which h's then follow.  ``branches``: ``[(rows, window,
    prefix)]``: h's rows are that many continuations of the first ``prefix``
    of the ``P`` positions, side by side, each under a window of its own and
    blind to the others (``forward``).  A
    block of queries is put to the keys up to its last query (and, under a
    window, from the first key its first query sees), a key/value head and
    half of its query heads at a time (a thread each)."""
    import jax.numpy as jnp

    n = h.shape[0]
    start = 0 if before is None else before[0].shape[0]
    group = n_heads // n_kv_heads
    halves = 2 if group % 2 == 0 else 1
    part = group // halves
    q = (h[q_from:] @ lp["wq"]).reshape(n - q_from, n_kv_heads, group, -1)
    k = (h @ lp["wk"]).reshape(n, n_kv_heads, -1)
    v = (h @ lp["wv"]).reshape(n, n_kv_heads, -1)
    d = k.shape[-1]
    scale = 1.0 / math.sqrt(d)
    out, lo = [], 0
    for count, win, start in branches or [(n, window, start)]:
        rows = slice(lo, lo + count)
        pos = start + jnp.arange(count)
        # (Queries from a later row on only where the rows are one run.)
        first_q = 0 if branches else q_from
        q_r, k_r, v_r = q[lo:lo + count - first_q], k[rows], v[rows]
        if rotate:
            q_r = rope_interleaved(q_r.reshape(-1, n_heads, d),
                                   pos[first_q:], theta).reshape(q_r.shape)
            k_r = rope_interleaved(k_r, pos, theta)
        if before is not None:
            k_r = jnp.concatenate([before[0][:start], k_r])
            v_r = jnp.concatenate([before[1][:start], v_r])
        block = _attention_block(win, scale)
        k_pos = jnp.arange(start + count)
        # [Hkv, halves, part, queries, D]: a task's query heads lie together.
        q_t = q_r.transpose(1, 2, 0, 3).reshape(
            n_kv_heads, halves, part, count - first_q, d)
        tasks = [(q_t[i, j], k_r[:, i], v_r[:, i])
                 for i in range(n_kv_heads) for j in range(halves)]
        for a in range(first_q, count, q_block):
            b = min(a + q_block, count)
            first = 0 if win is None else max(0, start + a - win + 1)
            keys = slice(first, start + b)
            got = list(_pool().map(
                lambda t: block(
                    t[0][:, a - first_q:b - first_q].reshape(-1, d),
                    t[1][keys], t[2][keys], jnp.tile(pos[a:b], part),
                    k_pos[keys]), tasks))
            # -> [queries, Hkv, group, D]
            out.append(jnp.stack(got).reshape(
                n_kv_heads, group, b - a, d).transpose(2, 0, 1, 3))
        lo += count
    return (jnp.concatenate(out).reshape(n - q_from, -1) @ lp["wo"],
            (k_r, v_r))


def swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


@functools.lru_cache(maxsize=None)
def _swiglu_jit():
    import jax

    return jax.jit(swiglu)


def prepare(p):
    """The weights as the passes below take them, made **once** and in
    place: every matrix an array of the host's device (handing a numpy array
    to a compiled product copies it, 19 GB a pass at the published widths:
    all of the 300 s the harness waits), a layer's held experts and its
    shared experts as lists of ``(W_g, W_u, W_d)``, matrices of their own:
    ``egu [E, d, 2f]`` holds an expert's gate columns, then its up columns;
    ``sgu [d, 2 n f]`` every shared expert's gate columns, then every one's
    up columns, and ``sd [n f, d]`` their down rows (models/cohere_moe.py).
    The float32 numpy leaves are let go one by one as they are replaced, so
    the host never holds the model twice.  Idempotent."""
    import jax.numpy as jnp

    if p.get("prepared"):
        return p

    def own(a):
        return jnp.asarray(np.ascontiguousarray(a))

    for lp in p["layers"]:
        egu, ed, sgu, sd = (lp.pop(k) for k in ("egu", "ed", "sgu", "sd"))
        f, fs = ed.shape[1], sgu.shape[1] // 2
        n = sd.shape[0] // f
        lp["experts"] = [(own(egu[e, :, :f]), own(egu[e, :, f:]), own(ed[e]))
                         for e in range(egu.shape[0])]
        lp["shared"] = [(own(sgu[:, i * f:(i + 1) * f]),
                         own(sgu[:, fs + i * f:fs + (i + 1) * f]),
                         own(sd[i * f:(i + 1) * f])) for i in range(n)]
        del egu, ed, sgu, sd
        for k in ("ln", "wq", "wk", "wv", "wo", "router"):
            lp[k] = own(lp[k])
    p["embed"], p["lnf"] = own(p["embed"]), own(p["lnf"])
    p["prepared"] = True
    return p


def route(lp, h, *, top_k, first, follow=None):
    """The router on normed rows h ``[n, d]``: (chosen ``[n, top_k]``, weights
    ``[n, top_k]``, flips ``[n]``).  ``s = sigmoid(h W_r)``, the ``top_k``
    largest, ``w = s / sum s`` over the chosen; no bias, no scale (assumed).
    ``follow`` ``[n, words]`` (int32 words of the served record, bit e of
    word w = held expert ``first + 32 w + e`` was chosen): the held experts
    take part as the words say, absent ones fill the other places by their
    own scores.  flips: how far from the edge between the top_k-th and the
    next score the farthest expert lies that the followed choice and the
    reference's own disagree about; 0 where they agree."""
    import jax

    s = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    order = np.argsort(-s, axis=-1, kind="stable")
    own = order[:, :top_k]
    held = len(lp["experts"])
    flips = np.zeros(len(s))
    chosen = own
    if follow is not None:
        words = np.asarray(follow, np.int64) & 0xFFFFFFFF
        want = ((words[:, np.arange(held) // 32] >> (np.arange(held) % 32))
                & 1).astype(bool)
        forced = s.copy()
        forced[:, first:first + held] += np.where(want, 4.0, -4.0)
        chosen = np.argsort(-forced, axis=-1, kind="stable")[:, :top_k]
        edge = np.take_along_axis(
            s, order[:, top_k - 1:top_k + 1], axis=-1).mean(-1)
        ours = np.zeros(s.shape, bool)
        np.put_along_axis(ours, own, True, axis=-1)
        theirs = np.zeros(s.shape, bool)
        np.put_along_axis(theirs, chosen, True, axis=-1)
        flips = np.where(ours != theirs, np.abs(s - edge[:, None]),
                         0.0).max(-1)
    picked = np.take_along_axis(s, chosen, axis=-1)
    return chosen, picked / picked.sum(-1, keepdims=True), flips


def experts(lp, h, chosen, weights, *, first):
    """``routed + shared`` for normed rows h ``[n, d]`` and a prepared layer:
    the held experts one at a time (a thread each), each on the rows that
    chose it (padded with zero rows to a few shapes, so the product is
    compiled a few times); the shared experts computed apart and
    averaged."""
    import jax.numpy as jnp

    run = _swiglu_jit()
    h_np = np.asarray(h)

    def one(e):
        tok, slot = np.nonzero(chosen == first + e)
        if not tok.size:
            return None
        pad = next((c for c in (16, 64, 256) if tok.size <= c),
                   -(-tok.size // 512) * 512)
        rows = np.zeros((pad, h_np.shape[1]), np.float32)
        rows[:tok.size] = h_np[tok]
        out = np.asarray(run(rows, *lp["experts"][e]))
        return tok, out[:tok.size] * weights[tok, slot][:, None]

    y = np.zeros(h_np.shape, np.float32)
    for got in _pool().map(one, range(len(lp["experts"]))):
        if got is not None:
            # (A token chooses an expert once: the rows are distinct.)
            y[got[0]] += got[1]
    shared = sum(run(h, *one_shared) for one_shared in lp["shared"])
    return jnp.asarray(y) + shared / len(lp["shared"])


def block(lp, x, *, q_from=0, eps, top_k, first, follow=None, **attn):
    """One parallel block: x ``[n, d]`` -> (the rows from ``q_from`` on after
    the block, (keys, values), chosen ``[n, top_k]``, flips ``[n]``)."""
    h = layer_norm(x, lp["ln"], eps)
    o, kv = attention(lp, h, q_from=q_from, **attn)
    chosen, weights, flips = route(lp, h, top_k=top_k, first=first,
                                   follow=follow)
    y = experts(lp, h[q_from:], chosen[q_from:], weights[q_from:],
                first=first)
    return x[q_from:] + o + y, kv, chosen, flips


def forward(p, ids, last, *, n_heads, n_kv_heads, window, kinds, top_k,
            first, theta, eps, logit_scale=1.0, follow=None,
            q_block=512, keep=None, prompt=None, branches=None):
    """Full context, no cache, no pieces.  ``ids`` [n] -> (logits of the
    ``last`` positions ``[last, vocab]``, chosen experts ``[layers, n,
    top_k]``, flips ``[n]``: the largest over the layers).  ``kinds``: a
    layer each, ``"ring"`` (a window layer: rotated, banded) or ``"rows"`` (a
    full one: neither); ``follow`` ``[n, layers, words]``: the served
    record's words.  ``keep=P`` returns, fourth, every layer's keys and
    values of the first ``P`` positions; handed back as ``prompt`` with
    ``branches`` ``[(rows, window, prefix)]``, the ids are several
    continuations, side by side, each of the first ``prefix`` of those
    positions, under its own window and blind to the others (everything but
    the attention is a row's own, so every matrix is read once for all of
    them).  The last layer computes the
    block for the ``last`` rows alone (module docstring); with ``branches``
    every row is asked for."""
    import jax.numpy as jnp

    n = ids.shape[0]
    p = prepare(p)
    x = p["embed"][ids]
    chosen, flips, kept = [], np.zeros(n), []
    for li, (lp, kind) in enumerate(zip(p["layers"], kinds)):
        ring = kind == "ring"
        at_end = li == len(kinds) - 1 and not branches
        x, (k, v), picked, flip = block(
            lp, x, q_from=n - last if at_end else 0, eps=eps, top_k=top_k,
            first=first,
            follow=None if follow is None else follow[:, li],
            n_heads=n_heads, n_kv_heads=n_kv_heads,
            window=window if ring else None, rotate=ring, theta=theta,
            q_block=q_block, before=None if prompt is None else prompt[li],
            branches=branches and [(c, w if ring else None, pre)
                                   for c, w, pre in branches])
        if keep is not None:
            kept.append((k[:keep], v[:keep]))
        chosen.append(picked)
        flips = np.maximum(flips, flip)
    logits = layer_norm(x[x.shape[0] - last:], p["lnf"],
                        eps) @ p["embed"].T * logit_scale
    out = (logits, np.stack(chosen), flips)
    return out if keep is None else (*out, kept)


def backend_forward(params, backend, ids, last, follow=None, q_block=512,
                    **kw):
    """``forward`` at the sizes a backend object states, **as the model is
    published**: three window layers then a full one whatever the backend
    serves (``published_kinds`` / ``published_window`` where a control of the
    comparison serves another: ``testdata/cohere_moe_controls.py``)."""
    return forward(params, ids, last, n_heads=backend.n_heads,
                   n_kv_heads=backend.n_kv_heads,
                   window=getattr(backend, "published_window",
                                  backend.window),
                   kinds=getattr(backend, "published_kinds",
                                 backend.layer_kinds),
                   top_k=backend.top_k, first=backend.first_expert,
                   theta=backend.rope_theta,
                   eps=backend.norm_eps, logit_scale=backend.logit_scale,
                   follow=follow, q_block=q_block, **kw)


def probe(server, cfg, traffic, seed) -> dict:
    """``kimi_linear``'s probe (the prompts streamed together, then one at a
    time, each asking for its record), with **prompts that are prefixes of
    one another**: the longest is drawn, the others are its first tokens.
    The server keeps no prefix (a slot is a stream's alone), so every stream
    is prefilled whole and decoded as any other; the reference's one pass
    over the longest prompt serves all of them (``check``), which is what
    lets a verdict on 18 000 prompt positions at the published widths fit
    the 300 s the harness waits."""
    lens = [int(n) for n in traffic["probe_prompt_lens"]]
    rng = np.random.default_rng([int(seed), 99])
    longest = rng.integers(0, int(cfg[cfg["wire"]["vocab"]]), max(lens))

    class Nested:
        """``default_rng`` as the probe uses it: the next prompt's ids."""

        def integers(self, low, high, n):
            return longest[:n]

    draw, np.random.default_rng = np.random.default_rng, lambda *_: Nested()
    try:
        return _kimi.probe(server, cfg, traffic, seed)
    finally:
        np.random.default_rng = draw


def check(params, probe, backend) -> dict:
    """``kimi_linear``'s ``judge`` on this family's forward pass and limits,
    and ``smallthinker``'s ``window_edge``.  The passes are made before the
    judging: **one pass over the longest prompt** (following its served
    routing) leaves every layer's keys and values; then **every stream's own
    positions** (its prompt's last token and the tokens it emitted) are
    computed in one pass, side by side, each against the keys of its own
    prompt's length (the prompts are prefixes of the longest: ``probe``), and
    a stream that reaches the window's edge under a window of one key fewer
    and of one more as well.  A stream whose prompt was routed otherwise than
    the longest's first positions (or is no prefix of it) gets a pass over
    its own prompt."""
    take_every_core()
    params = prepare(params)
    layers, words = len(params["layers"]), backend.held_words
    window = getattr(backend, "published_window", backend.window)
    streams = {}                         # (prompt, tokens, words) -> a group
    for prompt, pair in zip(probe["prompts"], zip(
            zip(probe["concurrent"], probe["concurrent_record"]),
            zip(probe["solo"], probe["solo_record"]))):
        for toks, record in pair:
            if isinstance(toks, dict) or np.shape(record)[0] != len(
                    prompt) + len(toks) - 1 or not toks:
                continue
            follow = _kimi.record_columns(record, layers * words)[0]
            streams.setdefault(
                (tuple(prompt), tuple(toks), follow.tobytes()),
                follow.reshape(-1, layers, words))
    # Groups: the streams whose prompts (ids and served routing) are the
    # first positions of one base prompt, the longest first.
    groups = []
    for key in sorted(streams, key=lambda k: -len(k[0])):
        n_p, follow = len(key[0]), streams[key]
        for base, base_follow, members in groups:
            if (base[:n_p] == key[0]
                    and (base_follow[:n_p - 1] == follow[:n_p - 1]).all()):
                members.append(key)
                break
        else:
            groups.append((key[0], follow[:n_p], [key]))
    done, edges = {}, {}
    for base, base_follow, members in groups:
        n_b = len(base)
        _, _, before, kept = backend_forward(
            params, backend, np.asarray(base[:-1], np.int32), 1,
            follow=base_follow[:n_b - 1], keep=n_b - 1)
        ids, follows, branches, spans = [], [], [], {}
        for key in members:
            prompt, toks = key[0], key[1]
            n_p = len(prompt)
            own = np.asarray(prompt[-1:] + toks[:-1], np.int32)
            edge = len(toks) > 1 and n_p + len(toks) - 1 >= window
            spans[key] = (len(branches), 3 if edge else 1)
            for w in [window] + ([window - 1, window + 1] if edge else []):
                ids.append(own)
                follows.append(streams[key][n_p - 1:])
                branches.append((own.size, w, n_p - 1))
        logits, _, flips = backend_forward(
            params, backend, np.concatenate(ids), sum(map(len, ids)),
            follow=np.concatenate(follows), prompt=kept, branches=branches)
        logits, at = np.asarray(logits), np.cumsum([0] + list(map(len, ids)))
        for key, (first, count) in spans.items():
            rows = [logits[at[i]:at[i + 1]] for i in range(first,
                                                            first + count)]
            n_p, toks = len(key[0]), key[1]
            done[key] = (rows[0], np.concatenate(
                [before[:n_p - 1], flips[at[first]:at[first + 1]]]))
            if count == 3:
                # The record's logits of a row: its emitted token's, then
                # the first ids'; of the rows behind the prompt's.
                def columns(r):
                    later = np.arange(1, len(toks))
                    return np.concatenate(
                        [r[later, toks[1:]][:, None], r[1:, :SAMPLES]],
                        axis=1).astype(np.float64)

                edges[key] = (columns(rows[0]), [columns(r)
                                                 for r in rows[1:]])

    def rows_fn(prompt, emitted, record_words):
        return done[tuple(prompt), tuple(emitted),
                    np.asarray(record_words).tobytes()]

    verdict = _kimi.judge(probe, rows_fn, layers * words, margin=MARGIN,
                          logit_rms_alone=LOGIT_RMS_ALONE,
                          logit_rms_together=LOGIT_RMS_TOGETHER,
                          logit_max=LOGIT_MAX, tie=TIE)
    if "tokens_checked" in verdict:
        lean = window_edge(probe, edges, layers * words)
        verdict.update(
            window_lean_fewer=lean[0], window_lean_more=lean[1],
            window_lean=WINDOW_LEAN, passes_over_a_prompt=len(groups),
            ok=bool(verdict["ok"] and max(lean) <= WINDOW_LEAN))
    return verdict


# -- operations and bytes of a step ------------------------------------------

def _dims(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, f = cfg["num_hidden_layers"], cfg["moe_intermediate_size"]
    n_window = sum(1 for s in cfg["sliding_window_layout"][:layers] if s)
    return {"d": d, "heads": h, "head_dim": hd, "row": hk * hd, "attn": d * hd * (2 * h + 2 * hk),
            "router": d * cfg["num_experts"], "expert": 3 * d * f,
            "shared": cfg["num_shared_experts"] * 3 * d * f,
            "layers": layers, "n_window": n_window,
            "n_global": layers - n_window,
            "window": cfg["sliding_window_size"],
            "held": cfg["n_routed_experts"],
            "chosen_here": (cfg["num_experts_per_tok"] / cfg["num_experts"]
                            * cfg["n_routed_experts"]),
            "vocab": cfg["vocab_size"]}


def decode_attention(cfg: dict, lanes: float, live_rows: float):
    """One layer's ``decode_wave_attention`` with grouped-query rows: each
    lane's live rows of K and of V (``Hkv x D`` values, bfloat16: 4 KB a row
    for the two) read once for all the heads and one row of each written; the
    useful products (a head's ``D`` features a score and a value, not the
    block-diagonal's ``Hkv``-fold).  (flops, bytes)."""
    m = _dims(cfg)
    return (float(4 * lanes * live_rows * m["heads"] * m["head_dim"]),
            float(2 * lanes * (live_rows + 1) * m["row"] * 2))


def window_attention(cfg: dict, lanes: float, ring_rows: float):
    """One window layer's ``window_wave_attention``: the same kernel over a
    ring, the lane's live ring rows read once, one written.  (flops,
    bytes)."""
    return decode_attention(cfg, lanes, ring_rows)


def expert_ffn(cfg: dict, pairs: float, touched: float, part: str = "both"):
    """One layer's grouped matmuls (``pangu_moe``'s count: ``pairs`` rows
    through an expert each, the ``touched`` experts' matrices read once,
    bfloat16, the rows in (bfloat16) and out (float32)).  (flops, bytes)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    up = (2 * pairs * 2 * d * f,
          touched * 2 * d * f * 2 + pairs * (d * 2 + 2 * f * 4))
    down = (2 * pairs * f * d,
            touched * f * d * 2 + pairs * (f * 2 + d * 4))
    flops, nbytes = {"up": up, "down": down,
                     "both": (up[0] + down[0], up[1] + down[1])}[part]
    return float(flops), float(nbytes)


def dense_products(cfg: dict, lanes: float):
    """A wave's dense products: every layer's four projections and its
    shared experts' pair, and the tied head over the vocabulary's slice, each
    weight read once (bfloat16), two operations a weight and live lane.
    (flops, bytes)."""
    m = _dims(cfg)
    weights = m["layers"] * (m["attn"] + m["shared"]) + m["d"] * m["vocab"]
    return float(2 * lanes * weights), float(2 * weights)


def decode_step(cfg: dict, lanes: float, rows_window: float,
                rows_global: float, pairs: float, touched: float):
    """One decode wave: ``lanes`` streams advance one token; each reads
    ``rows_window`` ring rows a window layer and ``rows_global`` rows a full
    layer (means a lane and layer); ``pairs`` (token, expert) pairs and
    ``touched`` experts' matrices a layer (means a layer).  Weights are
    bfloat16 but the float32 router; what one operation hands the next is not
    counted.  (flops, bytes)."""
    m = _dims(cfg)
    w_f, w_b = window_attention(cfg, lanes, rows_window)
    g_f, g_b = decode_attention(cfg, lanes, rows_global)
    e_f, e_b = expert_ffn(cfg, pairs, touched)
    d_f, d_b = dense_products(cfg, lanes)
    flops = (d_f + m["layers"] * (2 * lanes * m["router"] + e_f)
             + m["n_window"] * w_f + m["n_global"] * g_f)
    nbytes = (d_b + m["layers"] * (m["router"] * 4 + e_b)
              + m["n_window"] * w_b + m["n_global"] * g_b
              + lanes * m["d"] * 2)
    return float(flops), float(nbytes)


def piece_step(cfg: dict, positions: float, pairs_window: float,
               pairs_global: float, programs: float, heads: float = 0.0):
    """``programs`` piece programs that consumed ``positions`` valid prompt
    positions and scored ``pairs_window`` and ``pairs_global`` (query, key)
    pairs (each summed over the layers of its kind: counters
    ``prefill_pairs_window``, ``prefill_pairs_global``), ``heads`` of them
    with a head (``prefill_heads``).  Useful work only: two operations a
    weight and valid position for the projections, the router, the shared
    experts and the ``8 / 128 x 16`` held experts a position chooses; four a
    pair, head and lane of 128 for the attention; the tied head's product for
    one row a program that ran it.  Every held weight read once a program
    (the touched share taken as 1), the head's where it ran; cache rows are
    left out of the bytes.  (flops, bytes)."""
    m = _dims(cfg)
    per_position = m["layers"] * (m["attn"] + m["router"] + m["shared"]
                                  + m["chosen_here"] * m["expert"])
    flops = (2 * positions * per_position
             + 4 * (pairs_window + pairs_global) * m["heads"] * m["head_dim"]
             + 2 * heads * m["d"] * m["vocab"])
    nbytes = (programs * m["layers"] * (
        (m["attn"] + m["shared"] + m["held"] * m["expert"]) * 2
        + m["router"] * 4) + heads * m["d"] * m["vocab"] * 2)
    return float(flops), float(nbytes)


def _counters(ctx):
    import progspans

    w = progspans.window(ctx)
    if w is None:
        return None
    c = w["counters"]
    if not c.get("fetched_waves") or not c.get("fetched_lanes_live"):
        return None
    return c


def _traced_waves(ctx):
    """The decode waves of the traced seconds by the generator's token clock:
    (live lanes a wave, mean ring rows a live lane read in a window layer,
    mean rows in a full layer), or None where the run has no trace or no
    token fell into it.  The counters are the window's 50 s and the trace its
    last 4: with 24 lanes of which about 19 decode at a time, a 4 s sample's
    lanes lie several percent off the window's mean, and a kernel's share of
    its roofline is its bytes over **the traced calls'** time.  A token of
    ordinal k >= 1 of a stream with a prompt of P came from a wave at context
    ``P + k - 1``; the traced seconds are the harness's (``run.py``
    ``trace_window``: ``trace_seconds`` from ``t1 - trace_end_margin_s -
    trace_seconds``, later by the start call's own time).

    **None as well where the quotient exceeds the wave's capacity**
    (``serve.kwargs.max_streams``): a wave holds no more lanes than slots, so
    more tokens a ``jit_decode`` than slots means waves ran that the trace
    shows under another name, and a kernel's bytes at such lanes would
    flatter its share.  The window's counters are used then."""
    import reduce

    tr, ev = ctx.get("trace") or {}, None
    step = (tr.get("modules") or {}).get("jit_decode")
    if step and step.get("count") and "ev_t" in ctx:
        ev = reduce.stream_events(ctx)
    if ev is None:
        return None
    slot, t, ordinal = ev
    span = float(ctx["traffic"]["trace_seconds"])
    lo = (ctx["t1"] - float(ctx["traffic"]["trace_end_margin_s"]) - span
          + float(tr.get("start_call_s", 0.0)))
    hit = (t >= lo) & (t < lo + span) & (ordinal > 0)
    if not hit.any():
        return None
    lanes = float(hit.sum()) / step["count"]
    if lanes > int(ctx["cfg"]["serve"]["kwargs"]["max_streams"]):
        return None
    n = ctx["req"]["prompt_len"][slot[hit]] + ordinal[hit] - 1
    ring = np.minimum(n, _dims(ctx["cfg"])["window"] - 1)
    return lanes, float(ring.mean()), float(n.mean())


def wave_means(ctx, traced_seconds: bool = True):
    """Means over the decode waves, from the program's counters: (live lanes
    a wave, context positions a live lane, pairs a layer, experts touched a
    layer, waves), or None.  In a traced run the lanes and the context are
    the traced seconds' (``_traced_waves``) and the pairs follow the lanes:
    what a kernel's share of **the traced calls'** time wants.  With
    ``traced_seconds`` false, the window's counters alone."""
    c = _counters(ctx)
    if c is None or "expert_pairs_local" not in c:
        return None
    waves, lanes = c["fetched_waves"], c["fetched_lanes_live"]
    layers = _dims(ctx["cfg"])["layers"]
    pairs = c["expert_pairs_local"] / waves / layers
    touched = c["experts_touched"] / waves / layers
    traced = _traced_waves(ctx) if traced_seconds else None
    if traced is not None:
        return (traced[0], traced[2], pairs * traced[0] * waves / lanes,
                touched, waves)
    return (lanes / waves, c["fetched_positions_valid"] / lanes, pairs,
            touched, waves)


def rows_by_kind(ctx, traced_seconds: bool = True):
    """Mean rows a live lane read in one window layer and in one full layer
    of the decode waves (counters ``fetched_rows_window``,
    ``fetched_rows_global``; in a traced run the traced seconds' waves,
    ``_traced_waves``, unless ``traced_seconds`` is false), or None."""
    c = _counters(ctx)
    if c is None or "fetched_rows_window" not in c:
        return None
    traced = _traced_waves(ctx) if traced_seconds else None
    if traced is not None:
        return traced[1], traced[2]
    m = _dims(ctx["cfg"])
    lanes = c["fetched_lanes_live"]
    return (c["fetched_rows_window"] / lanes / m["n_window"],
            c["fetched_rows_global"] / lanes / m["n_global"])


def rows_per_wave(ctx):
    """``decode_attn_roofline``'s form, for the **full** layers' calls (the
    ones named ``decode_wave_attention`` in a trace): (0, rows a wave read in
    one full layer, waves), or None."""
    m, rows = wave_means(ctx), rows_by_kind(ctx)
    if m is None or rows is None:
        return None
    return 0.0, rows[1] * m[0], m[4]


def step_mix(ctx):
    """Decode cells: the window's waves as one mean step (live lanes, not the
    bucket; touched experts by the counter), by the window's counters in a
    traced run too: the whole step's share is taken over the counters'
    seconds, not over the traced calls."""
    m, rows = wave_means(ctx, False), rows_by_kind(ctx, False)
    if m is None or rows is None:
        return None
    return [(float(m[4]), decode_step(ctx["cfg"], m[0], rows[0], rows[1],
                                      m[2], m[3]))]


def prefill_work(ctx):
    """The window's piece programs by its counters, (flops, bytes) of all of
    them (``piece_step`` through ``reduce.pieces_work``), or None.  The pairs
    are the program's own counters; the parent of the PR that added them has
    them from the harness's table of prompts."""
    import reduce

    m = _dims(ctx["cfg"])
    return reduce.pieces_work(ctx, piece_step, m["n_window"], m["n_global"],
                              m["window"])


def wave_rows(cfg: dict) -> int:
    """Rows of the sorted layout of a full wave's grouped matmuls (the
    program's ``capacity_rows`` at its wave tile)."""
    lanes = int(cfg["serve"]["kwargs"]["max_streams"])
    tile = int(cfg["serve"]["expert_tile_rows"])
    held = int(cfg["n_routed_experts"])
    worst = lanes * min(int(cfg["num_experts_per_tok"]), held) \
        + held * (tile - 1)
    return -(-worst // tile) * tile
