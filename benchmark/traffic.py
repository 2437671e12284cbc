"""The one general traffic generator.  A traffic mix is a data file under
``benchmark/traffic/``; this module turns (file, configuration, seed,
seconds) into a plan: request bodies, due times and lengths.

What makes runs repeat: **the seed permutes, it does not resample.**
Request ``k`` of ``N`` takes the ``(k + 0.5) / N`` quantile of the file's
length distribution, so every seed offers the same multiset of lengths, the
same request count and the same token total; the seed shuffles the order,
draws the token ids and places the arrivals.  Open-loop arrivals are ``N =
rate x seconds`` sorted uniform draws over the window (a Poisson process
conditioned on its count).

Never imports JAX; numpy only.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import family  # noqa: E402

PHASES = ("warmup", "preroll", "window")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def apply_rehearsal(d: dict, rehearse: bool) -> dict:
    """A file may carry ``rehearse_cpu`` overrides (tiny sizes for
    debugging on the CPU); they apply only with ``--rehearse-cpu``."""
    d = dict(d)
    over = d.pop("rehearse_cpu", None) or {}
    if not rehearse:
        return d
    for key, value in over.items():
        if key.startswith("serve_") and "serve" in d:
            serve, sub = dict(d["serve"]), key[len("serve_"):]
            serve[sub] = ({**serve.get(sub, {}), **value}
                          if isinstance(value, dict) else value)
            d["serve"] = serve
        else:
            d[key] = value
    return d


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths, the (k+0.5)/n quantiles of ``dist``, ascending."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        vals = np.full(n, dist["value"], np.float64)
    elif kind == "uniform":
        vals = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "loguniform":
        vals = dist["min"] * (dist["max"] / dist["min"]) ** q
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.rint(vals).astype(np.int64)


def _rng(seed: int, phase: str, what: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), PHASES.index(phase), what])


class Plan:
    """Requests of one phase: parallel arrays plus encoded bodies."""

    def __init__(self, due, prompt_len, output_len, bodies, rows,
                 body_index):
        self.due = due                  # seconds from window start (open)
        self.prompt_len = prompt_len    # per distinct request
        self.output_len = output_len
        self.bodies = bodies            # encoded HTTP requests (distinct)
        self.rows = rows                # rows per request (infer)
        self.body_index = body_index    # request k -> distinct body

    def __len__(self):
        return len(self.body_index)

    def multiset(self):
        """What every seed must offer alike: (count, sorted prompt
        lengths, sorted output lengths, token total)."""
        p = np.sort(self.prompt_len[self.body_index])
        o = np.sort(self.output_len[self.body_index])
        return len(self), p.tolist(), o.tolist(), int(p.sum() * self.rows)


def _bodies(cfg, traffic, prompt_len, output_len, rng):
    """Encoded requests, by the configuration's model family."""
    fam = family.load(cfg["family"])
    rows = int(traffic.get("rows_per_request", 1))
    model = cfg["serve"]["model_name"]
    return [fam.encode_request(cfg, model, rows, int(n), int(m), rng)
            for n, m in zip(prompt_len, output_len)]


def build_plan(cfg: dict, traffic: dict, seed: int, seconds: float,
               phase: str) -> Plan:
    """The requests of one phase.

    - ``window`` (open): N = rate x seconds requests due in [0, seconds).
    - ``preroll`` (open): rate x preroll_s requests due in [-preroll_s, 0).
    - closed loops: one cycle of ``cycle_requests`` distinct requests that
      the clients walk round and round; ``preroll`` and ``window`` are one
      continuous run, so ``preroll`` has no plan of its own.
    - ``warmup``: ``warmup.requests`` of the window's own requests, evenly
      spaced in order of prompt length with the shortest and the longest
      included, all sent at once (closed, ``warmup.requests`` clients);
      ``warmup.max_tokens``, if given, replaces their output lengths in
      order (a ladder that walks the live-stream count down).
    """
    rows = int(traffic.get("rows_per_request", 1))
    loop = traffic["loop"]
    if loop == "open":
        span = seconds if phase != "preroll" else float(traffic["preroll_s"])
        n = int(round(traffic["rate_per_s"] * span))
    else:
        n = int(traffic["cycle_requests"])
    if phase == "warmup":
        base_n = n if loop == "closed" else int(round(
            traffic["rate_per_s"] * seconds))
        full_p = quantile_lengths(traffic["prompt_len"], base_n)
        w = traffic.get("warmup", {})
        k = min(int(w.get("requests", 8)), base_n)
        pick = np.unique(np.rint(np.linspace(0, base_n - 1, k)).astype(int))
        prompt_len = full_p[pick]
        out_q = quantile_lengths(traffic["output_len"], base_n)
        output_len = out_q[pick]
        if w.get("max_tokens"):
            ladder = np.asarray(w["max_tokens"], np.int64)
            output_len = ladder[np.arange(len(pick)) % len(ladder)]
        rng = _rng(seed, phase, 0)
        bodies = _bodies(cfg, traffic, prompt_len, output_len, rng)
        return Plan(np.zeros(len(pick)), prompt_len, output_len,
                    bodies, rows, np.arange(len(pick)))
    prompt_len = quantile_lengths(traffic["prompt_len"], n)
    output_len = quantile_lengths(traffic["output_len"], n)
    # Independent shuffles: long prompts do not always get long outputs.
    prompt_len = prompt_len[_rng(seed, phase, 1).permutation(n)]
    output_len = output_len[_rng(seed, phase, 2).permutation(n)]
    bodies = _bodies(cfg, traffic, prompt_len, output_len,
                     _rng(seed, phase, 3))
    if loop == "open":
        lo, hi = (0.0, seconds) if phase == "window" else (
            -float(traffic["preroll_s"]), 0.0)
        due = np.sort(_rng(seed, phase, 4).uniform(lo, hi, n))
    else:
        due = np.zeros(n)
    return Plan(due, prompt_len, output_len, bodies, rows,
                np.arange(n))
