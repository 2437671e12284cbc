"""From raw results to what the metric readers read.

``context`` gathers one run's evidence into a dict: the load generator's
per-request clocks joined with the plan (lengths), the server's counters
read at both ends of the window, the compile log, and the reduced device
trace.  The functions below are the arithmetic the readers under
``benchmark/metrics/`` share; each reader is a few lines that pick one of
them.  A function that has nothing to read returns ``None`` and the harness
leaves the metric out.

Window rules: an open-loop request belongs to the window if it was *due*
inside it and is timed from its due time; a closed-loop request belongs if
it *completed* inside it.  A rate is all the window's work over all the
window's seconds; a tail is the tail of all the window's requests, a failed
or unfinished one counting as slower than any limit.
"""

from __future__ import annotations

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import family  # noqa: E402
import progspans  # noqa: E402
import roofline  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def context(cfg, traffic, seed, seconds, win, compile_times, device,
            trace) -> dict:
    load = win["load"]
    t0, t1 = load["t_zero"], load["t_end"]
    phases = ["preroll", "window"] if traffic["loop"] == "open" \
        else ["window"]
    plans = [traffic_mod.build_plan(cfg, traffic, seed, seconds, p)
             for p in phases]
    prompt = np.concatenate([p.prompt_len for p in plans])
    output = np.concatenate([p.output_len for p in plans])
    rows = plans[-1].rows
    g = np.asarray(load["gidx"], np.int64)
    req = {k: np.asarray(load[k], np.float64)
           for k in ("due", "sent", "first", "done", "engine_ms")}
    req["status"] = np.asarray(load["status"], np.int64)
    req["events"] = np.asarray(load["events"], np.int64)
    req["prompt_len"] = prompt[g]
    req["output_len"] = output[g]
    per_row = traffic.get("tokens_per_row")
    req["tokens"] = rows * (np.full(len(g), per_row) if per_row
                            else req["prompt_len"])
    finished = req["done"] > 0
    ok = finished & (req["status"] == 200)
    if traffic["loop"] == "open":
        inw = (req["due"] >= t0) & (req["due"] < t1)
        bad = inw & finished & ~ok
        if float(traffic.get("drain_s", 0)) > 0:
            bad |= inw & ~finished
    else:
        inw = finished & (req["done"] >= t0) & (req["done"] < t1)
        bad = inw & ~ok
    req["in_window"], req["ok"] = inw, ok
    before, after = win["snaps"].get("before"), win["snaps"].get("after")
    return {
        "cfg": cfg, "traffic": traffic, "seed": seed, "seconds": seconds,
        "t0": t0, "t1": t1, "req": req, "rows": rows,
        "ev_slot": np.asarray(load["ev_slot"], np.int64),
        "ev_t": np.asarray(load["ev_t"], np.float64),
        "attempted": int(inw.sum()), "failed": int(bad.sum()),
        "snap_before": before, "snap_after": after,
        "compile_times": list(compile_times), "device": device,
        "trace": trace, "reconnects": load.get("reconnects", 0),
    }


def pct(values, q):
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if values.size else None


# -- load generator clocks ----------------------------------------------------

def response_ms(ctx, upto="done"):
    """Open loop: milliseconds from a request's due time to its response
    (``done``) or its first streamed event (``first``), for every request
    due in the window; a failed or unfinished one counts as the whole
    window (slower than any limit)."""
    r = ctx["req"]
    inw = r["in_window"]
    if not inw.any():
        return None
    ms = (r[upto] - r["due"]) * 1e3
    good = r["ok"] if upto == "done" else (r["first"] > 0)
    ms = np.where(good, ms, ctx["seconds"] * 1e3)
    return ms[inw]


def late_ms(ctx):
    """How late the generator sent: send time less due time (open loop)."""
    r = ctx["req"]
    if ctx["traffic"]["loop"] != "open" or not r["in_window"].any():
        return None
    return ((r["sent"] - r["due"]) * 1e3)[r["in_window"]]


def tokens_per_s(ctx):
    """Tokens of the requests that completed inside the window, over the
    window."""
    r = ctx["req"]
    done = r["in_window"] & r["ok"]
    return float(r["tokens"][done].sum() / ctx["seconds"])


def stream_events(ctx):
    """(slot, time, ordinal within the stream) of every streamed event."""
    slot, t = ctx["ev_slot"], ctx["ev_t"]
    if slot.size == 0:
        return None
    order = np.lexsort((t, slot))
    slot, t = slot[order], t[order]
    first = np.r_[True, slot[1:] != slot[:-1]]
    start = np.maximum.accumulate(np.where(first, np.arange(slot.size), 0))
    return slot, t, np.arange(slot.size) - start


def itl_gaps_ms(ctx):
    """Gaps between consecutive streamed tokens of one stream, for every
    token that arrived inside the window."""
    ev = stream_events(ctx)
    if ev is None:
        return None
    slot, t, ordinal = ev
    later = (ordinal[1:] > 0) & (t[1:] >= ctx["t0"]) & (t[1:] < ctx["t1"])
    gaps = (t[1:] - t[:-1])[later] * 1e3
    return gaps if gaps.size else None


def mean_context(ctx):
    """Mean number of valid positions a decode step reads, over the tokens
    streamed in the window: prompt length plus tokens already emitted."""
    ev = stream_events(ctx)
    if ev is None:
        return None
    slot, t, ordinal = ev
    inw = (t >= ctx["t0"]) & (t < ctx["t1"]) & (ordinal > 0)
    if not inw.any():
        return None
    return float((ctx["req"]["prompt_len"][slot[inw]] + ordinal[inw]).mean())


def kv_live_share(ctx):
    """Percent of the key/value arena's rows (stream slots x positions)
    that held live context, averaged over the window's decode waves: the
    valid positions every streamed token's wave read, summed, over the
    waves the server counted and the arena's capacity."""
    ev, waves = stream_events(ctx), waves_delta(ctx)
    if ev is None or not waves:
        return None
    slot, t, ordinal = ev
    inw = (t >= ctx["t0"]) & (t < ctx["t1"]) & (ordinal > 0)
    read = float((ctx["req"]["prompt_len"][slot[inw]] + ordinal[inw]).sum())
    capacity = (int(ctx["cfg"]["serve"]["kwargs"]["max_streams"])
                * int(ctx["traffic"]["max_model_len"]))
    return 100.0 * read / sum(n for n, _ in waves.values()) / capacity


def open_streams_mean(ctx):
    """Time-average of streams in flight as the generator saw them."""
    r = ctx["req"]
    sent = r["sent"]
    end = np.where(r["done"] > 0, r["done"], ctx["t1"])
    overlap = np.clip(np.minimum(end, ctx["t1"])
                      - np.maximum(sent, ctx["t0"]), 0, None)
    return float(overlap.sum() / ctx["seconds"])


def outside_engine_ms(ctx):
    """Client time (send to response) less the server's own Server-Timing
    account of queue and compute: wire, codec and frontend."""
    r = ctx["req"]
    sel = r["in_window"] & r["ok"] & (r["engine_ms"] >= 0)
    if not sel.any():
        return None
    return ((r["done"] - r["sent"]) * 1e3 - r["engine_ms"])[sel]


def rejected_count(ctx):
    r = ctx["req"]
    return float((r["in_window"] & np.isin(r["status"], (429, 503))).sum())


# -- the server's counters, differenced over the window -----------------------

def stats_delta(ctx):
    a, b = ctx["snap_before"], ctx["snap_after"]
    if not a or not b:
        return None

    def totals(snap):
        """Counters summed over every served model."""
        t = {"queue_ns": 0, "queue_count": 0, "batches": {}}
        for s in snap["stats"]["model_stats"]:
            t["queue_ns"] += s["inference_stats"]["queue"]["ns"]
            t["queue_count"] += s["inference_stats"]["queue"]["count"]
            for x in s.get("batch_stats", []):
                k = int(x["batch_size"])
                t["batches"][k] = t["batches"].get(k, 0) + int(
                    x["compute_infer"]["count"])
        return t

    ta, tb = totals(a), totals(b)
    out = {k: tb[k] - ta[k] for k in ("queue_ns", "queue_count")}
    out["batches"] = {k: v - ta["batches"].get(k, 0)
                      for k, v in tb["batches"].items()
                      if v - ta["batches"].get(k, 0) > 0}
    return out


def waves_delta(ctx):
    """{bucket: (waves, host seconds)} of decode waves in the window."""
    a, b = ctx["snap_before"], ctx["snap_after"]
    if not a or not b:
        return None

    def waves(s):
        out = {}
        for m in s["profile"].get("models", {}).values():
            for w in m.get("decode_waves", []):
                k = int(w["bucket"])
                n, s_ = out.get(k, (0, 0.0))
                out[k] = (n + int(w["waves"]), s_ + float(w["device_s"]))
        return out

    wa, wb = waves(a), waves(b)
    d = {k: (v[0] - wa.get(k, (0, 0.0))[0], v[1] - wa.get(k, (0, 0.0))[1])
         for k, v in wb.items()}
    return {k: v for k, v in d.items() if v[0] > 0} or None


def rows_per_exec(ctx):
    d = stats_delta(ctx)
    if not d or not d["batches"]:
        return None
    return (sum(k * n for k, n in d["batches"].items())
            / sum(d["batches"].values()))


def padded_row_share(ctx):
    """Share of executed rows that were padding up to the batch bucket."""
    d = stats_delta(ctx)
    if not d or not d["batches"]:
        return None
    cap = int(ctx["cfg"]["serve"]["kwargs"].get("max_batch_size", 0)) or max(
        d["batches"])
    real = sum(k * n for k, n in d["batches"].items())
    run = sum(roofline.next_bucket(k, cap) * n
              for k, n in d["batches"].items())
    return 100.0 * (run - real) / run


def prefill_lane_fill(ctx):
    """Share of prefill lanes that held a prompt (cells with no decode
    wave: every execution is a prefill of ``prefill_lanes`` lanes)."""
    d = stats_delta(ctx)
    if not d or not d["batches"] or waves_delta(ctx):
        return None
    lanes = int(ctx["cfg"]["serve"]["prefill_lanes"])
    real = sum(k * n for k, n in d["batches"].items())
    return 100.0 * real / (lanes * sum(d["batches"].values()))


def compiles_in_window(ctx) -> int:
    return sum(1 for t in ctx["compile_times"] if ctx["t0"] <= t < ctx["t1"])


# -- the device trace ---------------------------------------------------------

def step_ms(ctx):
    """Mean device time of the cell's jitted step in the traced seconds."""
    tr = ctx["trace"]
    if not tr or not tr.get("modules"):
        return None
    m = tr["modules"].get(ctx["traffic"]["step_module"])
    return m["mean_ms"] if m else None


def counters_seconds(ctx):
    """Seconds the program's counters were differenced over: the harness's
    clock at the two snapshots (``snap["t"]``, read before each snapshot's
    requests).  The second snapshot comes when the load generator has ended,
    0.1-1.1 s after ``t1``, and the program works on meanwhile, so the
    counters hold a little more than the window's 50 s of work: over ``t1 -
    t0`` a rate would read up to 2% high.  ``t1 - t0`` where a snapshot has
    no clock (a hand-made context)."""
    a, b = ctx.get("snap_before") or {}, ctx.get("snap_after") or {}
    if "t" in a and "t" in b:
        return float(b["t"] - a["t"])
    if "t0" in ctx and "t1" in ctx:
        return float(ctx["t1"] - ctx["t0"])
    return None


def prefill_counts(ctx):
    """What the window's prefill programs did, by the program's counters:
    ``programs`` (the count of the span gen.prefill_dispatch), ``positions``
    (``prefill_positions_valid``: a piece's positions that held a prompt
    token), ``heads`` (``prefill_heads``: the programs whose head ran),
    ``pairs_window`` / ``pairs_global`` (the (query, key) pairs scored, where
    the backend declares them), ``pieces`` (``prefill_pieces``: a lane's
    piece each), ``lanes`` (``prefill_lanes_live``: prompts in one-shot
    programs).  None where the program has no such spans; a window
    that held no prefill program gives ``programs`` 0."""
    w = progspans.window(ctx)
    if w is None or "gen.prefill_dispatch" not in w["spans"]:
        return None
    c = w["counters"]
    return {"programs": w["spans"]["gen.prefill_dispatch"]["count"],
            "positions": c.get("prefill_positions_valid", 0),
            "heads": c.get("prefill_heads", 0),
            "pairs_window": c.get("prefill_pairs_window", 0),
            "pairs_global": c.get("prefill_pairs_global", 0),
            "pieces": c.get("prefill_pieces", 0),
            "lanes": c.get("prefill_lanes_live", 0)}


def window_prompts(ctx):
    """The harness's own table: the lengths of the prompts whose first token
    arrived inside the window (their prefill ended in it), or None."""
    r = ctx.get("req")
    if r is None or "t0" not in ctx:
        return None
    hit = (r["first"] >= ctx["t0"]) & (r["first"] < ctx["t1"])
    return r["prompt_len"][hit] if hit.any() else None


def causal_pairs(prompts, band: int | None = None):
    """(query, key) pairs one layer scores over a whole prompt of P tokens,
    for each P of ``prompts``: the lower triangle, ``P (P + 1) / 2``; with
    ``band`` (a query sees its own and the ``band - 1`` keys before it) the
    triangle up to the band and ``band`` a query after."""
    p = np.asarray(prompts, np.float64)
    if band is None:
        return p * (p + 1) / 2
    short = np.minimum(p, band)
    return short * (short + 1) / 2 + (p - short) * band


def pairs_a_position(ctx, band: int | None = None):
    """Mean pairs a prompt position scores in one layer, over the prompts of
    ``window_prompts``; a family multiplies it by the positions the program
    counted, so the table gives the shape and the counter the amount.  None
    where the context has no table."""
    prompts = window_prompts(ctx)
    if prompts is None:
        return None
    return float(causal_pairs(prompts, band).sum() / prompts.sum())


def pieces_work(ctx, piece_step, n_window: int = 0, n_global: int = 0,
                band: int | None = None):
    """The window's piece programs by its counters, (flops, bytes) of all of
    them through a family's ``piece_step(cfg, positions, pairs_window,
    pairs_global, programs, heads)``, or None where the window held none.
    The attention pairs are the program's own where it counts them
    (``prefill_pairs_window``, ``prefill_pairs_global``); else the counted
    positions times the pairs a position of the harness's table of prompts
    scores (``pairs_a_position``: the band of ``band`` keys in each of
    ``n_window`` layers, the triangle in each of ``n_global``), and left out
    where the context has no table: a floor, never a flattery."""
    n = prefill_counts(ctx)
    if n is None or not n["programs"]:
        return None
    ring, whole = n["pairs_window"], n["pairs_global"]
    if not ring and not whole:
        if n_window:
            ring = n["positions"] * n_window * (
                pairs_a_position(ctx, band) or 0.0)
        whole = n["positions"] * n_global * (pairs_a_position(ctx) or 0.0)
    return piece_step(ctx["cfg"], n["positions"], ring, whole,
                      n["programs"], n["heads"])


def step_mfu_roofline(ctx):
    """The share of the counters' seconds that the chip would need at its
    roofline for the useful work they count, in percent: the family's
    ``step_mix`` (the decode waves at their mean live lanes, rows and expert
    pairs: ``[(waves, (flops, bytes) of one)]``) and ``prefill_work`` (the
    prefill programs: ``(flops, bytes)`` of all of them, every held weight
    read once a program), each at ``roofline.min_seconds``, over
    ``counters_seconds``.  Counters and the harness's clock alone: no trace,
    no program's name, so it reads the same work whatever program did it.  A
    family without ``prefill_work`` has its prefill left out (a floor)."""
    seconds = counters_seconds(ctx)
    if not seconds or not ctx.get("cfg") or not ctx.get("device"):
        return None
    if ctx["device"].get("platform") == "cpu":      # a rehearsal: no chip,
        return None                                 # no share of its peak
    fam = family.load(ctx["cfg"]["family"])
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    waves = fam.step_mix(ctx)
    if not waves:
        return None
    least = sum(n * roofline.min_seconds(f, by, peaks)[0]
                for n, (f, by) in waves)
    work = fam.prefill_work(ctx) if hasattr(fam, "prefill_work") else None
    if work:
        least += roofline.min_seconds(*work, peaks)[0]
    return 100.0 * least / seconds


def kernel_groups(ctx, match,
                  old_calls_a_step: float = 1.0) -> list[tuple[float, float]]:
    """``[(device seconds, calls)]`` of the groups of ``jit_decode`` whose
    name ``match`` accepts, from the trace's table of every operation
    (``tracereduce``'s ``program_ops``): a call is an event the trace holds,
    whatever its rank among the operations.  Nothing to read gives ``[]``.

    A trace reduced before PR 39 (a kept ``context.json``; the tests under
    ``tests/``, which that PR could not edit) has no table, and its
    ``device_ops`` are its ten longest operations, not the breakdown's lines:
    each that ``match`` accepts is taken as ``old_calls_a_step`` calls a step
    of the program.  Where the table is, ``device_ops`` is printed, not read."""
    tr = ctx["trace"] or {}
    if "program_ops" in tr:
        return [(seconds, float(events)) for name, (seconds, events)
                in (tr["program_ops"].get("jit_decode") or {}).items()
                if match(name)]
    step = (tr.get("modules") or {}).get("jit_decode")
    if not step:
        return []
    return [(seconds, step["count"] * old_calls_a_step)
            for name, seconds in tr.get("device_ops") or [] if match(name)]


def device_idle_share(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]


def hbm_peak_bytes(ctx):
    devs = (ctx.get("memory") or {}).get("devices", [])
    peak = max([d.get("peak_bytes_in_use", 0) for d in devs] + [0])
    return float(peak) if peak else None
