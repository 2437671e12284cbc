"""``setup_s`` cut into the program's set-up timeline: the arithmetic of the
``.setup`` readers that PR 57 added.

The program's ``GET /v2/profile`` carries one list, ``startup``: the
launcher's phases (``startup.*``) and the three phases of every compilation
(``compile.trace``, ``compile.lower``, ``compile.backend``), each a span
relative to the launcher's entry, and ``startup_clock.entry_monotonic_s``:
that entry on ``time.monotonic()``.  The harness's own clock is the same one
(``CLOCK_MONOTONIC`` is the machine's), so ``ctx["t0"]`` (the window's start)
and ``ctx["t0"] - ctx["setup_s"]`` (the harness's launch) lie on the
program's timeline, and so does the pre-roll, which the traffic file fixes:
the load generators are released ``preroll_s + 0.25`` seconds before the
window opens (``run.py`` ``run_loadgen``).

Everything here reads ``ctx["snap_before"]``, the snapshot taken at the
window's start, which holds the whole launch.  A program without
``startup_clock`` (the parent of PR 57) gives ``None`` everywhere.
"""

from __future__ import annotations

import progspans
import tracereduce

PROCESS = ("startup.process", "startup.imports")
FIRST_RUN = "startup.first_run:"
FRONTENDS = "startup.frontends"
TRACE, LOWER, BACKEND = "compile.trace", "compile.lower", "compile.backend"
RELEASE_LEAD_S = 0.25  # run.py: t_zero = now + 0.25 + preroll_s


def spans(ctx) -> list[dict] | None:
    """The timeline's spans that ended before the window's start, each with
    ``a`` and ``b``: its start and end on the harness's clock."""
    profile = progspans._profile(ctx.get("snap_before"))
    clock = profile.get("startup_clock")
    if clock is None or profile.get("startup") is None:
        return None
    entry = float(clock["entry_monotonic_s"])
    out = [dict(s, a=entry + s["start_s"], b=entry + s["end_s"])
           for s in profile["startup"]]
    return [s for s in out if s["b"] <= ctx["t0"]]


def summed(ctx, *names: str, where=None):
    """Summed length of the spans whose name starts with one of ``names``
    (and satisfies ``where``); 0.0 where the program has the timeline and
    no such span."""
    all_spans = spans(ctx)
    if all_spans is None:
        return None
    return float(sum(s["b"] - s["a"] for s in all_spans
                     if s["name"].startswith(names)
                     and (where is None or where(s))))


def covered(intervals, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` under the union of ``intervals``."""
    return tracereduce.union_seconds(
        [(max(a, lo), min(b, hi)) for a, b in intervals
         if min(b, hi) > max(a, lo)])


def release(ctx) -> float:
    """When the window's load generators were let go: the pre-roll's start."""
    return ctx["t0"] - float(ctx["traffic"].get("preroll_s", 0)) \
        - RELEASE_LEAD_S


def warm_traffic(ctx, all_spans):
    """``(a, b)``: from the frontends' "serving" to the pre-roll's release."""
    up = [s["b"] for s in all_spans if s["name"] == FRONTENDS]
    if not up:
        return None
    return up[-1], max(up[-1], release(ctx))


def warm_traffic_s(ctx):
    """The warm traffic's interval less the compile spans inside it: the
    program serving the harness's warm round, and the load generators'
    two starts."""
    all_spans = spans(ctx)
    w = warm_traffic(ctx, all_spans) if all_spans is not None else None
    if w is None:
        return None
    compiling = [(s["a"], s["b"]) for s in all_spans
                 if s["name"].startswith("compile.")]
    return (w[1] - w[0]) - covered(compiling, *w)


def unspanned_s(ctx):
    """``setup_s`` less everything that has a name: the union of every span
    of the timeline, of the warm traffic and of the pre-roll, between the
    harness's launch and the window's start."""
    all_spans = spans(ctx)
    if all_spans is None:
        return None
    t0 = ctx["t0"]
    named = [(s["a"], s["b"]) for s in all_spans]
    w = warm_traffic(ctx, all_spans)
    if w is not None:
        named += [w, (w[1], t0)]
    return float(ctx["setup_s"]) - covered(named, t0 - ctx["setup_s"], t0)


def partition(ctx) -> dict | None:
    """Every ``.setup`` reading of one run, the frontends' bind and the
    pre-roll, for the paper check and for ``PERF.md``'s table: the parts are
    disjoint but for a compilation inside a phase that is not the warm-up
    (``startup.imports``, ``startup.model_load:*``: counted under the phase
    and under the compile span) and for two threads that compile at once
    (``overlap_s``), so ``sum - overlap_s + unspanned == setup_s``."""
    if spans(ctx) is None:
        return None
    snap = ctx.get("snap_before")
    parts = {
        "startup_process_s": summed(ctx, *PROCESS),
        "startup_backend_init_s": progspans.startup_seconds(
            snap, "startup.backend_init") or 0.0,
        "startup_model_load_s": progspans.startup_seconds(
            snap, "startup.model_load:") or 0.0,
        "startup_trace_s": summed(ctx, TRACE),
        "startup_lower_s": summed(ctx, LOWER),
        "startup_compile_s": summed(ctx, BACKEND),
        "startup_first_run_s": summed(ctx, FIRST_RUN),
        "frontends_s": summed(ctx, FRONTENDS),
        "setup_warm_traffic_s": warm_traffic_s(ctx) or 0.0,
        "preroll_s": ctx["t0"] - release(ctx),
    }
    unspanned = unspanned_s(ctx)
    total = sum(parts.values())
    return {**parts, "setup_unspanned_s": unspanned,
            "startup_cache_miss_s": summed(
                ctx, BACKEND, where=lambda s: s.get("cache") == "miss"),
            "setup_s": float(ctx["setup_s"]),
            "overlap_s": total + unspanned - float(ctx["setup_s"])}
