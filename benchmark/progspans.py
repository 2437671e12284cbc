"""What the program's own clock says: the ``generative``, ``compiles`` and
``startup`` objects of ``GET /v2/profile``, which the harness snapshots at
both ends of the window (``ctx["snap_before"]``, ``ctx["snap_after"]``).

``generative`` holds, per generative model, loop-phase spans (``gen.loop``
and its children: count, total ns, max ns) and cumulative lane counters;
``compiles`` every XLA backend compilation the program's ``jax.monitoring``
listener heard; ``startup`` the set-up phases as (name, start_s, end_s).  All
are cumulative and monotone, so the window is the difference of two
snapshots.  A program that has none of them (the parent of the PR that added
them) gives ``None`` everywhere, and the readers report nothing.
"""

from __future__ import annotations


def _profile(snap) -> dict:
    return (snap or {}).get("profile") or {}


def generative(snap) -> dict | None:
    """Spans and counters summed over every generative model served."""
    spans: dict[str, dict] = {}
    counters: dict[str, int] = {}
    seen = False
    for m in _profile(snap).get("models", {}).values():
        g = m.get("generative")
        if not g:
            continue
        seen = True
        for name, s in g.get("spans", {}).items():
            t = spans.setdefault(name, {"count": 0, "total_ns": 0,
                                        "max_ns": 0})
            t["count"] += int(s["count"])
            t["total_ns"] += int(s["total_ns"])
            t["max_ns"] = max(t["max_ns"], int(s["max_ns"]))
        for name, v in g.get("counters", {}).items():
            counters[name] = counters.get(name, 0) + int(v)
    return {"spans": spans, "counters": counters} if seen else None


def window(ctx) -> dict | None:
    """``generative`` over the window: after less before (``max_ns`` is the
    run's, it does not difference)."""
    a, b = generative(ctx.get("snap_before")), generative(ctx.get("snap_after"))
    if a is None or b is None:
        return None
    spans = {}
    for name, s in b["spans"].items():
        before = a["spans"].get(name, {"count": 0, "total_ns": 0})
        spans[name] = {"count": s["count"] - before["count"],
                       "total_ns": s["total_ns"] - before["total_ns"],
                       "max_ns": s["max_ns"]}
    counters = {k: v - a["counters"].get(k, 0)
                for k, v in b["counters"].items()}
    return {"spans": spans, "counters": counters}


def span_ns(w: dict, name: str) -> int:
    return w["spans"].get(name, {}).get("total_ns", 0)


def ratio(num, den, scale: float = 1.0):
    return scale * num / den if den else None


def counter_ratio(ctx, num: str, den: str, scale: float = 1.0):
    w = window(ctx)
    if w is None:
        return None
    c = w["counters"]
    return ratio(c.get(num, 0), c.get(den, 0), scale)


def compiles(snap) -> dict | None:
    return _profile(snap).get("compiles")


def startup_seconds(snap, prefix: str):
    """Summed length of the set-up spans whose name starts with ``prefix``."""
    spans = _profile(snap).get("startup")
    if spans is None:
        return None
    hit = [s["end_s"] - s["start_s"] for s in spans
           if s["name"].startswith(prefix)]
    return sum(hit) if hit else None
