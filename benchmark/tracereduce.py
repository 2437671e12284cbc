#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to device figures.

    JAX_PLATFORMS=cpu python3 benchmark/tracereduce.py <trace dir or .pb> <out.json> [--inspect]

Reads the trace with ``jax.profiler.ProfileData`` (nothing but JAX) in a
child of its own; the harness parent never imports JAX.  A device plane is
one whose name starts with ``/device:TPU:``.  On it, the line ``XLA Ops``
holds one event per operation that ran on the device and the line ``XLA
Modules`` one event per jitted program (``jit_apply(...)``,
``jit_prefill(...)``, ``jit_decode(...)``).

- ``busy_s``: the union of the ``XLA Ops`` intervals, averaged over the
  device planes that ran anything; ``window_s``: from the first to the last
  device event over all planes; ``idle_share`` = 1 - busy / window.
- ``modules``: per program name (hash stripped) the count, total and mean
  device milliseconds: the time of the jitted step.  The trace starts and
  stops in the middle of steps, and the profiler records such a step only as
  far as the trace reaches; a clipped step is not a step's time, so the mean
  is over the ``whole`` events, those that touch neither end of the plane's
  trace (over all of them only where none is whole).  With steps of 0.9 s in
  a trace of 4 s the clipped ones are two of five or six.
- ``device_ops``: the ten operations with most device time.
- ``idle_gaps``: device idle time between consecutive programs, summed by
  the program that ended the gap (what the host was about to launch).

A trace with no device plane reduces to nothing (``{}``): the readers then
report nothing, and the harness leaves those metrics out.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


def find_trace(path: str) -> str | None:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb*")))
    return hits[-1] if hits else None


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def safe(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name)[:96]


def op_label(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line
    (``%fusion.5 = bf16[16,128]{...} fusion(...), kind=...``): keep the
    operation's name and its result shape."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
    return safe(f"{m.group(1)} {m.group(2)}") if m else safe(name)


def strip_hash(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals, in the
    intervals' unit."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def device_lines(pd):
    """[(plane name, ops events, module events)] with (name, start, end)
    tuples in nanoseconds."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif line.name == MODULES_LINE:
                mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        if ops or mods:
            out.append((plane.name, ops, mods))
    return out


def reduce_trace(pd) -> dict:
    planes = device_lines(pd)
    if not planes:
        return {}
    t_lo = min(e[1] for _, ops, mods in planes for e in (ops or mods))
    t_hi = max(e[2] for _, ops, mods in planes for e in (ops or mods))
    busy = [union_seconds([(s, e) for _, s, e in (ops or mods)])
            for _, ops, mods in planes]
    window_s = (t_hi - t_lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    modules: dict[str, list[float]] = {}
    whole: dict[str, list[float]] = {}
    op_time: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for _, ops, mods in planes:
        for name, s, e in ops:
            key = op_label(name)
            op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
        end = None
        lo = min(ev[1] for ev in ops + mods)
        hi = max(ev[2] for ev in ops + mods)
        for name, s, e in sorted(mods, key=lambda m: m[1]):
            modules.setdefault(strip_hash(name), []).append((e - s) / 1e6)
            if s > lo and e < hi:
                whole.setdefault(strip_hash(name), []).append((e - s) / 1e6)
            if end is not None and s > end:
                key = "before_" + safe(name)
                gaps[key] = gaps.get(key, 0.0) + (s - end) / 1e9
            end = e if end is None else max(end, e)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(planes),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "modules": {k: {"count": len(v), "total_s": sum(v) / 1e3,
                        "whole": len(whole.get(k, [])),
                        "mean_ms": (sum(whole[k]) / len(whole[k])
                                    if whole.get(k) else sum(v) / len(v))}
                    for k, v in modules.items()},
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in top_gaps],
    }


def inspect(pd, limit: int = 4) -> dict:
    """The trace's structure, for a human: planes, lines, first events."""
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({
                "line": line.name, "events": len(evs),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "duration_ns": e.duration_ns,
                           "stats": [[str(k), str(v)[:80]]
                                     for k, v in list(e.stats)[:12]]}
                          for e in evs[:limit]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def main() -> int:
    src, dst = sys.argv[1], sys.argv[2]
    path = find_trace(src)
    result: dict = {}
    if path is not None:
        pd = load(path)
        result = reduce_trace(pd)
        result["trace_bytes"] = os.path.getsize(path)
        if "--inspect" in sys.argv:
            result["inspect"] = inspect(pd)
    with open(dst, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
