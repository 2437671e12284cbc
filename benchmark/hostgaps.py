#!/usr/bin/env python3
"""Whose time is the device's idle time?  One ``.xplane.pb``, the device's
idle gaps summed by the host span of the program that covers each.

    JAX_PLATFORMS=cpu python3 benchmark/hostgaps.py <trace dir or .pb[.gz]> [out.json]

While a device trace is active the program writes its own spans into it as
``TraceAnnotation``s (``client_tpu/observability/spans.py``): ``gen.*`` for
the phases of the generative worker's loop, ``exec.*`` for the batcher's
stage / run / fetch.  They land on a host plane, on the same clock as the
device planes' ``XLA Modules`` and ``XLA Ops`` events, so a gap in which the
device ran nothing can be named by what the host was doing in it and not,
as ``tracereduce.py`` has to, by the program that ended it.

- A gap is the time between consecutive programs on a device plane's ``XLA
  Modules`` line (from the latest end so far to the next start).
- Each nanosecond of a gap goes to the innermost (shortest) host span that
  covers it, over all host threads; what no span covers is ``no span``.
- ``by_span``: seconds and the number of gaps in which the name took the
  largest part, largest first.

Not wired into ``run.py`` yet (an edit to the harness, so a ``benchmark``
issue's): run it on the trace ``run.py --artifacts DIR --trace 1`` keeps.
A trace with no device plane, or from a program without the annotations,
reduces to gaps under ``no span`` alone.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tracereduce as tr  # noqa: E402

SPAN_PREFIXES = ("gen.", "exec.")
NO_SPAN = "no span"


def host_spans(pd) -> list[tuple[str, int, int]]:
    """(name, start ns, end ns) of every program span on a host plane."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns)))
    return sorted(out, key=lambda s: s[1])


def idle_gaps(mods) -> list[tuple[int, int]]:
    """[(start, end)] between consecutive programs of one device plane."""
    gaps, end = [], None
    for _, s, e in sorted(mods, key=lambda m: m[1]):
        if end is not None and s > end:
            gaps.append((int(end), int(s)))
        end = e if end is None else max(end, e)
    return gaps


def attribute(gap: tuple[int, int], names, starts, ends) -> dict[str, int]:
    """Nanoseconds of one gap by the innermost covering span's name."""
    a, b = gap
    hit = np.nonzero((starts < b) & (ends > a))[0]
    out: dict[str, int] = {}
    if hit.size == 0:
        return {NO_SPAN: b - a}
    cuts = sorted({a, b, *(int(t) for t in starts[hit] if a < t < b),
                   *(int(t) for t in ends[hit] if a < t < b)})
    for lo, hi in zip(cuts, cuts[1:]):
        cover = hit[(starts[hit] <= lo) & (ends[hit] >= hi)]
        if cover.size:
            inner = cover[np.argmin(ends[cover] - starts[cover])]
            name = names[inner]
        else:
            name = NO_SPAN
        out[name] = out.get(name, 0) + hi - lo
    return out


def reduce_gaps(pd) -> dict:
    spans = host_spans(pd)
    names = [s[0] for s in spans]
    starts = np.asarray([s[1] for s in spans], np.int64)
    ends = np.asarray([s[2] for s in spans], np.int64)
    by: dict[str, list] = {}
    n_gaps, idle_ns = 0, 0
    for _, _, mods in tr.device_lines(pd):
        for gap in idle_gaps(mods):
            parts = attribute(gap, names, starts, ends)
            n_gaps += 1
            idle_ns += gap[1] - gap[0]
            top = max(parts, key=parts.get)
            for name, ns in parts.items():
                row = by.setdefault(name, [0, 0])
                row[0] += ns
                row[1] += name == top
    seen: dict[str, int] = {}
    for name in names:
        seen[name] = seen.get(name, 0) + 1
    return {
        "gaps": n_gaps,
        "idle_s": idle_ns / 1e9,
        "by_span": [[name, ns / 1e9, n] for name, (ns, n) in
                    sorted(by.items(), key=lambda kv: -kv[1][0])],
        "host_spans": seen,
    }


def main() -> int:
    path = tr.find_trace(sys.argv[1])
    if path is None:
        print(f"no trace under {sys.argv[1]}", file=sys.stderr)
        return 1
    result = reduce_gaps(tr.load(path))
    print(f"{result['gaps']} idle gaps, {result['idle_s']:.9f} s; host "
          f"spans in the trace: {json.dumps(result['host_spans'])}")
    for name, seconds, n in result["by_span"]:
        print(f"  {name:24s} {seconds:.9f} s  largest part of {n} gap(s)")
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
