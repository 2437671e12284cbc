#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to device figures.

    JAX_PLATFORMS=cpu python3 benchmark/tracereduce.py <trace dir or .pb> <out.json> [--inspect]
    python3 benchmark/tracereduce.py --groups <out.json or a kept run's context.json> <program> [lines]

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
- ``program_ops``: every operation the trace holds, program -> group ->
  ``[seconds, events]``, complete.  An ``XLA Ops`` event belongs to the ``XLA
  Modules`` event of its plane that contains it (``NO_PROGRAM`` where none
  does); a group is the operation's name with XLA's serial number dropped
  plus its result shape (``latent_wave_attention_bf16_5_129_4096_640_``), so
  a layer's kernel is one group whatever the depth and whatever its rank.
  The seconds are **self time**: an event's length less the union of the
  events it encloses on the line, so a ``while`` does not stand beside its
  body; two events that overlap without one enclosing the other keep their
  own lengths.  ``program_ops_union_s`` is each program's union of operation
  intervals: what its groups' seconds sum to, but for such overlaps.
- ``device_ops``: what a run's ``breakdown`` prints of that table, at most
  ten lines (``breakdown_ops``).  Printed only: no reader takes a number from
  it (until PR 39 the key held the ten longest operations, which the readers
  read; ``reduce.kernel_groups`` says what is left of that).
- ``idle_gaps``: device idle time between consecutive programs, summed by
  the program that ended the gap (what the host was about to launch).

A trace with no device plane reduces to nothing (``{}``): the readers then
report nothing, and the harness leaves those metrics out.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
NO_PROGRAM = "no_program"
BREAKDOWN_LINES = 10        # the most a result line's breakdown may carry
BREAKDOWN_SHARE = 0.02      # programs under this share of the device's time


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


def op_group(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line (``%fusion.270 =
    f32[512,36864]{...} fusion(...), kind=...``): keep the operation's name
    without XLA's serial numbers (``.270``, and ``.68.remat2`` of a
    rematerialised copy) and its result shape, so that ``fusion.270`` and
    ``fusion.95`` of one shape are one group, ``fusion_f32_512_36864_``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
    base, shape = (m.group(1), " " + m.group(2)) if m else (name, "")
    return safe(re.sub(r"\.(\d+|remat\d*|clone)(?=\.|$)", "", base) + shape)


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


def self_ns(ops: list[tuple]) -> list[tuple]:
    """``[(name, start, end, self ns)]`` by start: an event's length less
    the union of the events it encloses (of two events over one interval
    the first encloses the second)."""
    ops = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    covered = [0] * len(ops)
    open_: list[list] = []      # [end, covered up to, row], by start
    for row, (_, s, e) in enumerate(ops):
        while open_ and open_[-1][0] <= s:
            open_.pop()
        for ent in open_:
            if ent[0] >= e > ent[1]:        # encloses it, and it adds cover
                covered[ent[2]] += e - max(s, ent[1])
                ent[1] = e
        open_.append([e, s, row])
    return [(name, s, e, e - s - c) for (name, s, e), c in zip(ops, covered)]


def program_of(mods: list[tuple]):
    """A function from an event's (start, end) to the stripped name of the
    program whose event contains it, or ``NO_PROGRAM``."""
    mods = sorted(mods, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    reach, far = [], float("-inf")      # the latest end up to each program
    for m in mods:
        far = max(far, m[2])
        reach.append(far)

    def find(s, e):
        i = bisect.bisect_right(starts, s) - 1
        while i >= 0 and reach[i] >= e:
            if mods[i][2] >= e:
                return strip_hash(mods[i][0])
            i -= 1
        return NO_PROGRAM
    return find


def breakdown_ops(program_ops: dict, counts: dict) -> list[list]:
    """The ``BREAKDOWN_LINES`` lines a run's breakdown shows: of each program
    over ``BREAKDOWN_SHARE`` of the device's time, longest first, its longest
    groups, the lines split evenly among the programs (what is left over to
    the longest).  A line is ``[program/group_x<calls a program>, seconds]``."""
    total = {p: sum(s for s, _ in g.values()) for p, g in program_ops.items()}
    floor = BREAKDOWN_SHARE * sum(total.values())
    shown = [p for p in sorted(total, key=lambda p: -total[p])
             if total[p] > 0 and total[p] >= floor][:BREAKDOWN_LINES]
    out = []
    for i, p in enumerate(shown):
        lines = BREAKDOWN_LINES // len(shown) \
            + (i < BREAKDOWN_LINES % len(shown))
        runs = max(1, counts.get(p, 1))
        for group, (seconds, events) in sorted(
                program_ops[p].items(), key=lambda kv: -kv[1][0])[:lines]:
            out.append([f"{p}/{group.rstrip('_')}_x"
                        f"{max(1, round(events / runs))}", seconds])
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
    program_ops: dict[str, dict[str, list]] = {}
    spans: dict[str, list] = {}
    gaps: dict[str, float] = {}
    for _, ops, mods in planes:
        find = program_of(mods)
        for name, s, e, own in self_ns(ops):
            program = find(s, e)
            cell = program_ops.setdefault(program, {}).setdefault(
                op_group(name), [0.0, 0])
            cell[0] += own / 1e9
            cell[1] += 1
            spans.setdefault(program, []).append((s, e))
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
        "program_ops": program_ops,
        "program_ops_union_s": {p: union_seconds(v) / 1e9
                                for p, v in spans.items()},
        "device_ops": breakdown_ops(
            program_ops, {k: len(v) for k, v in modules.items()}),
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


def print_groups(path: str, program: str, lines: int = 25) -> int:
    """What one program's time is made of, from a reduced trace: its groups
    in milliseconds a program, longest first."""
    with open(path) as f:
        red = json.load(f)
    red = red.get("trace") or red
    groups = (red.get("program_ops") or {}).get(program)
    if not groups:
        print(f"no operation of {program!r} in {path}; programs: "
              f"{sorted(red.get('program_ops') or {})}")
        return 1
    module = (red.get("modules") or {}).get(program) or {}
    runs = module.get("count", 1)
    total = sum(s for s, _ in groups.values())
    print(f"{program}: {runs} programs (clipped ones too), "
          f"{module.get('mean_ms')} ms a whole one; {len(groups)} groups, "
          f"{1e3 * total / runs:.3f} ms a program of self time over a union "
          f"of {1e3 * red['program_ops_union_s'][program] / runs:.3f}")
    for group, (seconds, events) in sorted(
            groups.items(), key=lambda kv: -kv[1][0])[:lines]:
        print(f"  {1e3 * seconds / runs:8.3f} ms  x{events / runs:<7.2f} "
              f"{100 * seconds / total:5.1f}%  {group}")
    return 0


def main() -> int:
    if sys.argv[1] == "--groups":
        return print_groups(sys.argv[2], sys.argv[3], *map(int, sys.argv[4:]))
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
