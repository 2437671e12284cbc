#!/usr/bin/env python3
"""Checks ``tracereduce`` against traces whose figures are known.

1. A hand-made trace (an XSpace text proto written below): two programs on
   one device plane, operations that overlap and abut, a gap of known
   length.  Every figure can be worked out on paper, the table of every
   operation by program and group with them; a second one (``NESTED``) holds
   a ``while`` that encloses two operations and one that ran in no program.
2. ``recorded_v5e*.xplane.pb.gz``: pieces of real traces recorded on the TPU
   v5e by this benchmark, cut to their first programs to stay small
   (``cut_trace.py``).  Their figures were computed once by an independent
   brute-force method (a 1 ns occupancy raster, ``raster_busy_ns`` below)
   and are recomputed that way here as well, and so is every group's self
   time and event count (``raster_table``).
"""

import bisect
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import tracereduce as tr  # noqa: E402

HAND_MADE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_apply(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_prefill(77)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "copy-done.2" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "host work" } } }
"""


# A trace that starts and stops inside steps: the first and the last
# jit_decode are recorded only as far as the trace reaches (1 us each), the
# two between them whole (3 us each).
CLIPPED = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_decode(5)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.9" } }
}
"""


# jit_decode(9) runs [0, 10) us.  In it a ``while`` [1, 9) encloses fusion.3
# [2, 4) and a kernel [3, 6), which overlap without one enclosing the other,
# and fusion.4 [4.5, 5.5), which the kernel encloses: the while's own time is
# 8 - |[2, 6)| = 4 us, the kernel's 3 - 1 = 2, fusion.3 keeps its 2, fusion.4
# its 1: 9 us in all over a union of 8 (the overlap [3, 4) counts twice).
# fusion.3 runs once more at [12, 13), in no program.
NESTED = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 4500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_decode(9)" } }
  event_metadata { key: 2 value { id: 2
    name: "%while.2 = (s32[], bf16[8,16]{1,0}) while(%tuple.1)" } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.3 = f32[16,8]{1,0} fusion(%p.1), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4
    name: "%my_kernel.7 = bf16[2,8]{1,0} custom-call(%p.2)" } }
  event_metadata { key: 5 value { id: 5
    name: "%fusion.4 = f32[16,8]{1,0} fusion(%p.3), kind=kLoop" } }
}
"""


def raster_table(ops, mods) -> dict:
    """Independent of ``self_ns`` and ``program_of``: for every event the
    nanoseconds of its interval that no event it encloses covers, counted on
    a raster (of two events over one interval the first in the line's order
    encloses the second); its program by a scan of every module event."""
    table: dict = {}
    by_start = sorted((a, b, j) for j, (_, a, b) in enumerate(ops))
    for i, (name, s, e) in enumerate(ops):
        cover = bytearray(int(e - s))
        k = bisect.bisect_left(by_start, (s,))
        while k < len(by_start) and by_start[k][0] < e:
            a, b, j = by_start[k]
            if j != i and b <= e and ((a, b) != (s, e) or j > i):
                cover[int(a - s):int(b - s)] = b"\x01" * int(b - a)
            k += 1
        inside = [m for m, a, b in mods if a <= s and e <= b]
        program = tr.strip_hash(inside[-1]) if inside else tr.NO_PROGRAM
        cell = table.setdefault(program, {}).setdefault(
            tr.op_group(name), [0, 0])
        cell[0] += len(cover) - sum(cover)
        cell[1] += 1
    return table


def same_table(got: dict, want_ns: dict) -> bool:
    return (got.keys() == want_ns.keys() and all(
        got[p].keys() == want_ns[p].keys() and all(
            got[p][g][1] == n and near(got[p][g][0], ns / 1e9, 1e-12)
            for g, (ns, n) in want_ns[p].items()) for p in want_ns))


def raster_busy_ns(intervals) -> int:
    """Independent of union_seconds: mark every nanosecond that any
    interval covers, then count."""
    lo = min(s for s, _ in intervals)
    hi = max(e for _, e in intervals)
    cover = bytearray(int(hi - lo))
    for s, e in intervals:
        cover[int(s - lo):int(e - lo)] = b"\x01" * (int(e - lo) - int(s - lo))
    return sum(cover)


def near(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(1.0, abs(b))


def main() -> int:
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(HAND_MADE))
    r = tr.reduce_trace(pd)
    # on paper: ops cover [0,4) us, [6,7.5) us, [8,10) us = 7.5 us busy in
    # a window of 10 us; one 2 us gap before jit_prefill(77), none before
    # the third program (the second ends at 8 us, the third starts there).
    ok = (r["devices"] == 1 and near(r["window_s"], 10e-6)
          and near(r["busy_s"], 7.5e-6) and near(r["idle_share"], 0.25)
          and r["modules"]["jit_apply"]["count"] == 2
          and near(r["modules"]["jit_apply"]["mean_ms"], 0.003)
          and near(r["modules"]["jit_prefill"]["mean_ms"], 0.002)
          and r["idle_gaps"] == [["before_jit_prefill_77_", 2e-6]])
    print(("ok   " if ok else "FAIL ") + "hand-made trace: busy 7.5 us of "
          "10 us, idle 25%, one 2 us gap, step means 3 us and 2 us")
    if not ok:
        print(r)
        return 1
    # on paper: jit_apply's two runs hold fusion [0,1), [3,4), [8,10) and
    # copy-done [0.5,3) (it overlaps the first fusion and is not inside it);
    # jit_prefill holds copy-done [6,7.5).
    ok = (same_table(r["program_ops"], {
        "jit_apply": {"fusion": (4000, 3), "copy-done": (2500, 1)},
        "jit_prefill": {"copy-done": (1500, 1)}})
        and near(r["program_ops_union_s"]["jit_apply"], 6e-6)
        and r["device_ops"] == [["jit_apply/fusion_x2", 4e-6],
                                ["jit_apply/copy-done_x1", 2.5e-6],
                                ["jit_prefill/copy-done_x1", 1.5e-6]])
    print(("ok   " if ok else "FAIL ") + "hand-made trace: every operation "
          "by program and group, 6.5 us of jit_apply's groups over a union "
          "of 6 us; the breakdown names program, group and calls a program")
    if not ok:
        print(r)
        return 1
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(NESTED))
    r = tr.reduce_trace(pd)
    _, ops, mods = tr.device_lines(pd)[0]
    ok = (same_table(r["program_ops"], {
        "jit_decode": {"while_s32_": (4000, 1), "fusion_f32_16_8_": (3000, 2),
                       "my_kernel_bf16_2_8_": (2000, 1)},
        tr.NO_PROGRAM: {"fusion_f32_16_8_": (1000, 1)}})
        and same_table(r["program_ops"], raster_table(ops, mods))
        and near(r["program_ops_union_s"]["jit_decode"], 8e-6)
        and near(r["busy_s"], 9e-6))
    print(("ok   " if ok else "FAIL ") + "hand-made while: its own 4 us of "
          "8, not beside its body; two operations that overlap keep their "
          "lengths (9 us over a union of 8); one operation in no program")
    if not ok:
        print(r)
        return 1
    r = tr.reduce_trace(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(CLIPPED)))
    m = r["modules"]["jit_decode"]
    ok = (m["count"] == 4 and m["whole"] == 2 and near(m["mean_ms"], 0.003)
          and near(m["total_s"], 8e-6) and near(r["idle_share"], 0.0))
    print(("ok   " if ok else "FAIL ") + "clipped trace: four steps seen, "
          "two whole, step mean 3 us (not the 2 us of all four)")
    if not ok:
        print(r)
        return 1
    status = 0
    for name, (known_busy, known_window) in RECORDED.items():
        rec = os.path.join(HERE, name)
        if not os.path.exists(rec):
            print(f"FAIL the recorded trace {name} is missing")
            return 1
        pd = tr.load(rec)
        r = tr.reduce_trace(pd)
        _, events, mods = tr.device_lines(pd)[0]
        ops = [(s, e) for _, s, e in events]
        busy = raster_busy_ns(ops) / 1e9
        window = (max(e for _, e in ops) - min(s for s, _ in ops)) / 1e9
        table = r["program_ops"]
        groups = sum(len(g) for g in table.values())
        ok = (near(r["busy_s"], busy, 1e-6)
              and near(r["window_s"], window, 1e-6)
              and near(r["busy_s"], known_busy, 1e-6)
              and near(r["window_s"], known_window, 1e-6)
              and same_table(table, raster_table(events, mods))
              and sum(n for g in table.values() for _, n in g.values())
              == len(events)
              and all(near(sum(s for s, _ in table[p].values()), u, 1e-9)
                      for p, u in r["program_ops_union_s"].items()))
        print(("ok   " if ok else "FAIL ") + f"{name}: busy "
              f"{r['busy_s']:.9f} s of {r['window_s']:.9f} s (raster "
              f"{busy:.9f} of {window:.9f}; idle "
              f"{100 * r['idle_share']:.3f}%); {len(events)} operations in "
              f"{groups} groups of {sorted(table)}, each group's self time "
              f"and events as the raster gives them, summing to the "
              f"program's union of intervals")
        status |= 0 if ok else 1
    # The third recorded piece (gpt2_small.chat's programs and host spans,
    # PR 25) holds no operation line: an empty table, nothing to print.
    r = tr.reduce_trace(tr.load(os.path.join(
        HERE, "recorded_v5e_chat_hostspans.xplane.pb.gz")))
    ok = (r["program_ops"] == {} and r["device_ops"] == []
          and r["modules"]["jit_decode"]["count"] == 6 and r["busy_s"] > 0)
    print(("ok   " if ok else "FAIL ") + "recorded_v5e_chat_hostspans."
          "xplane.pb.gz: six programs, no operation line, an empty table")
    return status | (0 if ok else 1)


# Pieces of traces recorded on the TPU v5e by this benchmark (my chip runs,
# PR 23), cut by cut_trace.py, with (busy_s, window_s) from the raster
# method as noted when each piece was cut.
RECORDED = {
    # gpt2_small.longprompt, first 4 prefill programs: the device never idles
    "recorded_v5e.xplane.pb.gz": (0.048604087, 0.048653251),
    # bert_base.offline, first 12 programs: 44% idle between 16-row steps
    "recorded_v5e_offline.xplane.pb.gz": (0.02824886, 0.050751359),
}

if __name__ == "__main__":
    sys.exit(main())
