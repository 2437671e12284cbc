#!/usr/bin/env python3
"""Checks ``tracereduce`` against traces whose figures are known.

1. A hand-made trace (an XSpace text proto written below): two programs on
   one device plane, operations that overlap and abut, a gap of known
   length.  Every figure can be worked out on paper.
2. ``recorded_v5e*.xplane.pb.gz``: pieces of real traces recorded on the TPU
   v5e by this benchmark, cut to their first programs to stay small
   (``cut_trace.py``).  Their figures were computed once by an independent
   brute-force method (a 1 ns occupancy raster, ``raster_busy_ns`` below)
   and are recomputed that way here as well.
"""

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
          and r["idle_gaps"] == [["before_jit_prefill_77_", 2e-6]]
          and sorted(n for n, _ in r["device_ops"])
          == ["copy-done.2", "fusion.1"]
          and all(near(t, 4e-6) for _, t in r["device_ops"]))
    print(("ok   " if ok else "FAIL ") + "hand-made trace: busy 7.5 us of "
          "10 us, idle 25%, one 2 us gap, step means 3 us and 2 us")
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
        ops = [(s, e) for _, s, e in tr.device_lines(pd)[0][1]]
        busy = raster_busy_ns(ops) / 1e9
        window = (max(e for _, e in ops) - min(s for s, _ in ops)) / 1e9
        ok = (near(r["busy_s"], busy, 1e-6)
              and near(r["window_s"], window, 1e-6)
              and near(r["busy_s"], known_busy, 1e-6)
              and near(r["window_s"], known_window, 1e-6))
        print(("ok   " if ok else "FAIL ") + f"{name}: busy "
              f"{r['busy_s']:.9f} s of {r['window_s']:.9f} s (raster "
              f"{busy:.9f} of {window:.9f}; idle "
              f"{100 * r['idle_share']:.3f}%)")
        status |= 0 if ok else 1
    return status


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
