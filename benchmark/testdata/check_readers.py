#!/usr/bin/env python3
"""Checks the prefill and stall readers (``metrics/prefill_step_device_ms``,
``prefill_device_share``, ``prefill_calls_per_s``, ``itl_stall_share``) on a
hand-made context whose figures can be worked out on paper, and the two
trace readers on ``recorded_v5e.xplane.pb.gz`` (four ``jit_prefill`` programs
of a v5e trace) against figures taken from the events directly.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import tracereduce as tr  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)

STEP, SHARE, CALLS, STALL = (
    "prefill_step_device_ms.itl", "prefill_device_share.itl",
    "prefill_calls_per_s.obs", "itl_stall_share.obs")


def near(a, b, tol=1e-9):
    return a is not None and abs(a - b) <= tol * max(1.0, abs(b))


def snap(prefill_calls):
    spans = {"gen.loop": {"count": 1000, "total_ns": 0, "max_ns": 0}}
    if prefill_calls is not None:
        spans["gen.prefill_dispatch"] = {"count": prefill_calls,
                                         "total_ns": 0, "max_ns": 0}
    return {"profile": {"models": {"gpt:1": {"generative": {
        "spans": spans, "counters": {}}}}}}


def hand_made_ctx():
    """Two streams in a window [10, 20): stream 0 streams a token every
    5 ms eight times and then one after 40 ms; stream 1 a first token, then
    gaps of 5, 5 and 30 ms.  Gaps: ten of 5 ms, 30 and 40: median 5, so the
    30 and the 40 are stalls, 70 of 120 ms."""
    t0 = 10.0
    a = t0 + np.r_[0.0, np.cumsum([0.005] * 8 + [0.040])]
    b = t0 + 1 + np.r_[0.0, np.cumsum([0.005, 0.005, 0.030])]
    return {
        "t0": 10.0, "t1": 20.0, "seconds": 10.0, "traffic": {},
        "ev_slot": np.r_[np.zeros(a.size, np.int64),
                         np.ones(b.size, np.int64)],
        "ev_t": np.r_[a, b],
        "snap_before": snap(100), "snap_after": snap(260),
        "trace": {"window_s": 4.0, "modules": {
            "jit_prefill": {"count": 64, "total_s": 1.4, "whole": 63,
                            "mean_ms": 21.9},
            "jit_decode": {"count": 380, "total_s": 1.6, "whole": 379,
                           "mean_ms": 4.1}}},
    }


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    return 0 if ok else 1


def main() -> int:
    status = 0
    ctx = hand_made_ctx()
    status |= check(near(reader(STALL)(ctx), 100 * 70 / 120),
                    "hand-made gaps: 70 of 120 ms lie in gaps over three "
                    "medians, 58.33%")
    status |= check(near(reader(CALLS)(ctx), 16.0),
                    "hand-made spans: 160 prefill calls in 10 s, 16 a second")
    status |= check(near(reader(STEP)(ctx), 21.9)
                    and near(reader(SHARE)(ctx), 35.0),
                    "hand-made trace: a whole prefill 21.9 ms, 1.4 s of 4 s "
                    "= 35%")
    bare = dict(ctx, trace=None, ev_slot=np.zeros(0, np.int64),
                ev_t=np.zeros(0), snap_before=snap(None),
                snap_after=snap(None))
    nothing = [reader(s)(bare) for s in (STEP, SHARE, CALLS, STALL)]
    no_prefill = dict(ctx, trace={"window_s": 4.0, "modules": {
        "jit_decode": ctx["trace"]["modules"]["jit_decode"]}})
    nothing += [reader(STEP)(no_prefill),
                reader(SHARE)(no_prefill)]
    status |= check(all(v is None for v in nothing),
                    "nothing to read gives None, never 0")

    pd = tr.load(os.path.join(HERE, "recorded_v5e.xplane.pb.gz"))
    _, ops, mods = tr.device_lines(pd)[0]
    pre = sorted((s, e) for name, s, e in mods
                 if name.startswith("jit_prefill"))
    lo = min(ev[1] for ev in ops + mods)
    hi = max(ev[2] for ev in ops + mods)
    whole = [(e - s) / 1e6 for s, e in pre if s > lo and e < hi]
    total_s = sum(e - s for s, e in pre) / 1e9
    window_s = (max(e for _, _, e in ops) - min(s for _, s, _ in ops)) / 1e9
    rec = {"traffic": {}, "trace": tr.reduce_trace(pd)}
    step = reader(STEP)(rec)
    share = reader(SHARE)(rec)
    status |= check(
        len(pre) == 4 and near(step, sum(whole) / len(whole), 1e-6)
        and near(share, 100 * total_s / window_s, 1e-6)
        and near(step, RECORDED_PREFILL_MS, 1e-6)
        and near(share, RECORDED_PREFILL_SHARE, 1e-6),
        f"recorded_v5e.xplane.pb.gz: {len(pre)} prefill programs, "
        f"{len(whole)} whole, {step!r} ms each, {share!r}% of the piece")
    return status


# From the piece's events, as noted when this check was written (PR 27): the
# two prefill programs that touch neither end last 11.769666 and 23.885473 ms;
# the four together 48.610864 ms of a piece of 48.653251 ms.
RECORDED_PREFILL_MS = 17.8275695
RECORDED_PREFILL_SHARE = 100 * 48.610864 / 48.653251

if __name__ == "__main__":
    sys.exit(main())
