#!/usr/bin/env python3
"""Checks the prefill and stall readers (``metrics/prefill_step_device_ms``,
``prefill_device_share``, ``prefill_calls_per_s``, ``itl_stall_share``) on a
hand-made context whose figures can be worked out on paper, and the two
trace readers on ``recorded_v5e.xplane.pb.gz`` (four ``jit_prefill`` programs
of a v5e trace) against figures taken from the events directly.

The four kernel readers (``KERNELS``) on a reduced trace in which every call
of the kernel is shorter than ten other operations (PR 39: what a faster
kernel makes of a trace): each reads its share from every call the table of
operations holds, 1 / 0.7 times as much with the calls 30% shorter, and
nothing only where the table holds no event of the kernel.  Then the trace
reduction's own check (``check_trace.py``) and the whole step's share of the
roofline's (``check_step_mfu.py``), so that the tests that run this file hold
them too.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import family  # noqa: E402
import roofline  # noqa: E402
import tracereduce as tr  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)
from traffic import load_json  # noqa: E402

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


# (metric, configuration, calls of the kernel a decode step, the window's
# counters over 1000 waves)
STEPS, KIND = 200, "TPU v5 lite"
PANGU = dict(fetched_lanes_live=125_000, fetched_positions_valid=187_500_000,
             expert_pairs_local=240_000, experts_touched=62_000)
KERNELS = [
    ("latent_attn_roofline.itl", "pangu_ultra_moe", 5, PANGU),
    ("expert_ffn_roofline.itl", "pangu_ultra_moe", 4, PANGU),
    ("kda_state_roofline.itl", "kimi_linear", 6,
     dict(fetched_lanes_live=250_000, fetched_positions_valid=975_000_000,
          expert_pairs_local=1_750_000, experts_touched=217_000)),
    ("decode_attn_roofline.itl", "evabyte_6b5", 8,
     dict(fetched_lanes_live=15_000, fetched_positions_valid=255_000_000,
          fetched_rows_exact=15_000_000, fetched_rows_summary=15_000_000)),
]


def kernel_parts(metric, cfg, counters):
    """{group of the kernel in ``jit_decode``: least seconds of one call at
    the counters' means}, by the family's cost functions."""
    fam = family.load(cfg["family"])
    peaks = roofline.peaks_for(KIND)
    lanes = counters["fetched_lanes_live"] / 1000
    rows = counters["fetched_positions_valid"] / counters["fetched_lanes_live"]
    if metric.startswith("decode_attn"):
        per_lane = (counters["fetched_rows_exact"]
                    + counters["fetched_rows_summary"]) / 1000 / lanes
        return {"decode_wave_attention_bf16_8_17_4096_4096_":
                roofline.min_seconds(
                    *fam.decode_attention(cfg, lanes, per_lane), peaks)[0]}
    if metric.startswith("kda_state"):
        return {"kda_wave_update_f32_6_257_32_128_128_":
                roofline.min_seconds(*fam.kda_update(cfg, lanes), peaks)[0]}
    if metric.startswith("latent_attn"):
        return {"latent_wave_attention_bf16_5_129_4096_640_":
                roofline.min_seconds(
                    *fam.latent_attention(cfg, lanes, rows), peaks)[0]}
    n_moe = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    pairs = counters["expert_pairs_local"] / 1000 / n_moe
    touched = counters["experts_touched"] / 1000 / n_moe
    width = {"up": 2 * int(cfg["moe_intermediate_size"]),
             "down": int(cfg["hidden_size"])}
    return {f"grouped_matmul_f32_{fam.wave_rows(cfg)}_{n}_":
            roofline.min_seconds(
                *fam.expert_ffn(cfg, pairs, touched, part), peaks)[0]
            for part, n in width.items()}


def kernel_ctx(cfg, counters, kernel_groups):
    """1000 waves in the window, all of the full bucket; a trace of 200 decode
    steps whose ten longest groups are other operations; the kernel's name
    once more in ``jit_prefill``, which no reader may count."""
    lanes = int(cfg["serve"]["kwargs"]["max_streams"])

    def snap(c, waves):
        return {"profile": {"models": {"m:1": {
            "generative": {"spans": {}, "counters": c},
            "decode_waves": [{"bucket": lanes, "waves": waves,
                              "device_s": 0.0}]}}}}
    after = dict(counters, fetched_waves=1000)
    table = {"jit_decode": {f"fusion_f32_{i}_": [10.0 + i, STEPS]
                            for i in range(10)},
             "jit_prefill": {g: [9.0, 7] for g in kernel_groups}}
    table["jit_decode"].update(kernel_groups)
    return {"cfg": cfg, "traffic": {}, "device": {"kind": KIND},
            "snap_before": snap(dict.fromkeys(after, 0), 0),
            "snap_after": snap(after, 1000),
            "trace": {"window_s": 4.0, "program_ops": table,
                      "modules": {"jit_decode": {"count": STEPS}},
                      "device_ops": tr.breakdown_ops(
                          table, {"jit_decode": STEPS, "jit_prefill": 7})}}


def kernel_readers() -> int:
    status = 0
    for metric, config, calls, counters in KERNELS:
        cfg = load_json(os.path.join(BENCH, "configs", config + ".json"))
        least = kernel_parts(metric, cfg, counters)
        events = calls * STEPS

        def groups(scale):          # every call at twice its least time
            return {g: [scale * 2 * t * events, events]
                    for g, t in least.items()}
        ctx = kernel_ctx(cfg, counters, groups(1.0))
        longest = sorted(s for s, _ in ctx["trace"]["program_ops"][
            "jit_decode"].values())[-10:]
        got = reader(metric)(ctx)
        faster = reader(metric)(kernel_ctx(cfg, counters, groups(0.7)))
        shown = tuple("jit_decode/" + g.rstrip("_") for g in least)
        status |= check(
            min(longest) > max(s for s, _ in groups(1.0).values())
            and not any(line.startswith(shown)
                        for line, _ in ctx["trace"]["device_ops"])
            and near(got, 50.0) and near(faster, 50.0 / 0.7)
            and reader(metric)(kernel_ctx(cfg, counters, {})) is None
            and reader(metric)(dict(ctx, trace=None)) is None,
            f"{metric} on {config}: {calls} calls a step, every one below "
            f"the tenth longest operation, read {got!r}% from "
            f"{events * len(least)} events; 30% shorter calls {faster!r}%; "
            f"no event of the kernel in jit_decode: nothing")
    return status


def main() -> int:
    status = kernel_readers()
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
    import check_step_mfu
    import check_trace

    return status | check_trace.main() | check_step_mfu.main()


# From the piece's events, as noted when this check was written (PR 27): the
# two prefill programs that touch neither end last 11.769666 and 23.885473 ms;
# the four together 48.610864 ms of a piece of 48.653251 ms.
RECORDED_PREFILL_MS = 17.8275695
RECORDED_PREFILL_SHARE = 100 * 48.610864 / 48.653251

if __name__ == "__main__":
    sys.exit(main())
