#!/usr/bin/env python3
"""Checks what PR 43 added to the yardstick, on the CPU:

    python3 benchmark/testdata/check_smallthinker.py            # readers only
    python3 benchmark/testdata/check_smallthinker.py --rehearse # and the cell

1. The four new readers (``window_attn_roofline.itl``,
   ``cache_rows_window_share.obs``, ``lanes_past_window_share.obs``,
   ``cache_rows_read_share.itl``) and the accepted ``decode_attn_roofline.itl``
   on a hand-made context whose figures can be worked out on paper, and on a
   context of a program that has none of what they read (the parent of PR
   43): nothing, never 0 and never an error.
2. The family's arithmetic against the issue's reckoning of a wave.
3. With ``--rehearse``: the cell ``smallthinker_21b.mixed`` end to end at the
   configuration's ``rehearse_cpu`` sizes (a rehearsal proves nothing about
   the chip: control flow, the final line's keys, every listed counter
   reader printing a number).
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import family  # noqa: E402
import roofline  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)
from traffic import load_json  # noqa: E402

CELL = "smallthinker_21b.mixed"
WINDOW, SHARE, PAST, READ, GLOBAL = (
    "window_attn_roofline.itl", "cache_rows_window_share.obs",
    "lanes_past_window_share.obs", "cache_rows_read_share.itl",
    "decode_attn_roofline.itl")


def near(a, b, tol=1e-9):
    return a is not None and abs(a - b) <= tol * max(1.0, abs(b))


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    return 0 if ok else 1


def snap(counters):
    return {"profile": {"models": {"smallthinker:1": {"generative": {
        "spans": {}, "counters": counters}}}}}


def hand_made_ctx(cfg):
    """100 waves of 40 live lanes, 30 of them past the window: a lane reads
    3600 ring rows a window layer and 7000 rows a global layer in the mean; a
    traced 4 s whose decode steps hold 600 window calls (1.5 s) and 200
    global calls (0.9 s), and the window kernel's name in a piece as well."""
    lanes = 100 * 40
    after = {"fetched_waves": 100, "fetched_lanes_live": lanes,
             "fetched_lanes_past_window": 100 * 30,
             "fetched_positions_valid": lanes * 7000,
             "fetched_rows_window": lanes * 6 * 3600,
             "fetched_rows_global": lanes * 2 * 7000,
             "expert_pairs_local": 100 * 8 * 240,
             "experts_touched": 100 * 8 * 60}
    trace = {"window_s": 4.0,
             "modules": {"jit_decode": {"count": 100, "mean_ms": 20.0}},
             "program_ops": {
                 "jit_decode": {
                     "window_wave_attention_bf16_6_49_4096_512_": [1.5, 600],
                     "decode_wave_attention_bf16_2_49_16384_512_": [0.9, 200],
                     "fusion_f32_48_2560_": [0.5, 4500]},
                 "jit_prefill": {
                     "window_wave_attention_bf16_6_49_4096_512_": [0.4, 60]}}}
    return {"cfg": cfg, "traffic": {"max_model_len": 16384},
            "snap_before": snap({k: 0 for k in after}),
            "snap_after": snap(after), "trace": trace,
            "device": {"kind": "TPU v5 lite"}}


def readers(cfg) -> int:
    status = 0
    fam = family.load(cfg["family"])
    ctx = hand_made_ctx(cfg)
    want = 100.0 * 6 * 3600 / (6 * 3600 + 2 * 7000)
    status |= check(near(reader(SHARE)(ctx), want),
                    f"hand-made counters: 6 x 3600 ring rows beside 2 x 7000 "
                    f"global rows a lane: {want:.2f}% are ring rows")
    status |= check(near(reader(PAST)(ctx), 75.0),
                    "hand-made counters: 30 of 40 live lanes past the "
                    "window, 75%")
    want = 100.0 * 40 * (6 * 3600 + 2 * 7000) / (
        48 * (6 * 4096 + 2 * 16384))
    status |= check(near(reader(READ)(ctx), want),
                    f"hand-made counters: 40 lanes x 35600 rows of 48 slots x "
                    f"57344: {want:.2f}% of the arena's rows a wave")
    peaks = roofline.peaks_for("TPU v5 lite")
    for name, rows, events, seconds in ((WINDOW, 3600.0, 600, 1.5),
                                        (GLOBAL, 7000.0, 200, 0.9)):
        least, bound = roofline.min_seconds(
            *fam.decode_attention(cfg, 40.0, rows), peaks)
        got = reader(name)(ctx)
        status |= check(
            bound == "memory" and near(got, 100 * events * least / seconds),
            f"hand-made trace: {events} events of {name.split('_')[0]} calls "
            f"in jit_decode, {seconds} s, against {least * 1e3:.3f} ms a call "
            f"(40 lanes x {rows:.0f} rows x 2 KB): {got:.2f}% of the memory "
            f"roofline, the piece's events left out")
    parent = dict(ctx, snap_before=snap({"fetched_waves": 0}),
                  snap_after=snap({"fetched_waves": 100,
                                   "fetched_lanes_live": 4000,
                                   "fetched_positions_valid": 1}),
                  trace={"window_s": 4.0, "modules": ctx["trace"]["modules"],
                         "program_ops": {"jit_decode": {
                             "fusion_f32_": [0.2, 150]}}})
    bare = dict(ctx, snap_before=None, snap_after=None, trace=None)
    other = dict(ctx, cfg=load_json(os.path.join(
        BENCH, "configs", "gpt2_small.json")))
    nothing = [reader(n)(c) for n in (WINDOW, SHARE, PAST, READ)
               for c in (parent, bare)]
    nothing += [reader(WINDOW)(other), reader(READ)(other)]
    status |= check(all(v is None for v in nothing),
                    "a program without the counters, a context without "
                    "snapshots or trace, a family without a ring: None, "
                    "never 0, never an error")
    return status


def arithmetic(cfg) -> int:
    fam = family.load(cfg["family"])
    _, ring = fam.window_attention(cfg, 48, 3850)
    _, whole = fam.decode_attention(cfg, 48, 7300)
    flops, total = fam.decode_step(cfg, 48, 3850, 7300, 288, 64)
    cache = 6 * ring + 2 * whole
    ok = (3.5e9 < cache < 3.9e9 and 10.4e9 < total < 11.2e9
          and fam.wave_rows(cfg) == 1248)
    return check(ok, f"a full wave at a mean context of 7300: the cache "
                 f"{cache / 1e9:.2f} GB (the issue's 3.5 at 2 kB a row), the step "
                 f"{total / 1e9:.2f} GB (7.2 of weights and 3.5 of cache), "
                 f"{flops / 1e9:.0f} GFLOP; 1248 rows of sorted layout")


def rehearse() -> int:
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "0",
         "--rehearse-cpu"], env=env, capture_output=True, text=True,
        timeout=1500, cwd=ROOT)
    status = check(out.returncode == 0, "the rehearsed cell exits 0"
                   + ("" if out.returncode == 0 else "\n" + out.stdout[-1500:]
                      + out.stderr[-1500:]))
    if status:
        return status
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1][lines[-1].index("{"):])
    status |= check(lines[-1].startswith("REHEARSAL")
                    and set(last["metrics"]) == {"itl_mean_ms", "setup_s"}
                    and last["failed"] == 0 and last["attempted"] > 0,
                    f"the final line: marked, {last['attempted']} requests, "
                    f"none failed, itl_mean_ms and setup_s")
    layer = next(json.loads(ln[ln.index("{"):]) for ln in lines
                 if "per-layer of this run" in ln)
    want = {SHARE, PAST, READ, "expert_rows_per_expert.obs",
            "expert_imbalance.obs", "experts_touched_share.itl",
            "emit_wave_handoff_share.itl", "wave_live_lanes_mean.itl",
            "xla_compiles_in_window.itl"}
    status |= check(want <= set(layer),
                    "untraced, every listed counter reader prints a number: "
                    f"missing {sorted(want - set(layer))}")
    verdict = next(json.loads(ln[ln.index("{"):]) for ln in lines
                   if "reference verdict" in ln)
    status |= check(verdict.get("streams_short") == 0
                    and verdict.get("tokens_checked", 0) > 0
                    and verdict.get("positions_followed", 0) > 0,
                    f"every probe stream brought its record: the reference "
                    f"followed {verdict.get('positions_followed')} positions "
                    f"and judged {verdict.get('tokens_checked')} tokens (at "
                    f"toy widths its limits are not the cell's)")
    return status


def main() -> int:
    cfg = load_json(os.path.join(BENCH, "configs", "smallthinker_21b.json"))
    status = readers(cfg) | arithmetic(cfg)
    if "--rehearse" in sys.argv:
        status |= rehearse()
    return status


if __name__ == "__main__":
    sys.exit(main())
