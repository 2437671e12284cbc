#!/usr/bin/env python3
"""Checks what PR 50 added to the yardstick, on the CPU:

    python3 benchmark/testdata/check_ouro.py            # readers only
    python3 benchmark/testdata/check_ouro.py --rehearse # and the cell

1. The two new readers (``loop_dense_roofline.itl``, ``passes_per_wave.obs``)
   and the accepted ones that take the cell through the family
   (``decode_attn_roofline.itl``, ``step_mfu_roofline.itl``) on a reduced trace of
   made-up times (``check_readers.py``'s ``kernel_ctx``: 48 attention calls a
   step at twice their least time, the dense products at twice theirs), and
   on a context of a program that counts no passes (the parent of PR 50) or
   of another family: nothing, never 0 and never an error.  They stand here
   and not in ``check_readers.py`` because a PR that adds a cell edits no file
   the benchmark has.
2. The family's arithmetic against the issue's reckoning of a wave.
3. With ``--rehearse``: the cell ``ouro_2b6.fewshot`` end to end at the
   configuration's ``rehearse_cpu`` sizes (a rehearsal proves nothing about
   the chip: control flow, the final line's keys, every listed counter reader
   printing a number).
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import family  # noqa: E402
import roofline  # noqa: E402
from check_readers import KIND, STEPS, check, kernel_ctx, near  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)
from traffic import load_json  # noqa: E402

CELL = "ouro_2b6.fewshot"
DENSE, PASSES = "loop_dense_roofline.itl", "passes_per_wave.obs"
ATTENTION, STEP = "decode_attn_roofline.itl", "step_mfu_roofline.itl"
KERNEL = "decode_wave_attention_bf16_48_19_1536_2048_"
# The window's counters over 1000 waves of 17 live lanes at 800 positions:
# four passes a wave, 48 calls' rows.
LANES, CONTEXT, WINDOW_S = 17.0, 800.0, 50.0
COUNTERS = dict(fetched_lanes_live=17_000,
                fetched_positions_valid=13_600_000,
                fetched_rows_global=48 * 13_600_000, fetched_passes=4_000)


def readers(cfg) -> int:
    fam = family.load(cfg["family"])
    peaks = roofline.peaks_for(KIND)
    call = roofline.min_seconds(*fam.decode_attention(cfg, LANES, CONTEXT),
                                peaks)[0]
    dense = roofline.min_seconds(*fam.dense_products(cfg, LANES, 4.0),
                                 peaks)[0]
    step = roofline.min_seconds(*fam.decode_step(cfg, LANES, CONTEXT, 4.0),
                                peaks)[0]
    events = 48 * STEPS

    def ctx_of(counters, groups, scale=1.0):
        ctx = kernel_ctx(cfg, counters, groups)
        # A step: the 48 calls and the dense products, each at twice its
        # least time (``scale`` shortens the dense part alone).
        ctx["trace"]["modules"]["jit_decode"]["mean_ms"] = 1e3 * (
            2 * 48 * call + scale * 2 * dense)
        ctx["traffic"] = {"step_module": "jit_decode"}
        ctx.update(t0=0.0, t1=WINDOW_S)
        return ctx
    groups = {KERNEL: [2 * call * events, events]}
    ctx = ctx_of(COUNTERS, groups)
    got = {m: reader(m)(ctx) for m in (DENSE, PASSES, ATTENTION, STEP)}
    faster = reader(DENSE)(ctx_of(COUNTERS, groups, 0.7))
    parent = {k: v for k, v in COUNTERS.items() if k != "fetched_passes"}
    other = dict(ctx, cfg=load_json(os.path.join(
        BENCH, "configs", "nemotron3_nano_30b.json")))
    # The whole step's share is the counters' 1000 waves over the window's
    # seconds (no trace in it): 1000 x 13.2 ms of 50 s.
    whole = 100.0 * 1000 * step / WINDOW_S
    status = check(
        near(got[DENSE], 50.0) and near(faster, 50.0 / 0.7)
        and near(got[PASSES], 4.0) and near(got[ATTENTION], 50.0)
        and near(got[STEP], whole, 1e-6) and 25.0 < whole < 27.0
        and reader(STEP)(dict(ctx, trace=None)) == got[STEP],
        f"a step of 48 calls and the dense products, each at twice its "
        f"least time: {DENSE} {got[DENSE]!r}% (30% shorter products "
        f"{faster!r}%), {ATTENTION} {got[ATTENTION]!r}% from {events} "
        f"events, {STEP} {got[STEP]!r}%, {PASSES} {got[PASSES]!r}")
    nothing = [reader(m)(c) for m in (DENSE, PASSES) for c in (
        ctx_of(parent, groups), dict(ctx, snap_before=None, snap_after=None))]
    nothing += [reader(DENSE)(dict(ctx, trace=None)), reader(DENSE)(other),
                reader(ATTENTION)(ctx_of(parent, groups)),
                reader(STEP)(ctx_of(parent, groups))]
    return status | check(
        all(v is None for v in nothing),
        "a program that counts no passes (the parent), a context without "
        "snapshots or trace, another family: nothing, from the new readers "
        f"and from the accepted ones through the family: {nothing}")


def arithmetic(cfg) -> int:
    fam = family.load(cfg["family"])
    _, dense = fam.dense_products(cfg, 18, 4)
    _, call = fam.decode_attention(cfg, 18, 800)
    flops, total = fam.decode_step(cfg, 18, 800, 4)
    least = roofline.min_seconds(flops, total, roofline.peaks_for(KIND))
    ok = (5.12e9 < dense < 5.15e9 and 0.117e9 < call < 0.119e9
          and 10.7e9 < total < 10.9e9 and 13.0e-3 < least[0] < 13.4e-3
          and least[1] == "memory")
    return check(ok, f"a wave of 18 live lanes at 800 positions: the dense "
                 f"products {dense / 1e9:.3f} GB (the issue's 4.93 + 0.20), "
                 f"an attention call {call / 1e9:.4f} GB (48 of them 5.66), "
                 f"the step {total / 1e9:.2f} GB, {flops / 1e9:.0f} GFLOP: "
                 f"{least[0] * 1e3:.2f} ms at the roofline, bound by "
                 f"{least[1]}")


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
    want = {"passes_per_wave.obs", "arena_live_share.itl",
            "kv_live_share.itl", "prefill_stage_ms_mean.itl",
            "prefill_lanes_per_call.obs", "wave_live_lanes_mean.itl",
            "xla_compiles_in_window.itl"}
    status |= check(want <= set(layer) and layer["passes_per_wave.obs"] == 4,
                    "untraced, every listed counter reader prints a number "
                    f"and a wave ran four passes: missing "
                    f"{sorted(want - set(layer))}")
    verdict = next(json.loads(ln[ln.index("{"):]) for ln in lines
                   if "reference verdict" in ln)
    status |= check(verdict.get("streams_short") == 0
                    and verdict.get("tokens_checked", 0) > 0
                    and verdict.get("logits_compared", 0) > 0,
                    f"every probe stream brought its record: the reference "
                    f"judged {verdict.get('tokens_checked')} tokens on "
                    f"{verdict.get('logits_compared')} logits (at toy widths "
                    f"its limits are not the cell's)")
    return status


def main() -> int:
    cfg = load_json(os.path.join(BENCH, "configs", "ouro_2b6.json"))
    status = readers(cfg) | arithmetic(cfg)
    if "--rehearse" in sys.argv:
        status |= rehearse()
    return status


if __name__ == "__main__":
    sys.exit(main())
