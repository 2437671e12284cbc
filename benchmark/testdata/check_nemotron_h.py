#!/usr/bin/env python3
"""Checks what PR 45 added to the yardstick, on the CPU:

    python3 benchmark/testdata/check_nemotron_h.py            # readers only
    python3 benchmark/testdata/check_nemotron_h.py --rehearse # and the cell

1. The two new readers (``ssm_state_roofline.itl``,
   ``expert_mlp_roofline.itl``) as ``check_readers.py`` checks the other
   kernel readers (its ``kernel_ctx``: a reduced trace in which every call of
   the kernel is shorter than ten other operations; each reads its share from
   every call the table holds, 1 / 0.7 times as much with the calls 30%
   shorter, nothing where the table holds no event of the kernel), and on a
   context of a program that has none of what they read (the parent of PR
   45) or of another family: nothing, never 0 and never an error.  They stand
   here and not in ``check_readers.py``'s ``KERNELS`` because a PR that adds a
   cell edits no file the benchmark has.
2. The family's arithmetic against the issue's reckoning of a wave.
3. With ``--rehearse``: the cell ``nemotron3_nano_30b.assistant`` end to end
   at the configuration's ``rehearse_cpu`` sizes (a rehearsal proves nothing
   about the chip: control flow, the final line's keys, every listed counter
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
sys.path.insert(0, HERE)

import family  # noqa: E402
import roofline  # noqa: E402
from check_readers import KIND, STEPS, check, kernel_ctx, near  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)
from traffic import load_json  # noqa: E402

CELL = "nemotron3_nano_30b.assistant"
STATE, EXPERTS = "ssm_state_roofline.itl", "expert_mlp_roofline.itl"
# The window's counters over 1000 waves of 250 live lanes at 2000 positions:
# 750 pairs and 63 touched experts an expert layer.
COUNTERS = dict(fetched_lanes_live=250_000,
                fetched_positions_valid=500_000_000,
                fetched_rows_global=1_000_000_000,
                expert_pairs_local=3_750_000, experts_touched=315_000)


def kernel_parts(metric, cfg):
    """{group of the kernel in ``jit_decode``: least seconds of one call at
    the counters' means}, by the family's cost functions."""
    fam = family.load(cfg["family"])
    peaks = roofline.peaks_for(KIND)
    if metric == STATE:
        return {"ssd_wave_update_f32_6_257_32_128_128_":
                roofline.min_seconds(*fam.ssm_update(cfg, 250.0), peaks)[0]}
    width = {"up": int(cfg["moe_intermediate_size"]),
             "down": int(cfg["hidden_size"])}
    return {f"grouped_matmul_f32_{fam.wave_rows(cfg)}_{n}_":
            roofline.min_seconds(*fam.expert_ffn(cfg, 750.0, 63.0, part),
                                 peaks)[0]
            for part, n in width.items()}


def readers(cfg) -> int:
    status = 0
    for metric, calls in ((STATE, 6), (EXPERTS, 5)):
        least = kernel_parts(metric, cfg)
        events = calls * STEPS

        def groups(scale):          # every call at twice its least time
            return {g: [scale * 2 * t * events, events]
                    for g, t in least.items()}
        ctx = kernel_ctx(cfg, COUNTERS, groups(1.0))
        got = reader(metric)(ctx)
        faster = reader(metric)(kernel_ctx(cfg, COUNTERS, groups(0.7)))
        other = dict(ctx, cfg=load_json(os.path.join(
            BENCH, "configs", "kimi_linear.json")))
        status |= check(
            near(got, 50.0) and near(faster, 50.0 / 0.7)
            and reader(metric)(kernel_ctx(cfg, COUNTERS, {})) is None
            and reader(metric)(dict(ctx, trace=None)) is None
            and reader(metric)(dict(ctx, snap_before=None, snap_after=None))
            is None and reader(metric)(other) is None,
            f"{metric}: {calls} calls a step, every one below the tenth "
            f"longest operation, read {got!r}% from {events * len(least)} "
            f"events; 30% shorter calls {faster!r}%; a program without the "
            f"kernel, a context without trace or snapshots, another family: "
            f"nothing")
    return status


def arithmetic(cfg) -> int:
    fam = family.load(cfg["family"])
    _, state = fam.ssm_update(cfg, 250)
    _, experts = fam.expert_ffn(cfg, 768, 64)
    _, rows = fam.decode_attention(cfg, 250, 2000)
    flops, total = fam.decode_step(cfg, 250, 2000, 768, 64)
    ok = (1.05e9 < state < 1.06e9 and 1.27e9 < experts < 1.30e9
          and 0.51e9 < rows < 0.52e9 and 14.4e9 < total < 15.2e9
          and fam.wave_rows(cfg) == 2496)
    return check(ok, f"a wave of 250 live lanes at 2000 positions: a state "
                 f"call {state / 1e9:.3f} GB (the issue's 1.05), an expert "
                 f"layer {experts / 1e9:.3f} GB (6.39 for five), an attention "
                 f"call {rows / 1e9:.3f} GB, the step {total / 1e9:.2f} GB, "
                 f"{flops / 1e9:.0f} GFLOP; 2496 rows of sorted layout")


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
    want = {"state_bytes_share.obs", "prefill_padded_position_share.itl",
            "arena_live_share.itl", "kv_live_share.itl",
            "expert_rows_per_expert.obs", "expert_imbalance.obs",
            "experts_touched_share.itl", "prefill_stage_ms_mean.itl",
            "wave_live_lanes_mean.itl", "xla_compiles_in_window.itl"}
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
    cfg = load_json(os.path.join(BENCH, "configs", "nemotron3_nano_30b.json"))
    status = readers(cfg) | arithmetic(cfg)
    if "--rehearse" in sys.argv:
        status |= rehearse()
    return status


if __name__ == "__main__":
    sys.exit(main())
