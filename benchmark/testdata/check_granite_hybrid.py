#!/usr/bin/env python3
"""Checks what PR 59 added to the yardstick, on the CPU:

    python3 benchmark/testdata/check_granite_hybrid.py            # readers
    python3 benchmark/testdata/check_granite_hybrid.py --rehearse # and the cell

1. The new reader ``hybrid_dense_roofline.itl`` and the accepted kernel
   readers the cell lists (``ssm_state_roofline.itl``,
   ``decode_attn_roofline.itl``) on ``check_readers.py``'s ``kernel_ctx`` (a
   reduced trace in which every call of a kernel is shorter than ten other
   operations): each kernel reader reads its share from every call the table
   holds; the dense reader reads the family's ``wave_dense`` over the step's
   mean time less both kernels' events, cannot pass 100% where the step takes
   at least its parts, and reads nothing on a program or a family without
   what it reads (the parent of PR 59, another family, no trace): never 0 and
   never an error.
2. The family's arithmetic against the issue's reckoning of a wave.
3. With ``--rehearse``: the cell ``granite4_h_micro.helpdesk`` end to end at
   the configuration's ``rehearse_cpu`` sizes (a rehearsal proves nothing
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

CELL = "granite4_h_micro.helpdesk"
DENSE, STATE, ROWS = ("hybrid_dense_roofline.itl", "ssm_state_roofline.itl",
                      "decode_attn_roofline.itl")
# The window's counters over 1000 waves of 76 live lanes at 900 positions.
COUNTERS = dict(fetched_lanes_live=76_000,
                fetched_positions_valid=68_400_000,
                fetched_rows_global=4 * 68_400_000)
STATE_GROUP = "ssd_wave_update_f32_36_81_32_128_128_"
ROWS_GROUP = "decode_wave_attention_bf16_4_81_2048_512_"


def readers(cfg) -> int:
    fam = family.load(cfg["family"])
    peaks = roofline.peaks_for(KIND)
    state = roofline.min_seconds(*fam.ssm_update(cfg, 76.0), peaks)[0]
    rows = roofline.min_seconds(*fam.decode_attention(cfg, 76.0, 900.0),
                                peaks)[0]
    dense = roofline.min_seconds(*fam.wave_dense(cfg, 76.0), peaks)[0]

    def ctx_of(scale, step_s):
        """Every kernel call at ``2 x scale`` its least time; a decode step
        of ``step_s`` seconds."""
        groups = {STATE_GROUP: [scale * 2 * state * 36 * STEPS, 36 * STEPS],
                  ROWS_GROUP: [scale * 2 * rows * 4 * STEPS, 4 * STEPS]}
        ctx = kernel_ctx(cfg, COUNTERS, groups)
        ctx["trace"]["modules"]["jit_decode"]["mean_ms"] = step_s * 1e3
        return ctx

    kernels = 2 * (36 * state + 4 * rows)
    ctx = ctx_of(1.0, kernels + 2 * dense)
    status = 0
    for metric in (STATE, ROWS):
        got, faster = reader(metric)(ctx), reader(metric)(
            ctx_of(0.7, kernels + 2 * dense))
        status |= check(near(got, 50.0) and near(faster, 50.0 / 0.7),
                        f"{metric} on the new cell's groups: {got!r}%, 30% "
                        f"shorter calls {faster!r}%")
    got = reader(DENSE)(ctx)
    tight = reader(DENSE)(ctx_of(1.0, kernels + dense))
    other = dict(ctx, cfg=load_json(os.path.join(
        BENCH, "configs", "nemotron3_nano_30b.json")))
    no_step = ctx_of(1.0, kernels + 2 * dense)
    del no_step["trace"]["modules"]["jit_decode"]
    status |= check(
        near(got, 50.0) and near(tight, 100.0)
        and reader(DENSE)(dict(ctx, trace=None)) is None
        and reader(DENSE)(dict(ctx, snap_before=None, snap_after=None))
        is None and reader(DENSE)(other) is None
        and reader(DENSE)(no_step) is None
        and reader(DENSE)(ctx_of(1.0, kernels)) is None,
        f"{DENSE}: a step of both kernels' calls and twice the dense "
        f"products' least time reads {got!r}%, one of exactly their least "
        f"{tight!r}%; no trace, no snapshots, another family, no "
        f"jit_decode, no time left for the products: nothing")
    return status


def arithmetic(cfg) -> int:
    fam = family.load(cfg["family"])
    _, state = fam.ssm_update(cfg, 80)
    _, rows = fam.decode_attention(cfg, 80, 900)
    _, weights = fam.wave_dense(cfg, 80)
    flops, total = fam.decode_step(cfg, 80, 900)
    s_bytes, r_bytes = fam.cache_bytes(cfg, 80, 80 * 900)
    ok = (0.335e9 < state < 0.340e9 and 0.147e9 < rows < 0.148e9
          and 6.36e9 < weights < 6.39e9 and 18.8e9 < total < 19.4e9
          and 12.07e9 < s_bytes < 12.09e9
          and 0.94 < s_bytes / (s_bytes + r_bytes) < 0.96)
    return check(ok, f"a wave of 80 live lanes at 900 positions: a state "
                 f"call {state / 1e9:.3f} GB (36 of them "
                 f"{s_bytes / 1e9:.2f} GB of states), an attention call "
                 f"{rows / 1e9:.3f} GB, the dense products' weights "
                 f"{weights / 1e9:.2f} GB, the step {total / 1e9:.2f} GB, "
                 f"{flops / 1e9:.0f} GFLOP; states "
                 f"{100 * s_bytes / (s_bytes + r_bytes):.1f}% of the cache "
                 f"bytes")


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
            "prefill_stage_ms_mean.itl", "prefill_lanes_per_call.obs",
            "prefill_head_share.itl", "wave_live_lanes_mean.itl",
            "gaps_behind_prefill_share.obs", "xla_compiles_in_window.itl"}
    status |= check(want <= set(layer),
                    "untraced, every listed counter reader prints a number: "
                    f"missing {sorted(want - set(layer))}")
    verdict = next(json.loads(ln[ln.index("{"):]) for ln in lines
                   if "reference verdict" in ln)
    status |= check(verdict.get("streams_short") == 0
                    and verdict.get("tokens_checked", 0) > 0,
                    f"every probe stream brought its record: the reference "
                    f"judged {verdict.get('tokens_checked')} tokens (at toy "
                    f"widths its limits are not the cell's)")
    return status


def main() -> int:
    cfg = load_json(os.path.join(BENCH, "configs", "granite4_h_micro.json"))
    status = readers(cfg) | arithmetic(cfg)
    if "--rehearse" in sys.argv:
        status |= rehearse()
    return status


if __name__ == "__main__":
    sys.exit(main())
