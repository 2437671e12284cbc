#!/usr/bin/env python3
"""Checks what PR 53 added to the yardstick, on the CPU:

    python3 benchmark/testdata/check_cohere_moe.py            # readers only
    python3 benchmark/testdata/check_cohere_moe.py --rehearse # and the cell

1. The two new readers (``piece_roofline.itl``, ``dense_branch_roofline.itl``)
   on a hand-made context whose figures can be worked out on paper, and on a
   context of a program that has none of what they read (the parent of PR
   53, a family without the counts): nothing, never 0 and never an error.
2. The family's arithmetic against the issue's reckoning of a wave and of a
   piece.
3. With ``--rehearse``: the cell ``command_a_plus.rag`` end to end at the
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

CELL = "command_a_plus.rag"
PIECE, DENSE = "piece_roofline.itl", "dense_branch_roofline.itl"


def near(a, b, tol=1e-9):
    return a is not None and abs(a - b) <= tol * max(1.0, abs(b))


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    return 0 if ok else 1


def snap(counters, spans):
    return {"profile": {"models": {"command_a_plus:1": {"generative": {
        "spans": spans, "counters": counters}}}}}


def hand_made_ctx(cfg):
    """100 piece programs of 500 valid positions in the mean, 13000 into
    their prompts, 4 with a head; 80 waves of 20 live lanes; a traced 4 s
    whose 80 decode steps of 15 ms hold 6 ms of the three kernels each and
    whose piece programs take 25 ms."""
    window, whole = 3 * 500 * 4096, 500 * 13000
    after = {"prefill_positions_valid": 100 * 500, "prefill_pieces": 100,
             "prefill_pairs_window": 100 * window,
             "prefill_pairs_global": 100 * whole, "prefill_heads": 4,
             "fetched_waves": 80, "fetched_lanes_live": 80 * 20}
    trace = {"window_s": 4.0,
             "modules": {"jit_decode": {"count": 80, "mean_ms": 15.0},
                         "jit_prefill": {"count": 100, "mean_ms": 25.0}},
             "program_ops": {"jit_decode": {
                 "window_wave_attention_bf16_3_25_4096_1024_": [0.24, 240],
                 "decode_wave_attention_bf16_1_25_25600_1024_": [0.12, 80],
                 "grouped_matmul_f32_432_8192_": [0.08, 320],
                 "grouped_matmul_f32_432_4096_": [0.04, 320],
                 "fusion_f32_24_4096_": [0.5, 4500]}}}
    spans = {"gen.prefill_dispatch": {"count": 100, "total_ns": 1,
                                      "max_ns": 1}}
    zero = {"gen.prefill_dispatch": {"count": 0, "total_ns": 0, "max_ns": 0}}
    return {"cfg": cfg, "traffic": {"max_model_len": 25600},
            "snap_before": snap({k: 0 for k in after}, zero),
            "snap_after": snap(after, spans), "trace": trace,
            "device": {"kind": "TPU v5 lite"}}


def readers(cfg) -> int:
    status = 0
    fam = family.load(cfg["family"])
    ctx = hand_made_ctx(cfg)
    peaks = roofline.peaks_for("TPU v5 lite")
    least, bound = roofline.min_seconds(
        *fam.piece_step(cfg, 500, 3 * 500 * 4096, 500 * 13000, 1, 0.04),
        peaks)
    got = reader(PIECE)(ctx)
    status |= check(
        near(got, 100 * least / 0.025) and 40 < got < 100,
        f"hand-made counters and trace: a mean piece of 500 positions 13000 "
        f"into its prompt against {least * 1e3:.2f} ms ({bound}-bound), 25 ms "
        f"a program: {got:.2f}%")
    least, bound = roofline.min_seconds(*fam.dense_products(cfg, 20), peaks)
    got = reader(DENSE)(ctx)
    status |= check(
        bound == "memory" and near(got, 100 * least / (0.015 - 0.006)),
        f"hand-made trace: 15 ms a wave less 6 ms of the three kernels "
        f"against {least * 1e3:.3f} ms for 3.0 GB of dense weights: "
        f"{got:.2f}% of the memory roofline")
    parent = dict(ctx, snap_before=snap({"fetched_waves": 0}, {}),
                  snap_after=snap({"fetched_waves": 80,
                                   "fetched_lanes_live": 1600,
                                   "prefill_positions_valid": 5}, {}))
    bare = dict(ctx, snap_before=None, snap_after=None, trace=None)
    other = dict(ctx, cfg=load_json(os.path.join(
        BENCH, "configs", "gpt2_small.json")))
    nothing = [reader(PIECE)(parent), reader(PIECE)(bare),
               reader(DENSE)(bare), reader(PIECE)(other),
               reader(DENSE)(other)]
    status |= check(all(v is None for v in nothing),
                    "a program without the counters, a context without "
                    "snapshots or trace, a family without the counts: None, "
                    "never 0, never an error")
    return status


def arithmetic(cfg) -> int:
    fam = family.load(cfg["family"])
    _, ring = fam.window_attention(cfg, 24, 4095)
    _, whole = fam.decode_attention(cfg, 24, 13700)
    flops, total = fam.decode_step(cfg, 24, 4095, 13700, 192, 0.79 * 16)
    cache = 3 * ring + whole
    status = check(
        2.4e9 < cache < 2.8e9 and 10.2e9 < total < 11.2e9
        and fam.wave_rows(cfg) == 432,
        f"a full wave at a mean context of 13700: the cache "
        f"{cache / 1e9:.2f} GB (the issue's 2.6), the step {total / 1e9:.2f} "
        f"GB (the issue's 10.7), {flops / 1e9:.0f} GFLOP; 432 rows of sorted "
        f"layout")
    flops, nbytes = fam.piece_step(
        cfg, 512, 3 * 512 * 4096, sum(range(13313, 13825)), 1, 0)
    return status | check(
        2.4e12 < flops < 2.8e12 and 9.1e9 < nbytes < 9.6e9,
        f"a piece of 512 positions 13k into a prompt: {flops / 1e12:.2f} "
        f"TFLOP (the issue's 2.6) beside {nbytes / 1e9:.2f} GB of weights "
        f"(the issue's 9.5)")


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
    want = {"cache_rows_window_share.obs", "lanes_past_window_share.obs",
            "cache_rows_read_share.itl", "expert_rows_per_expert.obs",
            "expert_imbalance.obs", "experts_touched_share.itl",
            "prefill_head_share.itl", "prefill_lanes_per_call.obs",
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
    cfg = load_json(os.path.join(BENCH, "configs", "command_a_plus.json"))
    status = readers(cfg) | arithmetic(cfg)
    if "--rehearse" in sys.argv:
        status |= rehearse()
    return status


if __name__ == "__main__":
    sys.exit(main())
