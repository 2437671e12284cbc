#!/usr/bin/env python3
"""Checks what PR 34 added to the yardstick, on the CPU:

    python3 benchmark/testdata/check_kimi_linear.py            # readers only
    python3 benchmark/testdata/check_kimi_linear.py --rehearse # and the cell

1. The three new readers (``kda_state_roofline.itl``, ``state_bytes_share.obs``,
   ``prefill_padded_position_share.itl``) on a hand-made context whose figures
   can be worked out on paper, and on a context of a program that has none of
   what they read (the parent of PR 34): nothing, never 0 and never an error.
2. The family's arithmetic against the issue's reckoning of a wave.
3. With ``--rehearse``: the cell ``kimi_linear.longgen`` end to end at the
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

CELL = "kimi_linear.longgen"
KDA, SHARE, PADDED = ("kda_state_roofline.itl", "state_bytes_share.obs",
                      "prefill_padded_position_share.itl")
STATE = 32 * 128 * 128 * 4          # one layer's state of one slot, bytes


def near(a, b, tol=1e-9):
    return a is not None and abs(a - b) <= tol * max(1.0, abs(b))


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    return 0 if ok else 1


def snap(counters):
    return {"profile": {"models": {"kimi:1": {"generative": {
        "spans": {}, "counters": counters}}}}}


def hand_made_ctx(cfg):
    """100 waves of 250 live lanes at 3900 rows a lane; pieces that held 9000
    prompt positions and 1000 padded ones; a traced 4 s with 150 decode steps
    that hold the six layers' state kernels (900 events, 1.8 s), shorter
    than other operations, and the kernel's name in a piece as well."""
    after = {"fetched_waves": 100, "fetched_lanes_live": 25000,
             "fetched_positions_valid": 25000 * 3900,
             "prefill_positions_valid": 9000,
             "prefill_positions_padded": 1000,
             "expert_pairs_local": 100 * 7 * 250,
             "experts_touched": 100 * 7 * 31}
    trace = {"window_s": 4.0,
             "modules": {"jit_decode": {"count": 150, "mean_ms": 20.0}},
             "program_ops": {
                 "jit_decode": {
                     "kda_wave_update_f32_6_257_32_128_128_": [1.8, 900],
                     "latent_wave_attention_bf16_2_257_8192_640_": [2.0, 300],
                     "fusion_f32_256_2304_": [2.5, 4500]},
                 "jit_prefill": {
                     "kda_wave_update_f32_6_257_32_128_128_": [0.4, 60]}}}
    return {"cfg": cfg, "traffic": {"max_model_len": 8192},
            "snap_before": snap({k: 0 for k in after}),
            "snap_after": snap(after), "trace": trace,
            "device": {"kind": "TPU v5 lite"}}


def readers(cfg) -> int:
    status = 0
    fam = family.load(cfg["family"])
    ctx = hand_made_ctx(cfg)
    status |= check(near(reader(PADDED)(ctx), 10.0),
                    "hand-made counters: 1000 of 10000 piece positions "
                    "were padding, 10%")
    want = 100 * 6 * 2 * STATE / (6 * 2 * STATE + 3900 * 2 * 1152)
    status |= check(near(reader(SHARE)(ctx), want),
                    f"hand-made counters: a lane's 12 state passes of "
                    f"{STATE} B beside 3900 rows x 2 layers x 1152 B: "
                    f"{want:.2f}% of the cache bytes are state")
    least, bound = roofline.min_seconds(
        *fam.kda_update(cfg, 250.0), roofline.peaks_for("TPU v5 lite"))
    got = reader(KDA)(ctx)
    status |= check(bound == "memory"
                    and near(got, 100 * 900 * least / 1.8),
                    f"hand-made trace: 900 events of the state kernel in "
                    f"jit_decode, 1.8 s, against {least * 1e3:.3f} ms a call: "
                    f"{got:.2f}% of the memory roofline, by the events the "
                    f"trace holds and not by steps x six layers")
    parent = dict(ctx, snap_before=snap({"fetched_waves": 0}),
                  snap_after=snap({"fetched_waves": 100,
                                   "fetched_lanes_live": 25000,
                                   "fetched_positions_valid": 1}),
                  trace={"window_s": 4.0, "modules": ctx["trace"]["modules"],
                         "program_ops": {"jit_decode": {
                             "fusion_f32_": [0.2, 150]}}})
    bare = dict(ctx, snap_before=None, snap_after=None, trace=None)
    other = dict(ctx, cfg=load_json(os.path.join(
        BENCH, "configs", "gpt2_small.json")))
    nothing = [reader(PADDED)(parent), reader(KDA)(parent),
               reader(PADDED)(bare), reader(SHARE)(bare), reader(KDA)(bare),
               reader(SHARE)(other), reader(KDA)(other)]
    status |= check(all(v is None for v in nothing),
                    "a program without the counters, a context without "
                    "snapshots or trace, a family without a state: None, "
                    "never 0, never an error")
    return status


def arithmetic(cfg) -> int:
    fam = family.load(cfg["family"])
    state, rows = fam.cache_bytes(cfg, 256, 256 * 3900)
    _, total = fam.decode_step(cfg, 256, 3900, 256, 32)
    ok = (near(state, 6 * 256 * 2 * STATE) and near(rows, 256 * 3900 * 2304)
          and 12.5e9 < total < 13.3e9)
    return check(ok, f"a full wave at 3900 rows: states {state / 1e9:.2f} GB "
                 f"(the issue's 6.4), latent rows {rows / 1e9:.2f} (2.3), "
                 f"the step {total / 1e9:.2f} (12.9)")


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
    want = {SHARE, PADDED, "arena_live_share.itl", "kv_live_share.itl",
            "expert_rows_per_expert.obs", "expert_imbalance.obs",
            "experts_touched_share.itl", "emit_wave_handoff_share.itl",
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
    cfg = load_json(os.path.join(BENCH, "configs", "kimi_linear.json"))
    status = readers(cfg) | arithmetic(cfg)
    if "--rehearse" in sys.argv:
        status |= rehearse()
    return status


if __name__ == "__main__":
    sys.exit(main())
