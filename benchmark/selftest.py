#!/usr/bin/env python3
"""Self-test of the yardstick, on the CPU, in seconds:

    python3 benchmark/selftest.py            # exit 0 = all checks hold
    python3 benchmark/selftest.py --quick    # skip the end-to-end rehearsal

1. At the manifest's ``run_seconds`` the quantile-stratified generator
   offers, for two seeds, the same request count, the same multiset of
   prompt and output lengths and the same token total, in different orders
   and at different arrival times.
2. The trace reduction reproduces known busy and idle figures on the small
   recorded trace kept beside it (``testdata/``) and on a hand-made one.
3. The roofline functions match operations counted by hand for one BERT
   step and one decode wave; the ``bert`` family's reference, which no cell
   runs today, agrees with the program's BertBackend at a tiny size; PR 27's
   readers and the rule that chose the window and the bound
   (``testdata/check_readers.py``, ``testdata/check_spread.py``).
4. The final line of a (rehearsed) run parses and holds only the contract's
   keys.

Nothing here measures a device; a number from this file is never a device
metric.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import family  # noqa: E402
import roofline  # noqa: E402
import traffic as T  # noqa: E402

CELL = "gpt2_small.chat"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "breakdown", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
               "window_s"}


def check(cond: bool, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what, flush=True)
    if not cond:
        raise SystemExit(1)


def test_generator() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    files = {c["name"]: os.path.join(ROOT, c["file"])
             for c in manifest["configs"]}
    seconds = float(manifest["run_seconds"])
    for cell in manifest["workloads"]:
        cfg = T.load_json(files[cell["config"]])
        tr = T.load_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"))
        a = T.build_plan(cfg, tr, 7, seconds, "window")
        b = T.build_plan(cfg, tr, 3000000019, seconds, "window")
        check(a.multiset() == b.multiset(),
              f"{cell['name']}: two seeds offer the same count "
              f"({len(a)}), length multiset and token total "
              f"({a.multiset()[3]})")
        differs = (a.prompt_len.tolist() != b.prompt_len.tolist()
                   or a.bodies[0] != b.bodies[0])
        check(differs, f"{cell['name']}: the seeds differ in order or ids")
        if tr["loop"] == "open":
            check(len(a) == round(tr["rate_per_s"] * seconds)
                  and a.due.tolist() != b.due.tolist()
                  and (a.due >= 0).all() and (a.due < seconds).all()
                  and (a.due[1:] >= a.due[:-1]).all(),
                  f"{cell['name']}: N = rate x seconds arrivals, sorted, "
                  f"inside the window, placed by the seed")
        again = T.build_plan(cfg, tr, 7, seconds, "window")
        check(again.bodies == a.bodies and again.due.tolist()
              == a.due.tolist(), f"{cell['name']}: same seed, same bytes")


def test_roofline() -> None:
    # BERT-base as published, at the sequence the zoo serves
    bert = {"hidden_size": 768, "intermediate_size": 3072,
            "num_hidden_layers": 12, "max_position_embeddings": 128}
    flops, nbytes = family.load("bert").encoder_step(bert, 1)
    # by hand, batch 1 x seq 128: per layer 2*128*(4*768^2 + 2*768*3072)
    # + 4*128^2*768 = 1,862,270,976; x12; + pooler 2*768^2 + head 2*768*2
    check(flops == 22_348_434_432.0,
          f"BERT step b1 s128 = 22,348,434,432 FLOP (got {flops:,.0f})")
    w = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 768 + 2 * 768
    check(nbytes == 2 * w + 2 * 128 * 768 * 2 + 2 * 128 * 4 + 770 * 4,
          f"BERT step b1 bytes = weights once + rows + io ({nbytes:,.0f})")
    gpt = T.load_json(os.path.join(HERE, "configs", "gpt2_small.json"))
    decode_step = family.load("gpt").decode_step
    flops, _ = decode_step(gpt, 32, 100)
    # by hand, 32 lanes, context 100: per layer 2*32*7,077,888 +
    # 4*32*100*768 = 462,815,232; x12; + head 2*32*768*50257
    check(flops == 8_024_014_848.0,
          f"decode wave 32 lanes ctx 100 = 8,024,014,848 FLOP "
          f"(got {flops:,.0f})")
    t, bound = roofline.min_seconds(*decode_step(gpt, 32, 100),
                                    roofline.peaks_for("TPU v5 lite"))
    check(bound == "memory" and 5e-4 < t < 1e-3,
          f"a decode wave is memory-bound, least {t * 1e3:.3f} ms on a v5e")
    try:
        roofline.peaks_for("TPU v9")
        check(False, "an unknown device kind is an error")
    except KeyError:
        check(True, "an unknown device kind is an error")


def test_trace_reduction() -> None:
    """Runs in a child: the reduction imports JAX, this file does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "testdata", "check_trace.py")],
        env=env, capture_output=True, text=True, timeout=300)
    print(out.stdout, end="")
    check(out.returncode == 0, "trace reduction reproduces the known "
          "figures" + ("" if out.returncode == 0 else "\n" + out.stderr))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "testdata", "check_family.py")],
        env=env, capture_output=True, text=True, timeout=300)
    print(out.stdout, end="")
    check(out.returncode == 0, "the family kept without a cell still "
          "agrees with the program"
          + ("" if out.returncode == 0 else "\n" + out.stderr))
    for script, what in (
            ("check_readers.py", "PR 27's readers read the recorded trace "
             "and a hand-made context"),
            ("check_spread.py", "BENCHMARK.json's window and bounds are "
             "what the rule gives on the recorded sets")):
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "testdata", script)],
            env=env, capture_output=True, text=True, timeout=300)
        print(out.stdout, end="")
        check(out.returncode == 0,
              what + ("" if out.returncode == 0 else "\n" + out.stderr))


def test_final_line() -> None:
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    check(out.returncode == 0, "a rehearsed cell exits 0"
          + ("" if out.returncode == 0 else "\n" + out.stderr[-2000:]))
    last = out.stdout.strip().splitlines()[-1]
    check(last.startswith("REHEARSAL"), "a rehearsal marks its line")
    obj = json.loads(last[last.index("{"):])
    obj.pop("rehearsal")
    check(set(obj) <= CONTRACT_KEYS and CONTRACT_KEYS - set(obj)
          <= {"breakdown"}, f"the final line holds only the contract's "
          f"keys: {sorted(obj)}")
    check(set(obj["device"]) <= DEVICE_KEYS, "device keys")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    want = {m["name"] for m in manifest["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    check(set(obj["metrics"]) == want,
          f"untraced metrics are the cell's end-to-end ones: "
          f"{sorted(obj['metrics'])}")
    check(all(set(v) == {"value", "unit"} for v in obj["metrics"].values()),
          "each metric is {value, unit}")


def main() -> int:
    test_generator()
    test_roofline()
    test_trace_reduction()
    if "--quick" not in sys.argv:
        test_final_line()
    print("selftest passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
