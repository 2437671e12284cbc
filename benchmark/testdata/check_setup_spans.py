#!/usr/bin/env python3
"""Paper check of the ``.setup`` readers (PR 57) on recorded launches.

    python3 benchmark/testdata/check_setup_spans.py
    python3 benchmark/testdata/check_setup_spans.py --record \
        <kept run's context.json> <traffic name> <out.json>

``recorded_setup_<cell>.json`` holds what the readers read of one run on the
chip (the builder's, PR 57; a run kept with ``--artifacts``): the
``startup``, ``startup_clock`` and ``compiles`` objects of the ``/v2/profile``
snapshot taken at the window's start, the moment it was taken (the harness's
clock; the window's start to a millisecond), the run's ``setup_s`` and the
traffic file's pre-roll.  One cell warms up in the launcher
(``kimi_linear.longgen``), one under the harness's warm traffic
(``gpt2_small.chat``).  Held here: every reader gives a number, the parts and
what is unspanned sum to ``setup_s``, the warm-up's children partition it, and
``setup_unspanned_s.setup`` stays under a tenth of ``setup_s``.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import setupspans  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)

RECORDED = {"kimi_linear.longgen": True, "gpt2_small.chat": False}
NEW = ["startup_process_s.setup", "startup_trace_s.setup",
       "startup_lower_s.setup", "startup_cache_miss_s.setup",
       "startup_first_run_s.setup", "setup_warm_traffic_s.setup",
       "setup_unspanned_s.setup"]
ACCEPTED = ["startup_backend_init_s.setup", "startup_model_load_s.setup",
            "startup_compile_s.setup"]


def record(context_path: str, traffic_name: str, out_path: str) -> None:
    with open(context_path) as f:
        kept = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    before = kept["snaps"]["before"]
    profile = before["profile"]
    with open(out_path, "w") as f:
        json.dump({"t0": before["t"],
                   "setup_s": kept["e2e"]["setup_s"]["value"],
                   "traffic": {"preroll_s": traffic.get("preroll_s", 0)},
                   "phases": kept["phases"],
                   "profile": {k: profile[k] for k in
                               ("startup", "startup_clock", "compiles")}},
                  f, indent=1)
        f.write("\n")


def context(cell: str) -> dict:
    with open(os.path.join(HERE, f"recorded_setup_{cell}.json")) as f:
        rec = json.load(f)
    return {"snap_before": {"profile": rec["profile"]}, "t0": rec["t0"],
            "setup_s": rec["setup_s"], "traffic": rec["traffic"],
            "phases": rec["phases"]}


def ok(cond: bool, what: str) -> bool:
    print(("ok   " if cond else "FAIL ") + what)
    return bool(cond)


def main() -> int:
    if sys.argv[1:2] == ["--record"]:
        record(*sys.argv[2:5])
        return 0
    good = True
    for cell, warms_up in RECORDED.items():
        ctx = context(cell)
        got = {name[:-6]: reader(name)(ctx) for name in NEW + ACCEPTED}
        setup_s = ctx["setup_s"]
        good &= ok(all(isinstance(v, float) for v in got.values()),
                   f"{cell}: every .setup reader gives a number: " + json.dumps(
                       {k: round(v, 3) for k, v in got.items()}))
        good &= ok(0 <= got["setup_unspanned_s"] < 0.1 * setup_s,
                   f"{cell}: setup_unspanned_s.setup "
                   f"{got['setup_unspanned_s']:.3f} s is under a tenth of "
                   f"setup_s {setup_s:.3f}")
        p = setupspans.partition(ctx)
        named = sum(v for k, v in p.items() if k not in (
            "setup_unspanned_s", "startup_cache_miss_s", "setup_s",
            "overlap_s"))
        good &= ok(abs(named - p["overlap_s"] + p["setup_unspanned_s"]
                       - setup_s) < 1e-6 and 0 <= p["overlap_s"] < 0.05 * setup_s,
                   f"{cell}: the parts ({named:.3f} s, of which "
                   f"{p['overlap_s']:.3f} under a phase and a compile span "
                   f"both) and the unspanned sum to setup_s; the pre-roll "
                   f"{p['preroll_s']:.2f}")
        good &= ok(got["startup_compile_s"] - 1e-6 <= ctx["snap_before"][
            "profile"]["compiles"]["seconds"] and abs(
            p["startup_compile_s"] - got["startup_compile_s"]) < 0.01,
                   f"{cell}: the accepted counter and the compile.backend "
                   f"spans agree ({got['startup_compile_s']:.3f} | "
                   f"{p['startup_compile_s']:.3f})")
        spans = setupspans.spans(ctx)
        warm = [s for s in spans if s["name"].startswith("startup.warmup:")]
        good &= ok(bool(warm) == warms_up and
                   (got["startup_first_run_s"] > 0) == warms_up,
                   f"{cell}: a warm-up in the launcher and first runs: "
                   f"{warms_up}")
        for w in warm:
            inside = [s for s in spans if s is not w and w["a"] <= s["a"]
                      and s["b"] <= w["b"]]
            total = sum(s["b"] - s["a"] for s in inside)
            length = w["b"] - w["a"]
            good &= ok(length - 1.0 < total <= length + 1e-6,
                       f"{cell}: {w['name']}'s {len(inside)} children sum to "
                       f"{total:.3f} of its {length:.3f} s")
            four = sum(got[k] for k in ("startup_trace_s", "startup_lower_s",
                                        "startup_compile_s",
                                        "startup_first_run_s"))
            outside = sum(s["b"] - s["a"] for s in spans
                          if s["name"].startswith("compile.")
                          and s not in inside)
            good &= ok(abs(four - outside - length) < 1.0,
                       f"{cell}: trace + lower + compile + first run "
                       f"{four:.3f} s, less the {outside:.3f} of compile "
                       f"spans outside the warm-up, is its length to 1 s")
        c = ctx["snap_before"]["profile"]["compiles"]
        rows = c["by_scope"].values()
        good &= ok(all(abs(c[a] - sum(r[b] for r in rows)) < 1e-6
                       for a, b in (("seconds", "seconds"),
                                    ("trace_seconds", "trace_s"),
                                    ("lower_seconds", "lower_s"),
                                    ("cache_hits", "hits"),
                                    ("count", "count"))),
                   f"{cell}: compiles' sums equal the sums over by_scope "
                   f"({c['count']} compiles, {c['cache_hits']} hits)")
        # The parent's snapshot: the same list without the clock.
        old = dict(ctx, snap_before={"profile": {
            k: v for k, v in ctx["snap_before"]["profile"].items()
            if k != "startup_clock"}})
        good &= ok(all(reader(name)(old) is None for name in NEW)
                   and all(reader(name)(old) == got[name[:-6]]
                           for name in ACCEPTED),
                   f"{cell}: without startup_clock the seven read None and "
                   f"the accepted three what they read")
    print("check_setup_spans " + ("passed" if good else "FAILED"))
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
