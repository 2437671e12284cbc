#!/usr/bin/env python3
"""Holds ``BENCHMARK.json`` to the runs that chose its window and bounds.

1. ``spread``'s statistics, the check's three tests of a bound and the rule
   on hand-made sets.
2. For every cell and end-to-end metric that ``spread_sets.json`` holds runs
   of: ``run_seconds`` and that metric's ``bound`` are what the rule gives on
   them or, where it gives nothing, what the file records of the parent.  New
   evidence goes into the file; a manifest that parts from it fails here.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import spread  # noqa: E402


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    return 0 if ok else 1


def six(width, shift=0.0):
    """Six runs around 10.0 + ``shift``.  Less the farthest (the run at
    + 3 x width) their quartiles lie 5 / 8 x width apart, so the check's
    spread is width / 16 as a share of the median 10; the quartiles of all
    six lie 23 / 16 x width apart."""
    return [10.0 + shift + d * width
            for d in (-0.5, -0.25, 0.0, 0.0, 0.5, 3.0)]


def hand_made(seconds, width, sets=4):
    """``sets`` alike sets of ``six(width)`` at one window."""
    return [{"cell": "c", "metric": "m", "set": f"s{seconds}_{k}",
             "seed": i, "seconds": seconds, "value": v}
            for k in range(sets) for i, v in enumerate(six(width))]


def main() -> int:
    status = 0
    runs6 = [10.0, 10.1, 10.05, 10.6, 9.98, 10.02]
    status |= check(
        abs(spread.trimmed_range(runs6) - 0.12) < 1e-12
        and spread.trimmed_range([1.0, 2.0]) == 1.0,
        "the range leaves out the run farthest from the median")
    # less 10.6: 9.98 10.0 10.02 10.05 10.1, quartiles 9.99 and 10.075 by
    # statistics.quantiles; the median of all six is 10.035
    status |= check(
        abs(spread.check_spread(runs6) - 0.085 / 10.035) < 1e-9,
        "the check's spread: the quartile distance less the farthest run, "
        "over the median of all six")
    status |= check(
        abs(spread.check_spread(six(0.16)) - 0.01) < 1e-9
        and abs(spread.quartile_spread(six(0.16)) - 0.023) < 1e-9,
        "hand-made six of width 0.16: 1% less the farthest, 2.3% whole")
    status |= check(spread.run_seconds_ceiling() == 51,
                    "the contract's arithmetic admits run_seconds up to 51")
    # too tight: 0.5 / 16 = 3.125% of the median is over 40% of 7% (2.8%)
    # and within 40% of 8% (3.2%)
    status |= check(
        not spread.pair_passes(six(0.5), six(0.5), 0.07)
        and spread.pair_passes(six(0.5), six(0.5), 0.08),
        "a bound holds where the pair's mean spread is within 40% of it")
    # too loose: eight times 23 / 16 x 0.02 / 10 = 2.3%
    status |= check(
        not spread.pair_passes(six(0.02), six(0.02), 0.05)
        and spread.pair_passes(six(0.02), six(0.02), 0.02)
        and spread.pair_passes(six(0.0001), six(0.0001), 0.01),
        "a bound over eight times the wider spread is too loose; 1% never")
    status |= check(
        not spread.pair_passes(six(0.05), six(0.05, shift=0.5), 0.03)
        and spread.pair_passes(six(0.05), six(0.05, shift=0.4), 0.05),
        "two sets' medians may differ by the bound at most")
    # 0.5 wants 8%, 0.13 wants 3%, 0.07 wants 2%, 0.7 wants over 10%
    runs = hand_made(20, 0.50) + hand_made(40, 0.13) + hand_made(50, 0.07)
    status |= check(spread.choose(runs, "c", "m") == (50, 0.02),
                    "the tightest bound that holds at any window: (50, 0.02)")
    status |= check(
        spread.choose(hand_made(20, 0.13) + hand_made(40, 0.13), "c", "m")
        == (20, 0.03), "of two windows with the same bound, the shorter")
    status |= check(
        spread.choose(hand_made(40, 0.07, sets=3) + hand_made(50, 0.13),
                      "c", "m") == (50, 0.03),
        "a window with fewer than four sets of six is not a candidate")
    status |= check(
        spread.choose(hand_made(50, 0.7), "c", "m") is None
        and spread.choose(hand_made(50, 0.7) + hand_made(60, 0.07),
                          "c", "m") is None,
        "over 40% of 10%, or past the ceiling: nothing is chosen")

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    data = spread.load()
    bounds = {m["name"]: m["bound"] for m in manifest["end_to_end"]}
    pairs = sorted({(r["cell"], r["metric"]) for r in data["runs"]
                    if r["metric"] in bounds})
    status |= check(bool(pairs), "spread_sets.json holds runs")
    for cell, metric in pairs:
        got = spread.choose(data["runs"], cell, metric)
        want = got or (data["parent"]["run_seconds"],
                       data["parent"]["bounds"][metric])
        have = (manifest["run_seconds"], bounds[metric])
        status |= check(have == want, f"{cell} {metric}: the rule gives "
                        f"{got}, so {want}; BENCHMARK.json has {have}")
    return status


if __name__ == "__main__":
    sys.exit(main())
