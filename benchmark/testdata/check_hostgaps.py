#!/usr/bin/env python3
"""Checks ``hostgaps`` against a piece of a real trace:
``recorded_v5e_chat_hostspans.xplane.pb.gz``, the programs and the program's
own host spans of PR 24's traced run of ``gpt2_small.chat`` on the TPU v5e
(cut to its ``XLA Modules`` events and the ``gen.*`` spans, times kept to
the nanosecond).  Its figures were computed once by an
independent brute-force method, nanosecond by nanosecond
(``raster_by_span`` below), and are recomputed that way here as well.

What the piece shows: the worker sits inside the jitted calls.  Each of the
two iterations it holds spends 0.89 s in ``gen.prefill_dispatch`` and 0.9 s
in ``gen.wave_dispatch``; each call returns as one ``jit_decode`` ends (the
runtime admits one program as one completes), and the few microseconds the
device idles between programs fall under those two spans.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import hostgaps  # noqa: E402
import tracereduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_v5e_chat_hostspans.xplane.pb.gz")
RECORDED_GAPS = 5
RECORDED_IDLE_NS = 54361
RECORDED_BY_SPAN = {"gen.prefill_dispatch": 26582, "gen.wave_dispatch": 18274,
                    hostgaps.NO_SPAN: 9505}
RECORDED_HOST_SPANS = {"gen.fetch_wait": 6, "gen.emit": 6, "gen.loop": 2,
                       "gen.admit": 2, "gen.prefill_dispatch": 2,
                       "gen.sweep": 2, "gen.wave_stage": 2,
                       "gen.wave_dispatch": 2}


def raster_by_span(pd) -> dict[str, int]:
    """Independent of ``hostgaps.attribute``: for every idle nanosecond
    between programs, the shortest host span that contains it."""
    spans = hostgaps.host_spans(pd)
    out: dict[str, int] = {}
    for _, _, mods in tr.device_lines(pd):
        mods = sorted(mods, key=lambda m: m[1])
        end = mods[0][2]
        for _, s, e in mods[1:]:
            for t in range(int(end), int(s)):
                best = None
                for name, a, b in spans:
                    if a <= t and t + 1 <= b and (
                            best is None or b - a < best[1]):
                        best = (name, b - a)
                key = best[0] if best else hostgaps.NO_SPAN
                out[key] = out.get(key, 0) + 1
            end = max(end, e)
    return out


def main() -> int:
    pd = tr.load(RECORDED)
    out = hostgaps.reduce_gaps(pd)
    assert out["gaps"] == RECORDED_GAPS, out
    assert round(out["idle_s"] * 1e9) == RECORDED_IDLE_NS, out
    by = {name: round(s * 1e9) for name, s, _ in out["by_span"]}
    assert by == RECORDED_BY_SPAN, by
    assert by == raster_by_span(pd), (by, raster_by_span(pd))
    assert sum(by.values()) == RECORDED_IDLE_NS
    assert out["host_spans"] == RECORDED_HOST_SPANS, out["host_spans"]
    # Every gap is named, by a gen.* span or as "no span" (counted).
    assert sum(n for _, _, n in out["by_span"]) == RECORDED_GAPS
    # Host and device share a clock: each blocking call of the worker ends
    # within 5 ms after a jit_decode program does.
    ends = sorted(e for _, _, e in tr.device_lines(pd)[0][2])
    for name, _, b in hostgaps.host_spans(pd):
        if name in ("gen.prefill_dispatch", "gen.wave_dispatch"):
            lag = min(b - e for e in ends if e <= b)
            assert 0 <= lag < 5_000_000, (name, lag)
    print("check_hostgaps: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
