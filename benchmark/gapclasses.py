"""The token gap as the generative worker produces it, by what stood between
two decode waves: the program's ``gap_*`` counters over the window
(``progspans.window``).  A gap is the time from one decode fetch to the next
with no ``gen.idle`` between them, weighted by the later wave's live lanes;
the gaps *behind a prefill* held at least one prefill call (a piece, or a
one-shot program), the *plain* ones none.
"""

from __future__ import annotations

import progspans


def classes(ctx):
    """((lanes, ns) behind a prefill, (lanes, ns) plain).  ``None`` where the
    program has no such counters (the parent of the PR that added them) or
    either class is empty: there is then no cost to take."""
    w = progspans.window(ctx)
    if w is None:
        return None
    c = w["counters"]
    behind = (c.get("gap_lanes_behind_prefill", 0),
              c.get("gap_lane_behind_prefill_ns", 0))
    plain = (c.get("gap_lanes", 0) - behind[0],
             c.get("gap_lane_ns", 0) - behind[1])
    return (behind, plain) if behind[0] and plain[0] else None


def prefill_cost_ns(behind, plain) -> float:
    """Mean gap behind a prefill less the mean plain gap: what one prefill
    between two waves costs a waiting stream."""
    return behind[1] / behind[0] - plain[1] / plain[0]
