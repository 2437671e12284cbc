"""Percent of the live lanes of the window's decode waves whose context had
outgrown the sliding window (counters ``fetched_lanes_past_window`` over
``fetched_lanes_live``): the lanes a ring saves reads for."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "fetched_lanes_past_window" not in w["counters"]:
        return None
    c = w["counters"]
    return progspans.ratio(c["fetched_lanes_past_window"],
                           c.get("fetched_lanes_live", 0), 100.0)
