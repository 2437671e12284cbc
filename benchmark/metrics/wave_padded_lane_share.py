"""Percent of the lanes of the window's decode waves that were padding up
to the wave bucket."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    c = w["counters"]
    padded = c.get("fetched_lanes_padded", 0)
    return progspans.ratio(padded, c.get("fetched_lanes_live", 0) + padded,
                           100.0)
