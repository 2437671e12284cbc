"""Percent of the window's token gaps (a lane of a decode fetch each) that
held at least one prefill call between their two waves."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "gap_lanes_behind_prefill",
                                   "gap_lanes", 100.0)
