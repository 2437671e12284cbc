"""Percent of prefill lanes that held a prompt (window)."""
import reduce


def read(ctx):
    return reduce.prefill_lane_fill(ctx)
