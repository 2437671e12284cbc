"""Percent of drains that took two or more decode fetches at once: their
tokens leave back to back (the pairs a client sees)."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "drains_multi", "drains", 100.0)
