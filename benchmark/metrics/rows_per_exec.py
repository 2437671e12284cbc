"""Rows per model execution over the window (batcher's counters)."""
import reduce


def read(ctx):
    return reduce.rows_per_exec(ctx)
