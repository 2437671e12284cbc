"""Requests of the window answered 429 or 503."""
import reduce


def read(ctx):
    return reduce.rejected_count(ctx)
