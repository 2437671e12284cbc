"""Tokens of requests completed in the window, per second of window."""
import reduce


def read(ctx):
    return reduce.tokens_per_s(ctx)
