"""Percent of executed rows that were padding up to the batch bucket."""
import reduce


def read(ctx):
    return reduce.padded_row_share(ctx)
