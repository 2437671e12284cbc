"""Median gap between streamed tokens of one stream, in the window."""
import reduce


def read(ctx):
    gaps = reduce.itl_gaps_ms(ctx)
    return None if gaps is None else reduce.pct(gaps, 50)
