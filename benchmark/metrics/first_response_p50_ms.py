"""Median milliseconds from due time to the whole response (open loop)."""
import reduce


def read(ctx):
    ms = reduce.response_ms(ctx)
    return None if ms is None else reduce.pct(ms, 50)
