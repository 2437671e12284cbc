"""Median of client time less the server's Server-Timing engine time."""
import reduce


def read(ctx):
    ms = reduce.outside_engine_ms(ctx)
    return None if ms is None else reduce.pct(ms, 50)
