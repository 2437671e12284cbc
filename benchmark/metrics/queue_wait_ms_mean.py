"""Mean milliseconds a request waited in the batcher's queue (window)."""
import reduce


def read(ctx):
    d = reduce.stats_delta(ctx)
    if not d or not d["queue_count"]:
        return None
    return d["queue_ns"] / d["queue_count"] / 1e6
