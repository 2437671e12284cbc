"""Time-average of streams in flight, by the generator's clocks."""
import reduce


def read(ctx):
    return reduce.open_streams_mean(ctx)
