"""Mean gap between streamed tokens of one stream, over every token of the
window: the time per output token."""
import reduce


def read(ctx):
    gaps = reduce.itl_gaps_ms(ctx)
    return None if gaps is None else float(gaps.mean())
