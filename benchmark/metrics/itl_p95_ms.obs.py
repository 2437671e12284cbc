"""95th percentile of the gap between streamed tokens (recorded)."""
import reduce


def read(ctx):
    gaps = reduce.itl_gaps_ms(ctx)
    return None if gaps is None else reduce.pct(gaps, 95)
