"""The online tail, recorded and not judged (moves first_response_p50_ms)."""
import reduce


def read(ctx):
    ms = reduce.response_ms(ctx)
    return None if ms is None else reduce.pct(ms, 95)
