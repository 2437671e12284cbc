"""Median milliseconds from due time to the first streamed token
(recorded, not judged: about a hundred samples a window)."""
import reduce


def read(ctx):
    ms = reduce.response_ms(ctx, upto="first")
    return None if ms is None else reduce.pct(ms, 50)
