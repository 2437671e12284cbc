"""How late the load generator sent, 95th percentile (send less due)."""
import reduce


def read(ctx):
    ms = reduce.late_ms(ctx)
    return None if ms is None else reduce.pct(ms, 95)
