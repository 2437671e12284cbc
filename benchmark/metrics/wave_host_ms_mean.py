"""Mean host-clock milliseconds per decode wave (/v2/profile, window)."""
import reduce


def read(ctx):
    w = reduce.waves_delta(ctx)
    if not w:
        return None
    return 1e3 * sum(s for _, s in w.values()) / sum(n for n, _ in w.values())
