"""Seconds of XLA backend compilation before the window started (warm: the
persistent cache's loads; cold: the compiles)."""
import progspans


def read(ctx):
    c = progspans.compiles(ctx.get("snap_before"))
    return float(c["seconds"]) if c else None
