"""XLA backend compilations inside the window, by the program's own
counter (/v2/profile compiles.count, differenced)."""
import progspans


def read(ctx):
    a = progspans.compiles(ctx.get("snap_before"))
    b = progspans.compiles(ctx.get("snap_after"))
    if a is None or b is None:
        return None
    return float(b["count"] - a["count"])
