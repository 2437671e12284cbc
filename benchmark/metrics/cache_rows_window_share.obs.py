"""Percent of the cache rows the window's decode waves read that were ring
rows of the sliding-window layers (counters ``fetched_rows_window`` over that
and ``fetched_rows_global``; the rest: the global layers' whole contexts)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "fetched_rows_window" not in w["counters"]:
        return None
    c = w["counters"]
    return progspans.ratio(
        c["fetched_rows_window"],
        c["fetched_rows_window"] + c["fetched_rows_global"], 100.0)
