"""Percent of the cache rows the window's decode waves read that were chunk
summaries (the rest: exact rows of the streams' current windows)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "fetched_rows_summary" not in w["counters"]:
        return None
    c = w["counters"]
    return progspans.ratio(
        c["fetched_rows_summary"],
        c["fetched_rows_summary"] + c["fetched_rows_exact"], 100.0)
