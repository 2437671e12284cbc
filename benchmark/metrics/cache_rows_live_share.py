"""Percent of the cache arena's rows (stream slots x the rows a slot holds,
``serve.cache_slot_rows``) that a decode wave read, averaged over the waves
the device ran in the window: the scheduler's own count of each wave's
summary and exact rows (a backend whose cache is not one slot per position;
``arena_live_share``'s twin for it)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    serve = ctx["cfg"]["serve"]
    if w is None or "cache_slot_rows" not in serve:
        return None
    c = w["counters"]
    if "fetched_rows_exact" not in c:
        return None
    capacity = int(serve["kwargs"]["max_streams"]) * int(
        serve["cache_slot_rows"])
    return progspans.ratio(
        c["fetched_rows_exact"] + c["fetched_rows_summary"],
        c.get("fetched_waves", 0) * capacity, 100.0)
