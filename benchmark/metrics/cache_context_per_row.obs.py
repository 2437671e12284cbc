"""Positions of context a decode wave covered per cache row it read:
``fetched_positions_valid`` over the summary and exact rows (1 for a cache
of one row a position, ``chunk_size`` in the limit of a long context)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "fetched_rows_summary" not in w["counters"]:
        return None
    c = w["counters"]
    return progspans.ratio(
        c.get("fetched_positions_valid", 0),
        c["fetched_rows_summary"] + c["fetched_rows_exact"])
