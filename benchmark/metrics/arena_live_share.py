"""Percent of the key/value arena (stream slots x positions) holding live
context, averaged over the decode waves the device ran in the window, from
the scheduler's own count of each wave's valid positions (kv_live_share's
inside twin)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    capacity = (int(ctx["cfg"]["serve"]["kwargs"]["max_streams"])
                * int(ctx["traffic"]["max_model_len"]))
    c = w["counters"]
    return progspans.ratio(c.get("fetched_positions_valid", 0),
                           c.get("fetched_waves", 0) * capacity, 100.0)
