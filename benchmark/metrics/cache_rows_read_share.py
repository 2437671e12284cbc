"""Percent of the cache arena's rows (stream slots x the rows a slot holds
over its layers: window layers x a ring + global layers x ``max_model_len``)
that a decode wave read, averaged over the window's waves (counters
``fetched_rows_window`` + ``fetched_rows_global`` over ``fetched_waves`` x the
capacity): ``arena_live_share``'s twin for a cache of two row shapes."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    cfg = ctx["cfg"]
    if (w is None or "fetched_rows_window" not in w["counters"]
            or "sliding_window_layout" not in cfg):
        return None
    c = w["counters"]
    layers = int(cfg["num_hidden_layers"])
    rings = sum(1 for s in cfg["sliding_window_layout"][:layers] if s)
    slot = (rings * int(cfg["sliding_window_size"])
            + (layers - rings) * int(ctx["traffic"]["max_model_len"]))
    capacity = int(cfg["serve"]["kwargs"]["max_streams"]) * slot
    return progspans.ratio(
        c["fetched_rows_window"] + c["fetched_rows_global"],
        c.get("fetched_waves", 0) * capacity, 100.0)
