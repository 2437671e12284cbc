"""Percent of the lanes of the window's one-shot prefill programs that held
a prompt (the rest were padded up to the program's lane count).  Nothing
where the program does not count them, or prefills by pieces only."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "prefill_lanes_live" not in w["counters"]:
        return None
    c = w["counters"]
    live = c["prefill_lanes_live"]
    return progspans.ratio(live, live + c.get("prefill_lanes_padded", 0),
                           100.0)
