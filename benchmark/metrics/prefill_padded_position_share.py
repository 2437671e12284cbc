"""Percent of the positions of the window's dispatched prefill pieces that
were padding up to the piece (counters ``prefill_positions_padded`` over it
and ``prefill_positions_valid``): work a piece does for nothing, and what a
layer with a recurrent state has to step over without moving it.  Nothing
where the program does not count them."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "prefill_positions_padded" not in w["counters"]:
        return None
    c = w["counters"]
    padded = c["prefill_positions_padded"]
    return progspans.ratio(padded, padded + c["prefill_positions_valid"],
                           100.0)
