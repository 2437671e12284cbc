"""Prompts a prefill-by-pieces call held, in the mean over the window:
counter ``prefill_pieces`` (a lane's piece each) over the count of the span
gen.prefill_dispatch (a program each).  1 where every piece program holds
one prompt; over 1 where the worker found several prompts in line and ran a
program of several lanes.  Nothing where the program prefills in one shot
(no piece is counted) or dispatched none in the window."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    pieces = w["counters"].get("prefill_pieces", 0)
    calls = w["spans"].get("gen.prefill_dispatch", {}).get("count", 0)
    return progspans.ratio(pieces, calls) if pieces else None
