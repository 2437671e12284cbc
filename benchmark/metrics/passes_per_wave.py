"""The passes over its layers that a mean decode wave ran: counter
``fetched_passes`` over ``fetched_waves`` (what the backend declares; the
first thing to move when lanes may stop at different passes).  Nothing where
the program counts no passes."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "fetched_passes",
                                   "fetched_waves") or None
