"""Percent of the generative worker's loop time spent in the three calls
that can block on the device: the jitted decode call (gen.wave_dispatch),
the prefill dispatch (gen.prefill_dispatch) and the fetch of a wave's tokens
(gen.fetch_wait), over gen.loop.  What is left is the host's own."""
import progspans

BLOCKING = ("gen.fetch_wait", "gen.wave_dispatch", "gen.prefill_dispatch")


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    return progspans.ratio(sum(progspans.span_ns(w, s) for s in BLOCKING),
                           progspans.span_ns(w, "gen.loop"), 100.0)
