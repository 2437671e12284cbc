"""Python the generative worker runs per decode dispatch, in milliseconds:
gen.loop less the calls that can block on the device (the jitted decode call
gen.wave_dispatch, the prefill dispatch gen.prefill_dispatch, whose jitted
call blocks the same way, and gen.fetch_wait) and less gen.idle, over the
window's dispatches."""
import progspans

BLOCKING = ("gen.fetch_wait", "gen.idle", "gen.wave_dispatch",
            "gen.prefill_dispatch")


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    host = progspans.span_ns(w, "gen.loop") - sum(
        progspans.span_ns(w, s) for s in BLOCKING)
    return progspans.ratio(host, w["counters"].get("dispatches", 0), 1e-6)
