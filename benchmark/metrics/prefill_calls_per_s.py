"""Prefill calls the generative worker dispatched per second of the window
(the program's gen.prefill_dispatch span count)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "gen.prefill_dispatch" not in w["spans"]:
        return None
    return progspans.ratio(w["spans"]["gen.prefill_dispatch"]["count"],
                           ctx["seconds"])
