"""Cache transitions (window dumps) the generative worker dispatched per
second of the window: the program's ``gen.transition_dispatch`` span count."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "gen.transition_dispatch" not in w["spans"]:
        return None
    return progspans.ratio(w["spans"]["gen.transition_dispatch"]["count"],
                           ctx["seconds"])
