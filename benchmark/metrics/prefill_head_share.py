"""Share of the window's piece programs that computed a head, in percent:
counter ``prefill_heads`` (a dispatched piece program in which some lane's
piece was its prompt's last, the only programs whose head runs under the
piece frame's conditional) over the count of the span gen.prefill_dispatch
(a program each).  100 / (pieces a prompt) where every program holds one
prompt; the rest of the programs read no row of the vocabulary's matrix.
Nothing where the program has no such counter (the parent of the PR that
added it), prefills in one shot or dispatched no piece in the window."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "prefill_heads" not in w["counters"]:
        return None
    if not w["counters"].get("prefill_pieces", 0):
        return None
    calls = w["spans"].get("gen.prefill_dispatch", {}).get("count", 0)
    return progspans.ratio(w["counters"]["prefill_heads"], calls, 100.0)
