"""A whole prefill-piece program against its roofline: the least seconds the
chip needs for the mean piece program of the window (the family's
``piece_step``: every held weight read once a program; two operations a
weight and valid position for the projections, the router, the shared experts
and the share of the held experts a position chooses; four a (query, key)
pair, head and lane for the attention; the head's product in the share of the
programs that ran it), at the window's counters (``prefill_positions_valid``,
``prefill_pairs_window``, ``prefill_pairs_global``, ``prefill_heads``, each
over the count of the span gen.prefill_dispatch, a program each), over
``jit_prefill``'s mean device time in the trace.  Useful work only, whatever
implements it.  Nothing where the family has no such count, the program no
such counters (the parent of the PR that added them) or the trace no
``jit_prefill``."""
import family
import progspans
import roofline


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    w = progspans.window(ctx)
    step = ((ctx["trace"] or {}).get("modules") or {}).get("jit_prefill")
    if (not hasattr(fam, "piece_step") or w is None or not step
            or "prefill_pairs_window" not in w["counters"]):
        return None
    c = w["counters"]
    programs = w["spans"].get("gen.prefill_dispatch", {}).get("count", 0)
    if not programs or not c.get("prefill_positions_valid"):
        return None
    flops, nbytes = fam.piece_step(
        ctx["cfg"], c["prefill_positions_valid"] / programs,
        c["prefill_pairs_window"] / programs,
        c["prefill_pairs_global"] / programs, 1.0,
        c.get("prefill_heads", 0) / programs)
    least, _ = roofline.min_seconds(
        flops, nbytes, roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least * 1e3 / step["mean_ms"]
