"""Mean milliseconds of the host's work around one prefill piece
(gen.prefill_stage: the piece's matrices, ``_stage_lanes`` and the
bookkeeping, exclusive of the jitted call gen.prefill_dispatch).  Nothing
where the program prefills in one shot and never opens the span."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    s = w["spans"].get("gen.prefill_stage", {})
    return progspans.ratio(s.get("total_ns", 0), s.get("count", 0), 1e-6)
