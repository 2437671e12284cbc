"""Percent of the window's summed token gaps that prefills put there: the
gaps behind a prefill times what one costs (``prefill_gap_cost_ms``), over
the sum of all gaps.  The inside twin of ``itl_stall_share.obs``, which is
blind where a prefill call is shorter than two steps."""
import gapclasses


def read(ctx):
    both = gapclasses.classes(ctx)
    if both is None:
        return None
    behind, plain = both
    return 100.0 * behind[0] * gapclasses.prefill_cost_ns(behind, plain) \
        / (behind[1] + plain[1])
