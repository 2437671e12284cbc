"""The decode kernel's share of its roofline: the least seconds the chip
needs for one layer's ``decode_wave_attention`` at the mean live lanes and
live cache rows of the window's waves (the model family's
``decode_attention``: rows read once, in the cache's dtype), times the calls
the trace holds, over the kernel's device time in the trace
(``reduce.kernel_groups``: every event of that name in ``jit_decode``; the
layers run under one ``scan``, and the profiler records an operation inside
it once an iteration).  Nothing where the trace holds no such event."""
import family
import progspans
import reduce
import roofline


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "decode_attention"):
        return None
    found = reduce.kernel_groups(
        ctx, lambda name: "decode_wave_attention" in name,
        int(ctx["cfg"]["num_hidden_layers"]))
    if not found:
        return None
    rows = fam.rows_per_wave(ctx)
    lanes = progspans.counter_ratio(ctx, "fetched_lanes_live",
                                    "fetched_waves")
    if rows is None or not lanes:
        return None
    flops, nbytes = fam.decode_attention(
        ctx["cfg"], lanes, (rows[0] + rows[1]) / lanes)
    least, _ = roofline.min_seconds(
        flops, nbytes, roofline.peaks_for(ctx["device"]["kind"]))
    return (100.0 * least * sum(calls for _, calls in found)
            / sum(seconds for seconds, _ in found))
