"""The dense products of a wave whose layers run several passes, against
their roofline: the least seconds the chip needs to read every layer's
weights once **a pass** (counter ``fetched_passes`` over ``fetched_waves``)
and the head once, at the mean live lanes of the window's waves (the family's
``dense_products``), over ``jit_decode``'s mean device time less what its
``decode_wave_attention_*`` events take of a program (the trace's table of
every operation).  It says whether a weight is read once a pass where it lies
or copied on the way.  Nothing where the family has no such products, the
program counts no passes or the trace holds no ``jit_decode``."""
import family
import progspans
import reduce
import roofline


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "dense_products"):
        return None
    step = ((ctx["trace"] or {}).get("modules") or {}).get("jit_decode")
    passes = progspans.counter_ratio(ctx, "fetched_passes", "fetched_waves")
    lanes = progspans.counter_ratio(ctx, "fetched_lanes_live",
                                    "fetched_waves")
    if not step or not step.get("count") or not passes or not lanes:
        return None
    attention = sum(seconds for seconds, _ in reduce.kernel_groups(
        ctx, lambda name: "decode_wave_attention" in name))
    dense = step["mean_ms"] / 1e3 - attention / step["count"]
    if dense <= 0:
        return None
    least, _ = roofline.min_seconds(
        *fam.dense_products(ctx["cfg"], lanes, passes),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least / dense
