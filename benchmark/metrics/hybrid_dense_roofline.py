"""The dense products of a wave of a dense decoder with a recurrent state,
against their roofline: the least seconds the chip needs to read every
mixer's two projections, every layer's feed-forward and the tied head once at
the mean live lanes of the window's waves (the family's ``wave_dense``), over
``jit_decode``'s mean device time less what its ``ssd_wave_update_*`` and
``decode_wave_attention_*`` events take of a program (the trace's table of
every operation).  It says whether the weights are read once where they lie.
``loop_dense_roofline.itl`` subtracts the attention alone and would count the
state kernels as dense time.  What is left in the denominator beside the
products (the convolutions, the norms, the head's choice) is the dense part's
own: the share cannot pass 100%.  Nothing where the family has no such
products (every family but one, and the parent of the PR that added it) or the
trace holds no ``jit_decode``."""
import family
import progspans
import reduce
import roofline


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "wave_dense"):
        return None
    step = ((ctx["trace"] or {}).get("modules") or {}).get("jit_decode")
    lanes = progspans.counter_ratio(ctx, "fetched_lanes_live",
                                    "fetched_waves")
    if not step or not step.get("count") or not lanes:
        return None
    kernels = sum(seconds for seconds, _ in reduce.kernel_groups(
        ctx, lambda name: "ssd_wave_update" in name
        or "decode_wave_attention" in name))
    dense = step["mean_ms"] / 1e3 - kernels / step["count"]
    if dense <= 0:
        return None
    least, _ = roofline.min_seconds(
        *fam.wave_dense(ctx["cfg"], lanes),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least / dense
