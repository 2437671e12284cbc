"""The dense products of a wave of a parallel-block decoder against their
roofline: the least seconds the chip needs to read every layer's four
projections and its shared experts' pair once, and the tied head over the
vocabulary's slice, at the mean live lanes of the window's waves (the
family's ``dense_products(cfg, lanes)``), over ``jit_decode``'s mean device
time less what its ``decode_wave_attention_*``, ``window_wave_attention_*``
and ``grouped_matmul_*`` events take of a program (the trace's table of every
operation).  It says whether the wide projections and the shared pair are
read at the chip's bandwidth.  Nothing where the family has no such products
of that form or the trace holds no ``jit_decode``."""
import family
import progspans
import reduce
import roofline

KERNELS = ("decode_wave_attention", "window_wave_attention", "grouped_matmul")


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "dense_products") or not hasattr(fam, "piece_step"):
        return None
    step = ((ctx["trace"] or {}).get("modules") or {}).get("jit_decode")
    lanes = progspans.counter_ratio(ctx, "fetched_lanes_live",
                                    "fetched_waves")
    if not step or not step.get("count") or not lanes:
        return None
    kernels = sum(seconds for seconds, _ in reduce.kernel_groups(
        ctx, lambda name: any(k in name for k in KERNELS)))
    dense = step["mean_ms"] / 1e3 - kernels / step["count"]
    if dense <= 0:
        return None
    least, _ = roofline.min_seconds(
        *fam.dense_products(ctx["cfg"], lanes),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least / dense
