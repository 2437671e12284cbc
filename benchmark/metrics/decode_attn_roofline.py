"""The decode kernel's share of its roofline: the least seconds the chip
needs for one layer's ``decode_wave_attention`` at the mean live lanes and
live cache rows of the window's waves (the model family's
``decode_attention``: rows read once, in the cache's dtype), times the calls
the trace holds (``jit_decode`` programs x layers), over the kernel's device
time in the trace.  The kernel has to be one operation of the trace's ten
longest (layers under one ``scan`` share a name); else nothing is read."""
import family
import progspans
import roofline


def read(ctx):
    tr = ctx["trace"] or {}
    fam = family.load(ctx["cfg"]["family"])
    busy = sum(s for name, s in tr.get("device_ops") or []
               if "decode_wave_attention" in name)
    step = (tr.get("modules") or {}).get("jit_decode")
    if not busy or not step or not hasattr(fam, "decode_attention"):
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
    calls = step["count"] * int(ctx["cfg"]["num_hidden_layers"])
    return 100.0 * calls * least / busy
