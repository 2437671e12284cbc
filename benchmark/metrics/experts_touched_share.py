"""Percent of the held experts that got at least one token, over the expert
layers of the window's decode waves (counter ``experts_touched`` over waves x
expert layers x experts held): the share of the expert weights a wave has to
read (an untouched expert's matrices are not fetched)."""
import family
import progspans


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    m = fam.wave_means(ctx) if hasattr(fam, "wave_means") else None
    if m is None:
        return None
    return progspans.ratio(m[3], int(ctx["cfg"]["n_routed_experts"]), 100.0)
