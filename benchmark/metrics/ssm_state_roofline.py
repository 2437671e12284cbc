"""The state-space kernel's share of its roofline: the least seconds the chip
needs for one M layer's ``ssd_wave_update`` at the mean live lanes of the
window's waves (the family's ``ssm_update``: the live lanes' states read once
and written once, float32, x, B, C and dt in and y out) times every call of
that name the trace holds in ``jit_decode``, over those calls' device time
(``kernel_share``).  Nothing where the family has no such kernel or the trace
no such event."""
import family


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "ssm_update"):
        return None
    m = fam.wave_means(ctx)
    if m is None:
        return None
    return fam.kernel_share(ctx, [(
        lambda name: "ssd_wave_update" in name,
        fam.ssm_update(ctx["cfg"], m[0]))])
