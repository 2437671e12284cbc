"""The state kernel's share of its roofline: the least seconds the chip needs
for one KDA layer's ``kda_wave_update`` at the mean live lanes of the window's
waves (the family's ``kda_update``: the live lanes' states read once and
written once, float32) times every call of that name the trace holds in
``jit_decode``, over those calls' device time (``kernel_share``).  Nothing
where the family has no such kernel."""
import family


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "kda_update"):
        return None
    m = fam.wave_means(ctx)
    if m is None:
        return None
    return fam.kernel_share(ctx, [(
        lambda name: "kda_wave_update" in name,
        fam.kda_update(ctx["cfg"], m[0]))])
