"""The latent decode kernel's share of its roofline: the least seconds the
chip needs for one layer's ``latent_wave_attention`` at the mean live lanes
and context rows of the window's waves (the family's ``latent_attention``:
live rows read once, bfloat16) times every call of that name the trace holds
in ``jit_decode``, over those calls' device time (``kernel_share``)."""
import family


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    m = fam.wave_means(ctx) if hasattr(fam, "wave_means") else None
    if m is None:
        return None
    return fam.kernel_share(ctx, [(
        lambda name: "latent_wave_attention" in name,
        fam.latent_attention(ctx["cfg"], m[0], m[1]))])
