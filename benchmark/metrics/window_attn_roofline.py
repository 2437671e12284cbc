"""The window layers' decode kernel's share of its roofline: the least
seconds the chip needs for one window layer's ``window_wave_attention`` at the
mean live lanes and ring rows of the window's waves (the family's
``window_attention``: a lane's live ring rows read once, 2 KB a row, counter
``fetched_rows_window``) times every call of that name the trace holds in
``jit_decode``, over those calls' device time (``kernel_share``).  Nothing
where the family has no ring or the program no such counter."""
import family


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if not hasattr(fam, "window_attention"):
        return None
    m, rows = fam.wave_means(ctx), fam.rows_by_kind(ctx)
    if m is None or rows is None:
        return None
    return fam.kernel_share(ctx, [(
        lambda name: "window_wave_attention" in name,
        fam.window_attention(ctx["cfg"], m[0], rows[0]))])
