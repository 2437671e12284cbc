"""Tokens a held expert computes in one expert layer of one decode wave, in
the mean: the (token, expert) pairs routed to experts held here (counter
``expert_pairs_local``, which the decode program returns behind a wave's
tokens) over waves x expert layers x experts held.  What the deployment's
experts see when the group serves these streams is the cell's target (4 for
128 lanes choosing 8 of 256)."""
import family
import progspans


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    m = fam.wave_means(ctx) if hasattr(fam, "wave_means") else None
    if m is None:
        return None
    return progspans.ratio(m[2], int(ctx["cfg"]["n_routed_experts"]))
