"""Percent of the cache bytes a mean decode wave moves that are recurrent
state: the live lanes' states, read and written in every KDA layer, over
those and the live positions' latent rows, read in every latent layer
(counters ``fetched_lanes_live`` and ``fetched_positions_valid``; the sizes
from the configuration, by the family's ``cache_bytes``).  Nothing where the
family keeps no state."""
import family
import progspans


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    w = progspans.window(ctx)
    if w is None or not hasattr(fam, "cache_bytes"):
        return None
    c = w["counters"]
    state, rows = fam.cache_bytes(ctx["cfg"], c.get("fetched_lanes_live", 0),
                                  c.get("fetched_positions_valid", 0))
    return progspans.ratio(state, state + rows, 100.0)
