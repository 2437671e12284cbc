"""Share of the window's decode waves whose tokens came out of a piece's
program, in percent: counter ``fetched_waves_carried`` (a fetched wave that
rode in the prefill piece of its token gap: one pass over the weights for
both) over ``fetched_waves`` (every fetched wave, carried or lone).  About the
share of the gaps that hold a piece where every such gap's wave rides; 0 where
the backend's piece programs carry a wave and none rode.  Nothing where the
program has no such counter (the parent of the PR that added it, and so
whatever its backends) or fetched no wave in the window."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "fetched_waves_carried" not in w["counters"]:
        return None
    c = w["counters"]
    return progspans.ratio(c["fetched_waves_carried"],
                           c.get("fetched_waves", 0), 100.0)
