"""Percent of the window's tokens that left the scheduler's worker in a
wave's record (one hand-off a fetched wave to the stream writer), not as one
``InferResponse`` each: the share of tokens the mechanism carried.  Nothing
where the program counts neither (the parent of the PR that added them)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or "emitted_tokens" not in w["counters"]:
        return None
    c = w["counters"]
    waved = c["emitted_tokens"]
    return progspans.ratio(waved, waved + c.get("emitted_tokens_callback", 0),
                           100.0)
