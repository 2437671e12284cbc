"""Percent of the KV arena's rows (slots x positions) holding live context,
averaged over the window's decode waves."""
import reduce


def read(ctx):
    return reduce.kv_live_share(ctx)
