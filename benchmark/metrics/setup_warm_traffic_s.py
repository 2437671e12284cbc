"""Seconds from the frontends' "serving" to the pre-roll's release, less the
compile spans inside: the harness's warm round as the program served it, and
the load generators' two starts."""
import setupspans


def read(ctx):
    return setupspans.warm_traffic_s(ctx)
