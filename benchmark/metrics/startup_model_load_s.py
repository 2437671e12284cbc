"""Seconds of model load (build, placement, arena), summed over models."""
import progspans


def read(ctx):
    return progspans.startup_seconds(ctx.get("snap_before"),
                                     "startup.model_load:")
