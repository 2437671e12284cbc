"""Seconds the accelerator backend (PjRt client) took to come up."""
import progspans


def read(ctx):
    return progspans.startup_seconds(ctx.get("snap_before"),
                                     "startup.backend_init")
