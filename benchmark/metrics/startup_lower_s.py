"""Seconds JAX spent lowering jaxprs to MLIR modules before the window
started (spans ``compile.lower``, summed)."""
import setupspans


def read(ctx):
    return setupspans.summed(ctx, setupspans.LOWER)
