"""Seconds JAX spent tracing Python functions to jaxprs before the window
started (spans ``compile.trace``, the outermost of nested ones, summed)."""
import setupspans


def read(ctx):
    return setupspans.summed(ctx, setupspans.TRACE)
