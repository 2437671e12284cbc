"""Seconds of ``setup_s`` under no span of the program's set-up timeline,
the warm traffic or the pre-roll."""
import setupspans


def read(ctx):
    return setupspans.unspanned_s(ctx)
