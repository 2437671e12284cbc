"""Seconds of the launcher's warm-up under no compile span: every program's
first execution and the staging for it (spans ``startup.first_run:*``,
summed)."""
import setupspans


def read(ctx):
    return setupspans.summed(ctx, setupspans.FIRST_RUN)
