"""Seconds from the operating system's start of the server's process to the
engine's construction that are no backend init: the interpreter and the
imports (spans ``startup.process`` + ``startup.imports``)."""
import setupspans


def read(ctx):
    return setupspans.summed(ctx, *setupspans.PROCESS)
