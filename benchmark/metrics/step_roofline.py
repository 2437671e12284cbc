"""Least time the chip could take for the step (roofline.py) over the
step's device time from the trace, in percent."""
import reduce


def read(ctx):
    return reduce.step_roofline(ctx)
