"""The whole step's share of the chip's roofline: the least seconds the chip
needs for the useful work the window's counters hold (its decode waves at
their live lanes and its prefill programs, ``roofline.py``'s counting rules)
over the seconds the counters span, in percent.  From the program's counters
and the harness's clock alone: a run with no trace reports it like a traced
one, and it names no program of the trace."""
import reduce


def read(ctx):
    return reduce.step_mfu_roofline(ctx)
