"""What one prefill between two waves costs a waiting stream, in
milliseconds: the mean token gap that held a prefill call less the mean gap
that held none, both at the worker's clock."""
import gapclasses


def read(ctx):
    both = gapclasses.classes(ctx)
    return None if both is None else gapclasses.prefill_cost_ns(*both) * 1e-6
