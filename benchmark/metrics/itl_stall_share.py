"""Percent of the window's summed token gaps that lies in gaps longer than
three times the median gap: the part of itl_mean_ms that is stalls (an
admitting iteration's prefill call) and not the bare wave."""
import numpy as np

import reduce


def read(ctx):
    gaps = reduce.itl_gaps_ms(ctx)
    if gaps is None:
        return None
    long = gaps > 3.0 * np.median(gaps)
    return 100.0 * float(gaps[long].sum() / gaps.sum())
