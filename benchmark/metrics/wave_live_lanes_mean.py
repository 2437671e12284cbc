"""Mean live lanes (streams decoding) per decode wave the device ran in
the window (counted by the scheduler when the wave's tokens arrive)."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "fetched_lanes_live", "fetched_waves")
