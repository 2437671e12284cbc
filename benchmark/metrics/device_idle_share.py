"""Share of the traced window in which no operation ran on the device."""
import reduce


def read(ctx):
    return reduce.device_idle_share(ctx)
