"""Mean device time of the cell's jitted step, from the device trace."""
import reduce


def read(ctx):
    return reduce.step_ms(ctx)
