"""Mean milliseconds of the jitted decode call to its return
(gen.wave_dispatch): the enqueue, and where a full runtime queue blocks."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None:
        return None
    s = w["spans"].get("gen.wave_dispatch", {})
    return progspans.ratio(s.get("total_ns", 0), s.get("count", 0), 1e-6)
