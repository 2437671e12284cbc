"""Peak bytes in use on the fullest chip (memory_stats via /v2/memory)."""
import reduce


def read(ctx):
    return reduce.hbm_peak_bytes(ctx)
