"""Mean device time of one whole ``jit_prefill`` program (an admitting
iteration's jitted call), from the device trace."""


def read(ctx):
    m = ((ctx["trace"] or {}).get("modules") or {}).get("jit_prefill")
    return m["mean_ms"] if m else None
