"""Percent of the traced window in which the program's ``jit_prefill`` ran
on the device (no stream decodes meanwhile)."""


def read(ctx):
    tr = ctx["trace"] or {}
    m = (tr.get("modules") or {}).get("jit_prefill")
    if not m or not tr.get("window_s"):
        return None
    return 100.0 * m["total_s"] / tr["window_s"]
