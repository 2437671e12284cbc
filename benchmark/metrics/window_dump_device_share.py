"""Percent of the traced window in which the program's ``jit_transition``
(a window dump) ran on the device.  A program that counts ``transitions``
(``/v2/profile``) and whose trace holds none read 0: at one dump every two
seconds a trace of four holds none one time in seven.  A program without the
counter (the parent of the PR that added it) reads nothing."""
import progspans


def read(ctx):
    tr = ctx["trace"] or {}
    w = progspans.window(ctx)
    if (not tr.get("window_s") or w is None
            or "transitions" not in w["counters"]):
        return None
    m = (tr.get("modules") or {}).get("jit_transition")
    return 100.0 * (m["total_s"] if m else 0.0) / tr["window_s"]
