"""A decode wave's attention kernels wherever they ran.

Since PR 56 a wave may ride in a prefill piece's program (the program's
``piece_wave``): its ``decode_wave_attention`` and ``window_wave_attention``
calls are then events of ``jit_prefill``, under the names they have in
``jit_decode``.  ``reduce.kernel_groups`` reads ``jit_decode`` alone, so the
accepted kernel shares see the lone waves only; the readers that use this
module take a kernel's events from both programs.

No call is counted.  A carrying program runs the wave's kernels whether a lane
rides or not (a wave of padded lanes), so calls say nothing of work; the work
is the streams' own: every token the load generator received in the traced
seconds, first tokens aside, came out of one wave's lane at a context the
harness knows (``cohere_moe._traced_waves``'s clock: ordinal ``k >= 1`` of a
prompt of ``P`` read ``P + k - 1`` positions).  Those lanes' rows, read once,
over the kernel's device time in the trace, every event of it: time a padded
call takes is the kernel's too.  Nothing where the program counts no carried
wave (the parent of the PR that added the counter), the run has no trace or no
token fell into it.
"""

from __future__ import annotations

import numpy as np

import family
import progspans
import reduce
import roofline

PROGRAMS = ("jit_decode", "jit_prefill")


def kernel_seconds(ctx, match) -> float:
    """Device seconds of the groups ``match`` accepts, over ``PROGRAMS``, from
    the trace's table of every operation (self time, ``tracereduce``)."""
    ops = (ctx.get("trace") or {}).get("program_ops") or {}
    return sum(seconds for program in PROGRAMS
               for name, (seconds, _) in (ops.get(program) or {}).items()
               if match(name))


def traced_contexts(ctx):
    """The context length behind every wave lane's token of the traced
    seconds (the harness's ``run.py`` ``trace_window``: ``trace_seconds`` from
    ``t1 - trace_end_margin_s - trace_seconds``, later by the start call's own
    time), or None."""
    w = progspans.window(ctx)
    tr = ctx.get("trace") or {}
    if (w is None or "fetched_waves_carried" not in w["counters"]
            or not tr.get("program_ops") or "ev_t" not in ctx):
        return None
    ev = reduce.stream_events(ctx)
    if ev is None:
        return None
    slot, t, ordinal = ev
    span = float(ctx["traffic"]["trace_seconds"])
    lo = (ctx["t1"] - float(ctx["traffic"]["trace_end_margin_s"]) - span
          + float(tr.get("start_call_s", 0.0)))
    hit = (t >= lo) & (t < lo + span) & (ordinal > 0)
    if not hit.any():
        return None
    return ctx["req"]["prompt_len"][slot[hit]] + ordinal[hit] - 1


def attention_share(ctx, kernel: str, ring: bool):
    """``kernel``'s share of its roofline over both programs, in percent: the
    least seconds for the traced seconds' lanes at their rows (a window
    layer's ring holds ``sliding_window_size - 1`` rows beside the one it
    overwrites) in every layer of the kernel's kind, by the family's
    ``window_attention`` / ``decode_attention``, over ``kernel_seconds``."""
    cfg = ctx["cfg"]
    fam = family.load(cfg["family"])
    cost = getattr(fam, "window_attention" if ring else "decode_attention",
                   None)
    n = traced_contexts(ctx) if cost else None
    seconds = kernel_seconds(ctx, lambda name: kernel in name)
    if n is None or not seconds:
        return None
    layout = cfg["sliding_window_layout"][:int(cfg["num_hidden_layers"])]
    layers = sum(1 for windowed in layout if bool(windowed) == ring)
    if ring:
        n = np.minimum(n, int(cfg["sliding_window_size"]) - 1)
    least, _ = roofline.min_seconds(
        *cost(cfg, float(n.size), float(n.mean())),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * layers * least / seconds
