"""A carrying piece program against the roofline of all it does: the mean
piece program of the window (the family's ``prefill_work``: every held weight
read once a program, the prompts' positions through them, ``piece_roofline``'s
count) **and the wave that rode in it** (PR 56) in the share of the programs
that carried one (counter ``fetched_waves_carried`` over the count of the span
gen.prefill_dispatch): the family's ``decode_step`` at the window's mean live
lanes, rows, expert pairs and touched experts, less what that step counts for
weights (the same step at no lane and no pair: the piece's pass has read
them), over ``jit_prefill``'s mean device time in the trace.
``piece_roofline.itl`` counts the piece's work alone over the same time, so it
falls where a wave rides; this one says what the one pass over the weights
does for both.  Nothing where the program counts no carried wave (the parent
of the PR that added the counter), the family has no such counts or the trace
no ``jit_prefill``."""
import family
import progspans
import roofline


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    w = progspans.window(ctx)
    step = ((ctx["trace"] or {}).get("modules") or {}).get("jit_prefill")
    if (w is None or not step or not hasattr(fam, "prefill_work")
            or "fetched_waves_carried" not in w["counters"]):
        return None
    c = w["counters"]
    programs = w["spans"].get("gen.prefill_dispatch", {}).get("count", 0)
    pieces = fam.prefill_work(ctx)
    waves, lanes = c.get("fetched_waves", 0), c.get("fetched_lanes_live", 0)
    if not programs or not pieces or not lanes:
        return None
    cfg = ctx["cfg"]
    layers = int(cfg["num_hidden_layers"])
    ring = sum(1 for s in cfg["sliding_window_layout"][:layers] if s)
    touched = c.get("experts_touched", 0) / waves / layers
    wave = fam.decode_step(
        cfg, lanes / waves, c.get("fetched_rows_window", 0) / lanes / ring,
        c.get("fetched_rows_global", 0) / lanes / (layers - ring),
        c.get("expert_pairs_local", 0) / waves / layers, touched)
    weights = fam.decode_step(cfg, 0.0, 0.0, 0.0, 0.0, touched)
    rode = c["fetched_waves_carried"] / programs
    least, _ = roofline.min_seconds(
        pieces[0] / programs + rode * (wave[0] - weights[0]),
        pieces[1] / programs + rode * (wave[1] - weights[1]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least * 1e3 / step["mean_ms"]
