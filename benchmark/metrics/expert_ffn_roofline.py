"""The grouped expert matmuls' share of their roofline: the least seconds
the chip needs for a full wave's gate-and-up and down products at the mean
pairs and touched experts of the window's waves (the family's
``expert_ffn``: the touched experts' matrices read once) times every call
the trace holds of the full wave's ``grouped_matmul`` groups, over those
calls' device time (``kernel_share``).  Waves of a smaller bucket run
operations of other shapes (``wave_rows``), so the groups' events are the
full bucket's alone; the full bucket's share of the window's waves scales
the steps of a trace reduced before PR 39 only."""
import family
import reduce


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    m = fam.wave_means(ctx) if hasattr(fam, "wave_means") else None
    waves = reduce.waves_delta(ctx)
    if m is None or not waves:
        return None
    rows = fam.wave_rows(ctx["cfg"])
    width = {"up": 2 * int(ctx["cfg"]["moe_intermediate_size"]),
             "down": int(ctx["cfg"]["hidden_size"])}
    parts = [(lambda name, tag=f"_f32_{rows}_{n}_": "grouped_matmul" in name
              and tag in name, fam.expert_ffn(ctx["cfg"], m[2], m[3], part))
             for part, n in width.items()]
    top = int(ctx["cfg"]["serve"]["kwargs"]["max_streams"])
    share = waves.get(top, (0, 0.0))[0] / sum(n for n, _ in waves.values())
    return fam.kernel_share(ctx, parts, share)
