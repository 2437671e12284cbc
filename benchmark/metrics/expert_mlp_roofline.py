"""The un-gated experts' two grouped matmuls' share of their roofline: the
least seconds the chip needs for a full wave's up and down products at the
mean pairs and touched experts of the window's waves (the family's
``expert_ffn``: the touched experts' two matrices read once) times every call
the trace holds of the full wave's ``grouped_matmul`` groups in ``jit_decode``
(``_f32_<wave rows>_<moe_intermediate_size>_`` and ``_<hidden_size>_``), over
those calls' device time (``kernel_share``).  Waves of a smaller bucket run
operations of other shapes (``wave_rows``), so the groups' events are the full
bucket's alone.  ``expert_ffn_roofline`` looks for a gated expert's ``2 x
moe_intermediate_size`` columns; this one reads a family that declares its
experts two matrices (``EXPERT_FORM = "plain"``).  Nothing where there is
nothing to read."""
import family


def read(ctx):
    fam = family.load(ctx["cfg"]["family"])
    if getattr(fam, "EXPERT_FORM", "gated") != "plain":
        return None
    m = fam.wave_means(ctx)
    if m is None:
        return None
    rows = fam.wave_rows(ctx["cfg"])
    width = {"up": int(ctx["cfg"]["moe_intermediate_size"]),
             "down": int(ctx["cfg"]["hidden_size"])}
    return fam.kernel_share(ctx, [
        (lambda name, tag=f"_f32_{rows}_{n}_": "grouped_matmul" in name
         and tag in name, fam.expert_ffn(ctx["cfg"], m[2], m[3], part))
        for part, n in width.items()])
