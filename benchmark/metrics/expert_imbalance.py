"""The busiest held expert's pairs over the mean held expert's, in one
expert layer of one decode wave (counters ``expert_pairs_busiest`` over
``expert_pairs_local`` / experts held): how uneven the groups of the grouped
matmul are (1 = even)."""
import progspans


def read(ctx):
    w = progspans.window(ctx)
    if w is None or not w["counters"].get("expert_pairs_local"):
        return None
    c = w["counters"]
    return progspans.ratio(
        c["expert_pairs_busiest"] * int(ctx["cfg"]["n_routed_experts"]),
        c["expert_pairs_local"])
