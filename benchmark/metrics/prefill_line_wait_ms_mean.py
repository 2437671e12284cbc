"""Mean milliseconds from a prompt's slot to the return of its first prefill
call (its first piece, or its one-shot program): the line it stands in while
older prompts' pieces go first, one call between two waves."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "prefill_line_wait_ns",
                                   "prompts_started", 1e-6)
