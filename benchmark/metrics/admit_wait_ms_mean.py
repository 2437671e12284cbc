"""Mean milliseconds a prompt stood in the engine's queue before the
generative worker gave it a slot (enqueue to ``_admit_batch``), over the
prompts whose prefill started in the window."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "admit_wait_ns", "prompts_started",
                                   1e-6)
