"""Mean milliseconds from a prompt's prefill dispatch to its first token
reaching the worker (the server's share of TTFT after the queue)."""
import progspans


def read(ctx):
    return progspans.counter_ratio(ctx, "first_token_wait_ns",
                                   "first_tokens", 1e-6)
