"""Seconds of backend compilation before the window started whose program
the persistent cache did not hold (spans ``compile.backend`` with ``cache``
"miss", summed): 0 on a warm launch, the compiler's seconds on a cold one or
after an eviction."""
import setupspans


def read(ctx):
    return setupspans.summed(ctx, setupspans.BACKEND,
                             where=lambda s: s.get("cache") == "miss")
