"""Peaks of the chip, and the operations and bytes a step needs.

The table of peaks is keyed by ``device_kind`` as JAX reports it; a device
that is not in the table is an error, never a default.  Each model family
(``benchmark/models/<family>.py``) computes, from shapes alone, the
floating-point operations and the bytes the *algorithm* needs for the useful
work of one decode wave (its **live** lanes at their valid context; a
bucket's padded lanes are not work) and of the window's prefill programs (the
prompts' own positions).  ``min_seconds`` is the larger of operations over
peak FLOP/s and bytes over peak bytes/s.  A kernel's roofline share is that
over the kernel's device time from the trace; the whole step's
(``step_mfu_roofline.itl``, ``reduce.step_mfu_roofline``) is the least
seconds of everything the window's counters hold over the seconds they span.

Counting rules, chosen so that a share can never be flattered:

- a multiply-add is 2 operations; only matrix multiplications and the two
  attention products are counted (layer norms, softmax, gelu are left out);
- causal attention counts the lower triangle only (what the algorithm
  needs, even where a kernel computes whole blocks);
- bytes are the least the step must move: every weight it uses once, the
  embedding rows it gathers, its inputs and outputs, and the key/value rows
  it writes or must read (the *valid* context, not the arena's reserved
  rows).  Re-reads, spills and copies the program makes are not counted.
"""

from __future__ import annotations

# Published peaks of one chip.  Source: Google Cloud documentation, "TPU
# v5e" system architecture page (197 TFLOP/s bf16, 819 GB/s HBM2e, 16 GB).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a "
                       f"row to benchmark/roofline.py PEAKS with its source")
    return PEAKS[device_kind]


def min_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound holds)."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def next_bucket(n: int, cap: int) -> int:
    """The program's bucket ladder: powers of two below ``cap``, then
    ``cap`` (client_tpu.engine.scheduler.power_buckets)."""
    b = 1
    while b < n and b < cap:
        b *= 2
    return min(b, cap)
