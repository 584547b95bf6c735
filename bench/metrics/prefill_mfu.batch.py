"""Prefill programs' share of the chip's peak: the operations the
window's prefill work needs over their device time at the bfloat16 peak
rate in the batch cell (trace)."""
from bench.metrics import mfu


def read(ctx):
    return mfu(ctx, "prefill")
