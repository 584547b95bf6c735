"""Prefill programs' share of their roofline: the least time the
window's prefill work needs (per chunk, operations at peak rate or bytes
at peak bandwidth, whichever is longer) over their device time
in the batch cell (trace)."""
from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "prefill")
