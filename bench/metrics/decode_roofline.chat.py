"""Decode programs' share of their roofline: the least time the
window's decode work needs (per step, operations at peak rate or bytes
at peak bandwidth, whichever is longer) over their device time
(trace)."""
from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "decode")
