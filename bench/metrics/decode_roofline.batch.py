"""Decode programs' share of their roofline in the batch cell: the least
time the window's decode work needs (per step, every weight of the
stage, every held expert included, and the live contexts' keys at peak
bandwidth, or the operations at peak rate, whichever is longer) over
their device time (trace)."""
from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "decode")
