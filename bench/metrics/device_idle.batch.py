"""Share of the batch cell's traced window with no operation on the
device (trace)."""
from bench.metrics import idle


def read(ctx):
    return idle(ctx)
