"""Share of the traced window with no operation on the device, averaged
over the chips (trace)."""
from bench.metrics import idle


def read(ctx):
    return idle(ctx)
