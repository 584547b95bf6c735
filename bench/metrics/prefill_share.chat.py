"""Share of device busy time spent in the two prefill programs (trace)."""
from bench.metrics import busy_share


def read(ctx):
    return busy_share(ctx, "prefill")
