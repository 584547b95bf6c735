"""Share of device busy time spent in the two prefill programs in the
batch cell: the B=1 chunks of each admitted request, each of which
streams every weight of the stage (trace)."""
from bench.metrics import busy_share


def read(ctx):
    return busy_share(ctx, "prefill")
