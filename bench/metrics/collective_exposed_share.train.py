"""Share of device busy time in an all-gather, reduce-scatter or other
collective with no other operation running on that device, averaged
over the chips (trace)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["collective_s"] or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["busy_s"]
