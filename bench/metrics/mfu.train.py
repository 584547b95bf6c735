"""The training step's share of the chips' peak: the operations of the
window's steps (forward and backward, recomputation not counted; from
bench/work.py) over the traced window, the chips and the bfloat16 peak
rate (trace)."""


def read(ctx):
    tr, w = ctx.get("trace"), ctx.get("work", {}).get("train")
    if not tr or not w or not tr["window_s"]:
        return None
    return 100.0 * w["flops"] / (tr["window_s"] * w["chips"]
                                 * ctx["peak_flops"])
