"""Model FLOP utilisation of the whole round: the forward and backward work
every worker's batch needs, times the rounds completed in the traced
window, over the window times the chips times their bf16 peak."""


def read(summary, ctx):
    if ctx["rounds"] <= 0 or summary.window_s <= 0:
        return None
    work = ctx["flops"].round_flops(ctx["cfg"]) * ctx["rounds"]
    return 100.0 * work / (summary.window_s * ctx["chips"]
                           * ctx["peak"]["bf16_flops_per_s"])
