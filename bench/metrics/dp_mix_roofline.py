"""Roofline share of the dense dp_mix kernel: the least time one round's mix
needs on the chip (the larger of its needed FLOPs over peak FLOP/s and its
needed bytes over peak bytes/s, counted on the unpadded [N, d] shapes),
times the calls in the traced window (one a round), over the device time
of the kernel's events. The kernel is the chunk program's one Mosaic
custom call; its HLO instruction, and so its trace event, is named after
the jitted wrapper ``dp_mix_round`` (a compile for a described v5e names
it ``dp_mix_round.13``). Without such events (a sparse round) the reader
returns nothing."""

KERNEL_MATCH = "dp_mix_round"


def read(summary, ctx):
    from bench import trace
    spent = sum(trace.op_totals(summary, KERNEL_MATCH).values())
    if spent <= 0 or ctx["rounds"] <= 0:
        return None
    flops, nbytes = ctx["flops"].dp_mix_work(ctx["cfg"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * ctx["rounds"] / spent
