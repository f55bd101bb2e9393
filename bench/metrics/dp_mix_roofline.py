"""Roofline share of the dense dp_mix kernel: the least time one round's mix
needs on the cell's chips together (the larger of its needed FLOPs over
peak FLOP/s and its needed bytes over peak bytes/s, counted on the
unpadded [N, d] shapes, over the chip count c), times the calls in the
traced window (one a round), over the device time of the kernel's events
averaged over the chips. That least time is reached only where each chip
mixes its own d/c column window (``--model-shards c``), which then reads
what one chip mixing all d columns at the same speed reads; a layout that
mixes the same columns on more than one chip reads less, never more. The
kernel is the chunk program's one Mosaic custom call; its HLO
instruction, and so its trace event, is named after the jitted wrapper
``dp_mix_round`` (a compile for a described v5e names it
``dp_mix_round.13``). Without such events (a sparse round) the reader
returns nothing."""


def read(summary, ctx):
    from bench import trace
    spent = sum(trace.op_totals(summary, trace.KERNEL_MATCH).values())
    if spent <= 0 or ctx["rounds"] <= 0:
        return None
    flops, nbytes = ctx["flops"].dp_mix_work(ctx["cfg"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * least * ctx["rounds"] / spent
