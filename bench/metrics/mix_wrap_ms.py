"""Device time per round of the mix around its kernel: the leaf ops under
the program's ``dwfl.mix`` scope (the mixing plan, whatever the wrapper
does to the buffer before and after the call, the flat step's clipped
gradient) less the ``dp_mix_round`` kernel itself, which
``dp_mix_roofline`` reads, averaged over the chips. Without such ops the
reader returns nothing."""

SCOPE = "dwfl.mix"


def read(summary, ctx):
    from bench import trace
    spent = trace.scope_seconds(summary, SCOPE, exclude=trace.KERNEL_MATCH)
    if spent <= 0 or ctx["rounds"] <= 0:
        return None
    return 1e3 * spent / ctx["rounds"]
