"""Device time per round of what the round computes only to report it:
the leaf ops under the program's ``dwfl.telemetry`` scope (the in-scan
telemetry rows of ``obs/telemetry``, the consensus and parameter-norm
reductions, the chunk's epilogue), averaged over the chips. Without such
ops the reader returns nothing."""

SCOPE = "dwfl.telemetry"


def read(summary, ctx):
    from bench import trace
    spent = trace.scope_seconds(summary, SCOPE)
    if spent <= 0 or ctx["rounds"] <= 0:
        return None
    return 1e3 * spent / ctx["rounds"]
