"""Device time per round of the gradient pass: the leaf ops under the
program's ``dwfl.grad`` scope (the vmapped ``value_and_grad`` and the
clip, ``core/protocol``), averaged over the chips. Without such ops (a
program older than its scopes) the reader returns nothing."""

SCOPE = "dwfl.grad"


def read(summary, ctx):
    from bench import trace
    spent = trace.scope_seconds(summary, SCOPE)
    if spent <= 0 or ctx["rounds"] <= 0:
        return None
    return 1e3 * spent / ctx["rounds"]
