"""Device time per round of the model-sharded round's worker-split
transfer: the leaf ops under the program's ``dwfl.shard`` scope (the
``all_to_all`` that trades each chip's column slab for its worker's full
row, the one that scatters the gradient's columns back, and the pad of the
worker axis; ``shard/round.py``), averaged over the chips. Without such
ops (an unsharded round, or a program older than the scope) the reader
returns nothing."""

SCOPE = "dwfl.shard"


def read(summary, ctx):
    from bench import trace
    spent = trace.scope_seconds(summary, SCOPE)
    if spent <= 0 or ctx["rounds"] <= 0:
        return None
    return 1e3 * spent / ctx["rounds"]
