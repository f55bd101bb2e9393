"""Seconds of set-up spent building the workers' initial weights (the
``dwfl.main.params`` span of ``launch/train.main``): on a model mesh the
[W, d] buffer built straight into its column-sharded layout. A program
without that span reads nothing."""


def read(summary, ctx):
    from bench import program_spans
    return program_spans.total_s("dwfl.main.params")
