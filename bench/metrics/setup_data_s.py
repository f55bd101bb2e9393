"""Seconds of set-up spent building the data inside ``train.main`` (its
``dwfl.main.data`` span): the data set, the Dirichlet split, the batcher
and the device store."""


def read(summary, ctx):
    from bench import program_spans
    return program_spans.total_s("dwfl.main.data")
