"""Seconds of set-up spent in ``launch/train.main`` (its ``dwfl.main``
span): arguments, the data build, weights, channel and the trainer, with
no round run."""


def read(summary, ctx):
    from bench import program_spans
    return program_spans.total_s("dwfl.main")
