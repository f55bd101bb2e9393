"""Seconds of set-up spent on the chunk program's first call (its
``dwfl.chunk.compile`` span): trace, compile or persistent-cache load, and
enqueue. A cell runs one chunk length, so this is one compile."""


def read(summary, ctx):
    from bench import program_spans
    return program_spans.total_s("dwfl.chunk.compile")
