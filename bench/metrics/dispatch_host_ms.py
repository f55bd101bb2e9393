"""Host time per chunk of the program's dispatch: the mean of its
``dwfl.chunk.dispatch`` spans, each ``ChunkRunner.run`` on a compiled
length from the call to its asynchronous return (it never waits on the
device). The set-up's first chunk is a ``dwfl.chunk.compile`` span, so
every dispatch span is one of the window's."""


def read(summary, ctx):
    from bench import program_spans
    got = program_spans.counter("dwfl.chunk.dispatch")
    if got is None:
        return None
    return 1e3 * got["total_s"] / got["count"]
