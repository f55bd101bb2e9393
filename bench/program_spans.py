"""The program's own span counters (``repro.obs.spans``), read in the
process that ran it: what the set-up and the chunk driver spent, by span
name. A program without them (one older than its spans) reads nothing."""


def counter(name):
    """{"count", "total_s", "max_s"} of the span ``name``, or None when
    the program has no span counters or never closed such a span."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    got = spans.counters().get(name)
    return got if got and got["count"] > 0 else None


def total_s(name):
    got = counter(name)
    return None if got is None else got["total_s"]
