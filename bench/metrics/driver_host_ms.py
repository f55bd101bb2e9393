"""Host time per chunk outside the chunk program: the telemetry readback
and the epsilon composition the harness runs after each chunk, read from
its own ``bench.readback`` spans in the trace."""


def read(summary, ctx):
    if ctx["chunks"] <= 0:
        return None
    spent = sum(b - a for a, b, n in summary.spans if n == "bench.readback")
    if spent <= 0:
        return None
    return spent * 1e-6 / ctx["chunks"]
