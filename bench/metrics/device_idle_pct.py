"""Share of the traced window in which no operation ran on the device
(averaged over the chips)."""


def read(summary, ctx):
    from bench import trace
    if summary.window_s <= 0 or not summary.devices:
        return None
    return 100.0 * (1.0 - trace.busy_ns(summary) * 1e-9 / summary.window_s)
