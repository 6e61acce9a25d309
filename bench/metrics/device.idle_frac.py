"""device.idle_frac: share of the traced window in which no op ran on
the device (1 − busy union / window), averaged over the chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    from bench.trace import busy_s
    return 100.0 * (1.0 - busy_s(ctx.trace) / ctx.trace.window_s)
