"""Device: share of the window in which no XLA op ran (%), update cells."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_ns() / ctx.trace.window_ns)
