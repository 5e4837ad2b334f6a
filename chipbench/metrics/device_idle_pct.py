"""device_idle_pct: share of the traced window in which no op ran on a
chip, averaged over chips, in %."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
