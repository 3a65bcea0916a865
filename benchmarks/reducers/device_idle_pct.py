"""Share of the traced window in which no op ran on the device, in %
(mean over the devices)."""


def reduce(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_s() / ctx.trace.window_s)
