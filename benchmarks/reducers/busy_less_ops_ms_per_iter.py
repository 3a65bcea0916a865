"""Device busy time per traced iteration less the ops whose name matches
`patterns`, in ms (mean over the devices): everything on the device that
is not those ops.  Ops are taken to run one at a time on a device's op
line, so their sum is their part of the busy union."""


def reduce(ctx, patterns):
    if ctx.trace is None:
        return None
    secs, _ = ctx.trace.matching_s(patterns)
    return (1000.0 * (ctx.trace.mean_busy_s() - secs)
            / ctx.counters["iterations"])
