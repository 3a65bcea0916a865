"""Device time of the ops whose name matches `patterns`, per traced
iteration, in ms (mean over the devices).  Nothing to read — no trace,
or no such op in it — returns nothing."""


def reduce(ctx, patterns):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.matching_s(patterns)
    if not calls:
        return None
    return 1000.0 * secs / ctx.counters["iterations"]
