"""Host time under the program's span `span` (a `global_timer.scope`,
written into the profiler's trace as a `TraceAnnotation`), less that under
the spans of `less`, per traced iteration, in ms.  Spans are clipped to
the traced window.  No such span in the trace returns nothing."""


def _span_s(trace, name):
    w0, w1 = trace.window
    total, found = 0, False
    for n, s, d in trace.host_spans:
        if n == name:
            found = True
            total += max(min(s + d, w1) - max(s, w0), 0)
    return (total / 1e9) if found else None


def reduce(ctx, span, less=()):
    if ctx.trace is None:
        return None
    secs = _span_s(ctx.trace, span)
    if secs is None:
        return None
    for name in less:
        secs -= _span_s(ctx.trace, name) or 0.0
    return 1000.0 * secs / ctx.counters["iterations"]
