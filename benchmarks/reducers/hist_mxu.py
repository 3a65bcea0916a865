"""The histogram kernels' MXU work, read from the labels the program
puts on its own calls.

Every kernel call of a program since PR 39 runs under a `device_scope`
part `Hist.mxu_n<n>_f<f>_e<e>` (lightgbm_tpu/ops/histogram.py
`mxu_call_scope`; docs/Observability.md has the grammar), which reaches
each kernel event's `op_name`: `n` the useful output columns (channels x
the wave's TRUE computed slots), `f` the MXU FLOP a row the call's dots
ask as they are written and tile-padded, `e` the `pallas_call`s the call
issues.  This walks the window's kernel events (`ScopeTrace.own_ns`,
trace names matching `patterns`), parses the part from the event's
`op_name` and sums, over all devices, an event's share `1 / e` of its
call's

    useful FLOP  kernel_costs/<flop_fn>.py cost(rows_local, hist_codes, n)
    asked FLOP   f x rows_local

and the events' own time.  `share`:

    "useful_of_peak"    100 x useful / `bf16_flop_per_s` of peaks.json
                        over the events' time (bound: MXU)
    "asked_over_useful" asked / useful: how many times the useful work
                        the dots ask (>= 1)

Their product is the MXU's share on the work as written.  `hist_codes`
is the program's registry counter (the sum of the device columns' code
counts).  No trace file of this run, no kernel event with the part (a
program from before the labels), or no `hist_codes` returns nothing."""

import importlib
import re

from benchmarks import scope_trace
from benchmarks.reducers import program_total

PART = re.compile(r"(?:^|/)Hist\.mxu_n(\d+)_f(\d+)_e(\d+)(?=/|:|$)")


def labelled_events(ctx, patterns):
    """[(n, f, e, own ns)] of the window's kernel events that carry the
    part, all devices; None without this run's trace file."""
    st = scope_trace.for_trace(ctx.trace)
    if st is None:
        return None
    rx = [re.compile(p) for p in patterns]
    out = []
    for device in st.devices:
        for name, op_name, own in st.own_ns(device, ctx.trace.window):
            if any(r.search(name) for r in rx):
                m = PART.search(op_name)
                if m:
                    out.append((*map(int, m.groups()), own))
    return out


def reduce(ctx, patterns, flop_fn, share):
    events = labelled_events(ctx, patterns)
    codes = program_total.totals("counter").get("hist_codes")
    if not events or not codes:
        return None
    cost = importlib.import_module("benchmarks.kernel_costs." + flop_fn).cost
    rows = ctx.counters["rows_local"]
    useful = sum(cost(rows, codes, n) / e for n, _, e, _ in events)
    asked = sum(f * rows / e for _, f, e, _ in events)
    seconds = sum(own for *_, own in events) / 1e9
    if not useful or not seconds:
        return None
    if share == "asked_over_useful":
        return asked / useful
    return 100.0 * useful / ctx.peaks["bf16_flop_per_s"] / seconds
