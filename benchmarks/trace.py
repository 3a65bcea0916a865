"""Profiler trace -> per-device interval lists, shared by the reducers.

A `Trace` holds, for each device, the operations of its op line as
`(name, start_ns, duration_ns)`, the host's named spans on the same
clock, and the traced window.  It is made either from the `.xplane.pb`
that `jax.profiler` wrote (`from_xplane`) or from a recorded trace kept
as JSON (`from_json`, the form of `testdata/`), so the reduction that
runs on the chip is the one `selftest.py` checks by hand-countable
numbers.  Which plane and line hold the device's ops is data
(`trace_layout.json`), read off the first chip trace.
"""

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "bench::"   # the drivers' own spans bracket the window


def _layout():
    with open(os.path.join(HERE, "trace_layout.json")) as f:
        return {k: re.compile(v) for k, v in json.load(f).items()}


def union_ns(intervals):
    """Total length of the union of `(start, end)` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, window):
    """The `(start, end)` stretches of `window` that no interval covers."""
    out, at = [], window[0]
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, window[1])))
        at = max(at, e)
        if at >= window[1]:
            break
    if at < window[1]:
        out.append((at, window[1]))
    return [(s, e) for s, e in out if e > s]


class Trace:
    def __init__(self, devices, host_spans, window):
        self.devices = devices          # {device name: [(name, start, dur)]}
        self.host_spans = host_spans    # [(name, start, dur)]
        self.window = tuple(window)     # (start_ns, end_ns)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, ops):
        w0, w1 = self.window
        for name, s, d in ops:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                yield name, s2, e2

    def busy_s(self, device):
        """Seconds of the window in which any op ran on `device`."""
        return union_ns([(s, e) for _, s, e in
                         self._clipped(self.devices[device])]) / 1e9

    def mean_busy_s(self):
        return (sum(self.busy_s(d) for d in self.devices)
                / max(len(self.devices), 1))

    def matching_s(self, patterns):
        """Seconds of ops whose name matches any of `patterns`, and their
        count: means over the devices.  (0.0, 0.0) when nothing matches."""
        rx = [re.compile(p) for p in patterns]
        secs = calls = 0
        for ops in self.devices.values():
            for name, s, e in self._clipped(ops):
                if any(r.search(name) for r in rx):
                    secs += (e - s) / 1e9
                    calls += 1
        n = max(len(self.devices), 1)
        return secs / n, calls / n

    def top_ops(self, k=10):
        """[[name, seconds]]: the ops that took most time themselves,
        mean over the devices.  A control-flow op (`cond`, `while`) spans
        the ops of its body on the same line, so an op's own time is its
        duration less that of the ops nested directly in it."""
        tot = {}
        for ops in self.devices.values():
            stack = []   # [end, name, own time] of the ops still open
            for name, s, e in sorted(self._clipped(ops),
                                     key=lambda o: (o[1], -o[2])):
                while stack and stack[-1][0] <= s:
                    _, done, own = stack.pop()
                    tot[done] = tot.get(done, 0) + own
                if stack:
                    stack[-1][2] -= e - s
                stack.append([e, name, e - s])
            for _, done, own in stack:
                tot[done] = tot.get(done, 0) + own
        n = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n / 1e9] for name, ns in top]

    def idle_by_host_span(self, k=10):
        """[[host span, seconds]]: the first device's idle time in the
        window, each gap under the innermost host span that covers its
        middle ("unnamed" where none does), largest first."""
        if not self.devices:
            return []
        first = sorted(self.devices)[0]
        ops = [(s, e) for _, s, e in self._clipped(self.devices[first])]
        tot = {}
        for s, e in gaps_ns(ops, self.window):
            mid = (s + e) // 2
            cover = [(d, name) for name, hs, d in self.host_spans
                     if hs <= mid < hs + d]
            name = min(cover)[1] if cover else "unnamed"
            tot[name] = tot.get(name, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]


def from_json(path):
    with open(path) as f:
        doc = json.load(f)
    return Trace({d: [tuple(op) for op in ops]
                  for d, ops in doc["devices"].items()},
                 [tuple(s) for s in doc.get("host_spans", [])],
                 doc["window_ns"])


def from_xplane(trace_dir):
    """Reduce the newest `.xplane.pb` under `trace_dir`.  The window runs
    from the start of the first host span whose name starts with
    `window_span` to the end of the last one; without such spans, from
    the first device op to the last."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    lay = _layout()
    devices, host_spans = {}, []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if lay["device_plane"].search(plane.name):
            for line in plane.lines:
                if lay["op_line"].search(line.name):
                    devices.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif lay["host_plane"].search(plane.name):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if lay["host_span"].search(e.name))
    marks = [(s, s + d) for name, s, d in host_spans
             if name.startswith(WINDOW_SPAN)]
    if not marks:
        marks = [(s, s + d) for ops in devices.values() for _, s, d in ops]
    if not marks:
        raise ValueError("the trace holds no device op and no window span")
    return Trace(devices, host_spans,
                 (min(s for s, _ in marks), max(e for _, e in marks)))
