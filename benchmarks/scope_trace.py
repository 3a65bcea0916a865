#!/usr/bin/env python3
"""The device's time under the program's own names.

The profiler's `XLA Ops` events are named by their HLO text
(`%fusion.1886 = s32[2625536,10]{...} fusion(...)`), which changes with
every compile.  What the program called the work — the `jax.named_scope`
labels of `lightgbm_tpu.utils.timer.device_scope`, `Tree.partition`,
`GBDT.score_update`, ... — is in each op's `op_name`, and the trace keeps
that as the stat `tf_op` of the event's METADATA (`scope_layout.json`), which
`jax.profiler.ProfileData` does not hand out (it gives an event's own
stats: on the chip `device_offset_ps`, `device_duration_ps`; my chip run,
PR 28).  So this module reads the `.xplane.pb` itself — the protobuf wire
format, the few fields of `xplane.proto` it needs — or a recorded trace
kept as JSON (`testdata/scope_trace_small.json`), and gives each op's OWN
time (its duration less the ops nested in it, as `Trace.top_ops` does for
`cond` / `while`) to the innermost scope label of its `op_name`.

`run.py` hands a reducer `ctx.trace` and not the file it was made from,
so `for_trace(ctx.trace)` takes the newest `.xplane.pb` under
`.bench_out/trace/*/` and uses it only if it holds exactly the ops of
`ctx.trace` (same planes, same counts, same first and last op); anything
else — no file, another run's file, the JSON testdata of `selftest.py` —
gives nothing, and the reducers then leave their metrics out.

    python3 benchmarks/scope_trace.py [file.xplane.pb | dir | trace.json]

prints the by-scope table of a trace (what `PERF.md` section 5 quotes).
"""

import glob
import json
import os
import re
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")       # run.py's, fixed
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace as bench_trace      # noqa: E402

UNSCOPED = ""


def _layout():
    """`scope_layout.json`: the stat that holds an op's op_name, and the
    form of a scope label in it."""
    with open(os.path.join(HERE, "scope_layout.json")) as f:
        doc = json.load(f)
    return {"op_name_stat": doc["op_name_stat"],
            "label": re.compile(doc["label"])}


LAYOUT = _layout()


# ------------------------------------------------------------ wire format
def _fields(buf, start, end):
    """(field number, wire type, value) of the message in buf[start:end];
    a length-delimited value is its (start, end)."""
    i = start
    while i < end:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                v, i = (i, i + v), i + v
        elif wire == 1:
            v, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 5:
            v, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, v


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """(name, value) of one XStat; a `ref_value` is the name of the stat
    metadata it points at."""
    name = value = None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num in (3, 4):
            value = v - (1 << 64) if num == 4 and v >= 1 << 63 else v
        elif num in (5, 6):
            value = _text(buf, v)
        elif num == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf, span, want_line):
    """One XPlane: (name, [(line name, timestamp_ns, [(metadata id,
    offset_ps, duration_ps, {stat: value})])], {metadata id: (name,
    {stat: value})}) for the lines `want_line(plane, line)` admits."""
    name, lines, metas, stat_names = "", [], [], {}
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            metas.append(v)
        elif num == 5:
            for n2, _, v2 in _fields(buf, *v):      # map entry: value
                if n2 == 2:
                    sid, sname = 0, ""
                    for n3, _, v3 in _fields(buf, *v2):
                        if n3 == 1:
                            sid = v3
                        elif n3 == 2:
                            sname = _text(buf, v3)
                    stat_names[sid] = sname
    out_lines, used = [], set()
    for span_l in lines:
        lname, ts, events = "", 0, []
        for num, _, v in _fields(buf, *span_l):
            if num == 2:
                lname = _text(buf, v)
            elif num == 3:
                ts = v
            elif num == 4:
                events.append(v)
        if not want_line(name, lname):
            continue
        evs = []
        for span_e in events:
            mid = off = dur = 0
            stats = {}
            for num, _, v in _fields(buf, *span_e):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
                elif num == 4:
                    k, val = _stat(buf, v, stat_names)
                    stats[k] = val
            used.add(mid)
            evs.append((mid, off, dur, stats))
        out_lines.append((lname, ts, evs))
    meta = {}
    for span_m in metas:
        for n2, _, v2 in _fields(buf, *span_m):     # map entry: value
            if n2 != 2:
                continue
            mid, mname, mstats = 0, "", []
            for n3, _, v3 in _fields(buf, *v2):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    mname = _text(buf, v3)
                elif n3 == 5:
                    mstats.append(v3)
            if mid in used:
                meta[mid] = (mname, dict(_stat(buf, s, stat_names)
                                         for s in mstats))
    return name, out_lines, meta


def read_xspace(path, want_line=lambda plane, line: True):
    """[(plane name, lines, event metadata)] of an `.xplane.pb`."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(buf, v, want_line)
            for num, _, v in _fields(buf, 0, len(buf)) if num == 1]


# ------------------------------------------------------------- the trace
class ScopeTrace:
    """Per device the ops of its op line as `(name, start_ns, dur_ns,
    op_name)`, and the host's named spans as `(name, start_ns, dur_ns,
    {attribute: value})`."""

    def __init__(self, devices, host_spans, window=None):
        self.devices = devices
        self.host_spans = host_spans
        self.window = window    # a recorded trace states its own
        self._tables = {}       # by_scope_s, per (window, skip)

    def is_the_file_of(self, trace):
        """`trace` (a `benchmarks.trace.Trace`) was made from the same
        file: same devices, and on each the same number of ops with the
        same first and last."""
        if set(self.devices) != set(trace.devices) or not self.devices:
            return False
        for d, ops in self.devices.items():
            theirs = trace.devices[d]
            if len(ops) != len(theirs) or not ops:
                return False
            for mine, other in ((ops[0], theirs[0]), (ops[-1], theirs[-1])):
                if tuple(mine[:3]) != tuple(other[:3]):
                    return False
        return True

    def own_ns(self, device, window):
        """[(name, op_name, own ns)]: each op's time in `window` less
        that of the ops nested directly in it."""
        w0, w1 = window
        clipped = []
        for name, s, d, op_name in self.devices[device]:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                clipped.append((s2, e2, name, op_name))
        out, stack = [], []      # stack: [end, name, op_name, own]
        for s, e, name, op_name in sorted(clipped,
                                          key=lambda o: (o[0], -o[1])):
            while stack and stack[-1][0] <= s:
                out.append(tuple(stack.pop()[1:]))
            if stack:
                stack[-1][3] -= e - s
            stack.append([e, name, op_name, e - s])
        out.extend(tuple(o[1:]) for o in stack)
        return out

    def by_scope_s(self, window, skip=()):
        """{scope label: seconds}, mean over the devices; ops whose name
        matches any pattern of `skip` (the kernels and all-reduces that
        other readers count by name) are left out, ops with no label go
        under `UNSCOPED`."""
        key = (tuple(window), tuple(skip))
        if key in self._tables:
            return self._tables[key]
        rx = [re.compile(p) for p in skip]
        tot = {}
        for device in self.devices:
            for name, op_name, own in self.own_ns(device, window):
                if any(r.search(name) for r in rx):
                    continue
                label = scope_of(op_name)
                tot[label] = tot.get(label, 0) + own
        n = max(len(self.devices), 1)
        self._tables[key] = {k: v / n / 1e9 for k, v in tot.items()}
        return self._tables[key]


def scope_of(op_name):
    """The innermost scope label of an op_name path:
    `jit(f)/Tree.histogram/jit(g)/Tree.hist_operands/reshape` is
    `Tree.hist_operands`.  An op_name with no label (the compiler's own
    copies carry `jit(f)/cond` or nothing) is `UNSCOPED`: no op is
    assigned by its trace name."""
    found = LAYOUT["label"].findall(op_name or "")
    return found[-1] if found else UNSCOPED


def from_xplane(path):
    lay = bench_trace._layout()

    def want(plane, line):
        return bool((lay["device_plane"].search(plane)
                     and lay["op_line"].search(line))
                    or lay["host_plane"].search(plane))
    devices, host_spans = {}, []
    for plane, lines, meta in read_xspace(path, want):
        on_device = bool(lay["device_plane"].search(plane))
        for _, ts, events in lines:
            for mid, off, dur, stats in events:
                name, mstats = meta.get(mid, ("", {}))
                start, dur_ns = ts + off // 1000, dur // 1000
                if on_device:
                    devices.setdefault(plane, []).append(
                        (name, start, dur_ns,
                         mstats.get(LAYOUT["op_name_stat"]) or ""))
                elif lay["host_span"].search(name):
                    host_spans.append((name, start, dur_ns, stats))
    return ScopeTrace(devices, host_spans)


def from_json(path):
    with open(path) as f:
        doc = json.load(f)
    return ScopeTrace({d: [tuple(op) for op in ops]
                       for d, ops in doc["devices"].items()},
                      [tuple(s) for s in doc.get("host_spans", [])],
                      tuple(doc["window_ns"]))


def newest_xplane(under=None):
    paths = glob.glob(os.path.join(
        under or os.path.join(OUT_DIR, "trace", "*"),
        "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


_cache = {}     # one traced run reads its file once for all reducers


def for_trace(trace):
    """The scope trace of the file `trace` was made from, or None."""
    if trace is None or not getattr(trace, "devices", None):
        return None
    path = newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        try:
            _cache[key] = from_xplane(path)
        except (OSError, ValueError, IndexError, struct.error):
            _cache[key] = None
    st = _cache[key]
    return st if st is not None and st.is_the_file_of(trace) else None


# -------------------------------------------------------------------- CLI
def main(argv):
    target = argv[0] if argv else None
    if target and target.endswith(".json"):
        st = from_json(target)
    else:
        path = (target if target and target.endswith(".pb")
                else newest_xplane(target))
        if path is None:
            sys.exit("scope_trace: no .xplane.pb found")
        st = from_xplane(path)
    marks = [(s, s + d) for name, s, d, _ in st.host_spans
             if name.startswith(bench_trace.WINDOW_SPAN)]
    if not marks:
        marks = [(s, s + d) for ops in st.devices.values()
                 for _, s, d, _ in ops]
    window = st.window or (min(s for s, _ in marks),
                           max(e for _, e in marks))
    iters = max(sum(name == "bench::update" and window[0] <= s < window[1]
                    for name, s, *_ in st.host_spans), 1)
    table = st.by_scope_s(window)
    print(json.dumps({"window_s": (window[1] - window[0]) / 1e9,
                      "iterations": iters,
                      "devices": sorted(st.devices)}))
    for label, s in sorted(table.items(), key=lambda kv: -kv[1]):
        print(f"{1000 * s / iters:10.3f} ms/iter  {label or '(no scope)'}")
    loose = {}
    for device in st.devices:
        for name, op_name, own in st.own_ns(device, window):
            if scope_of(op_name) == UNSCOPED:
                key = (re.sub(r"\.\d+", "", name[:60]), op_name[-60:])
                loose[key] = loose.get(key, 0) + own
    print("largest ops with no scope (ms/iter, first device line's name, "
          "op_name tail):")
    n = max(len(st.devices), 1)
    for (name, op_name), ns in sorted(loose.items(),
                                      key=lambda kv: -kv[1])[:12]:
        print(f"{ns / n / 1e6 / iters:10.3f}  {name}  [{op_name}]")


if __name__ == "__main__":
    main(sys.argv[1:])
