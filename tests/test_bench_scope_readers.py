"""The benchmark's scope readers (benchmarks/scope_trace.py and the
reducers that read it, the program's host spans and its own totals)
against `benchmarks/testdata/scope_trace_small.json`, cut from a chip
trace, and its hand counts.

The recorded trace is also written out as an `.xplane.pb` (a small
encoder of the protobuf wire format, below) so that the path a traced
run takes is the one checked: `jax.profiler.ProfileData` reads the file
for `ctx.trace`, `scope_trace` reads it for the op_names, and
`for_trace` has to recognise the two as one file."""

import json
import os
import struct
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run, scope_trace, trace as bench_trace   # noqa: E402
from benchmarks.reducers import (counter_ratio, host_span_ms_per_iter,   # noqa: E402
                                 program_total, scope_ms_per_iter,
                                 unscoped_ms_per_iter)

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
TRACE_METRICS = ("partition_ms", "split_find_ms", "hist_cache_ms",
                 "hist_operands_ms", "prune_ms", "score_update_ms",
                 "gradients_ms", "grow_unscoped_ms", "host_loop_ms",
                 "host_wait_ms")
PROGRAM_METRICS = ("waves_per_tree", "trace_lower_s", "compile_or_load_s",
                   "find_bin_s", "binning_s")
NEW_METRICS = TRACE_METRICS + PROGRAM_METRICS
SHIFT_NS = 160_000_000     # the recorded host spans start before 0


def _load(name):
    with open(os.path.join(TESTDATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def doc():
    return _load("scope_trace_small.json")


@pytest.fixture(scope="module")
def expected():
    return _load("scope_trace_small.expected.json")


# ----------------------------------------- xplane.proto, the wire format
def _varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _int(num, v):
    return _varint(num << 3) + _varint(v)


def _bytes(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, lines, stat_names=("tf_op", "iter", "tree")):
    """XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5};
    `lines`: [(line name, [(event name, start_ns, dur_ns, tf_op,
    {attr: int})])].  One event metadata per distinct (name, tf_op)."""
    stat_id = {s: i + 1 for i, s in enumerate(stat_names)}
    meta_id, out = {}, _bytes(2, name)
    for lname, events in lines:
        body = _bytes(2, lname) + _int(3, 0)          # timestamp_ns = 0
        for ename, start, dur, tf_op, attrs in events:
            mid = meta_id.setdefault((ename, tf_op), len(meta_id) + 1)
            ev = (_int(1, mid) + _int(2, start * 1000)
                  + _int(3, dur * 1000))
            for k, v in attrs.items():                # XStat int64_value
                ev += _bytes(4, _int(1, stat_id[k]) + _int(4, v))
            body += _bytes(4, ev)
        out += _bytes(3, body)
    for (ename, tf_op), mid in meta_id.items():
        md = _int(1, mid) + _bytes(2, ename)
        if tf_op:                                     # XStat str_value
            md += _bytes(5, _int(1, stat_id["tf_op"]) + _bytes(5, tf_op))
        out += _bytes(4, _int(1, mid) + _bytes(2, md))
    for sname, sid in stat_id.items():
        out += _bytes(5, _int(1, sid)
                      + _bytes(2, _int(1, sid) + _bytes(2, sname)))
    return out


def write_xplane(doc, path):
    planes = [_plane(dev, [("XLA Ops", [
        (n, s + SHIFT_NS, d, o, {}) for n, s, d, o in ops])])
        for dev, ops in doc["devices"].items()]
    planes.append(_plane("/host:CPU", [("python3", [
        (n, s + SHIFT_NS, d, "", a) for n, s, d, a in doc["host_spans"]])]))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(b"".join(_bytes(1, p) for p in planes))


@pytest.fixture
def traced_ctx(doc, expected, tmp_path, monkeypatch):
    """What `run.execute` hands the reducers after a traced run whose
    profiler file holds the recorded trace."""
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    trace_dir = tmp_path / "trace" / "cell"
    write_xplane(doc, str(trace_dir / "plugins" / "profile" / "t0"
                          / "host.xplane.pb"))
    tr = bench_trace.from_xplane(str(trace_dir))
    tr.window = tuple(w + SHIFT_NS for w in doc["window_ns"])
    return SimpleNamespace(trace=tr, spans={}, peaks=None,
                           counters=dict(expected["counters"]))


def _entries(names):
    return [{"name": n, "unit": "-"} for n in names]


# ------------------------------------------------------------------ tests
def test_scope_of_takes_the_innermost_label():
    assert scope_trace.scope_of(
        "jit(f)/cond/Tree.histogram/jit(g)/Tree.hist_operands/reshape:"
    ) == "Tree.hist_operands"
    assert scope_trace.scope_of("jit(_grad1)/GBDT.gradients/mul:") \
        == "GBDT.gradients"
    # the compiler's own copies: a path with no label, or none at all
    assert scope_trace.scope_of("jit(f)/cond:") == scope_trace.UNSCOPED
    assert scope_trace.scope_of("") == scope_trace.UNSCOPED


def test_recorded_trace_gives_the_hand_counted_table(doc, expected):
    st = scope_trace.from_json(
        os.path.join(TESTDATA, "scope_trace_small.json"))
    table = st.by_scope_s(tuple(doc["window_ns"]), skip=expected["skip"])
    assert set(table) == set(expected["by_scope_s"])
    for scope, want in expected["by_scope_s"].items():
        assert table[scope] == pytest.approx(want, rel=1e-9), scope


def test_own_time_takes_nested_ops_off_a_cond(doc):
    st = scope_trace.from_json(
        os.path.join(TESTDATA, "scope_trace_small.json"))
    own = {name: ns for name, _, ns in st.own_ns(
        "/device:TPU:1", tuple(doc["window_ns"]))}
    cond = next(k for k in own if k.startswith("%cond.9"))
    assert own[cond] == 2_500_000         # 20 ms less 2 + 10 + 1.5 + 4


def test_wire_reader_reads_what_profile_data_reads(traced_ctx):
    st = scope_trace.from_xplane(scope_trace.newest_xplane())
    tr = traced_ctx.trace
    assert set(st.devices) == set(tr.devices) and len(st.devices) == 2
    for dev, ops in st.devices.items():
        assert [op[:3] for op in ops] == tr.devices[dev]
    assert (sorted(s[:3] for s in st.host_spans)
            == sorted(tr.host_spans))
    attrs = {(name, tuple(a.items())) for name, _, _, a in st.host_spans
             if a}
    assert ("GBDT::iteration", (("iter", 7),)) in attrs
    assert ("GBDT::wait_tree", (("tree", 4),)) in attrs
    assert scope_trace.for_trace(tr) is not None


@pytest.mark.parametrize("metric", [
    "partition_ms", "split_find_ms", "hist_cache_ms", "hist_operands_ms",
    "prune_ms", "score_update_ms", "gradients_ms", "grow_unscoped_ms",
    "host_loop_ms", "host_wait_ms", "grow_other_ms"])
def test_trace_readers_give_the_hand_counts(traced_ctx, expected, metric):
    got = run.layer_metrics(_entries([metric]), {}, traced_ctx)
    assert got[metric]["value"] == pytest.approx(
        expected["metrics"][metric], rel=1e-9)


def test_scope_metrics_add_up_to_grow_other_ms(traced_ctx):
    names = ["partition_ms", "split_find_ms", "hist_cache_ms",
             "hist_operands_ms", "prune_ms", "score_update_ms",
             "gradients_ms", "grow_unscoped_ms", "grow_other_ms"]
    got = {k: v["value"] for k, v in run.layer_metrics(
        _entries(names), {}, traced_ctx).items()}
    assert sum(got[n] for n in names[:-1]) == pytest.approx(
        got["grow_other_ms"], rel=1e-12)


def test_another_runs_file_is_not_read(traced_ctx, doc):
    """The newest file under .bench_out is used only if it holds exactly
    the ops of ctx.trace: the JSON testdata of selftest.py, or a trace
    whose file is gone, reads nothing."""
    other = bench_trace.from_json(
        os.path.join(TESTDATA, "trace_small.json"))
    assert scope_trace.for_trace(other) is None
    shorter = bench_trace.Trace(
        {d: ops[:-1] for d, ops in traced_ctx.trace.devices.items()},
        traced_ctx.trace.host_spans, traced_ctx.trace.window)
    assert scope_trace.for_trace(shorter) is None
    ctx = SimpleNamespace(trace=other, spans={}, peaks=None,
                          counters={"iterations": 1})
    assert run.layer_metrics(_entries(TRACE_METRICS), {}, ctx) == {}


@pytest.mark.parametrize("trace", ["none", "no_devices", "no_file"])
def test_every_new_reducer_returns_nothing_on_empty_sources(
        trace, tmp_path, monkeypatch):
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_total, "totals", lambda kind: {})
    scope_trace._cache.clear()
    tr = {"none": None,
          "no_devices": bench_trace.Trace({}, [], (0, 1000)),
          "no_file": bench_trace.Trace(
              {"/device:TPU:0": [("%fusion.1 = f32[8]", 10, 100)]}, [],
              (0, 1000))}[trace]
    ctx = SimpleNamespace(trace=tr, spans={}, peaks=None,
                          counters={"iterations": 1})
    assert run.layer_metrics(_entries(NEW_METRICS), {}, ctx) == {}
    assert scope_ms_per_iter.reduce(ctx, "Tree.partition") is None
    if tr is not None:
        assert unscoped_ms_per_iter.reduce(ctx, [], []) is None
    assert host_span_ms_per_iter.reduce(ctx, "GBDT::iteration") is None
    assert program_total.reduce(ctx, "timer", ["Dataset::find_bin"]) is None
    assert program_total.reduce(
        ctx, "counter", ["first_iter_jit_trace_s"]) is None
    assert counter_ratio.reduce(ctx, "waves_total", "trees_grown") is None


def test_program_totals_are_read_in_process(monkeypatch):
    fake = {"timer": {"Dataset::find_bin": 5.5, "Dataset::binning": 1.25},
            "counter": {"first_iter_jit_trace_s": 4.0,
                        "first_iter_jit_lower_s": 3.5,
                        "jit_trace_s": 5.0, "jit_lower_s": 4.5,
                        "waves_total": 36, "trees_grown": 4,
                        "first_iter_backend_compile_s": 0.0,
                        "backend_compile_s": 2.0}}
    monkeypatch.setattr(program_total, "totals", lambda kind: fake[kind])
    ctx = SimpleNamespace(trace=None, spans={}, peaks=None, counters={})
    got = {k: v["value"] for k, v in run.layer_metrics(
        _entries(PROGRAM_METRICS), {}, ctx).items()}
    # compile_or_load_s: its counters are missing or 0 -> left out; the
    # process totals beside the first iteration's are not read
    assert got == {"waves_per_tree": 9.0, "trace_lower_s": 7.5,
                   "find_bin_s": 5.5, "binning_s": 1.25}


def test_program_totals_come_from_the_programs_own_recorders():
    from lightgbm_tpu.observability import global_registry
    from lightgbm_tpu.utils.timer import global_timer
    with global_timer.scope("Test::reader_probe"):
        pass
    global_registry.inc("test_reader_probe", 3)
    assert "Test::reader_probe" in program_total.totals("timer")
    assert program_total.totals("counter")["test_reader_probe"] >= 3


def test_manifest_names_every_new_metric_with_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert name in per_layer, name
        assert "workloads" not in per_layer[name]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json"))
    moves = {n: per_layer[n]["moves"] for n in NEW_METRICS}
    assert {moves[n] for n in NEW_METRICS[:11]} == {"iter_ms"}
    assert {moves[n] for n in NEW_METRICS[11:]} == {"setup_s"}


def test_selftest_passes_unedited():
    """`selftest.py` runs every file of layer_metrics/ on the PR 26 trace
    and demands exactly its hand-counted set: the new readers must find
    nothing to read there.  A process of its own: the program's totals
    in this one are not empty."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "selftest.py"),
         "--no-train"], capture_output=True, text=True, timeout=300,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-500:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["selftest"] == "ok"
    assert last["per_layer"] >= 20 and last["reducers_checked"] == 7


def test_struct_roundtrip_of_a_double_stat():
    """XStat.double_value is a fixed64: the reader gives a float."""
    payload = (_int(1, 1) + _varint(2 << 3 | 1)
               + struct.pack("<d", 0.25))
    name, value = scope_trace._stat(payload, (0, len(payload)), {1: "x"})
    assert (name, value) == ("x", 0.25)
