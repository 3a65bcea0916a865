"""`hist_mxu_roofline` and `hist_mxu_padding` (benchmarks/reducers/
hist_mxu.py) on a hand-made trace: the kernel events carry what their
call asks of the MXU in the part `Hist.mxu_n<n>_f<f>_e<e>` of their
`op_name` (lightgbm_tpu/ops/histogram.py `mxu_call_scope`), and the
reader sums useful and asked FLOP over them."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run, scope_trace, trace as bench_trace   # noqa: E402
from benchmarks.kernel_costs import hist_mxu_flop   # noqa: E402
from benchmarks.reducers import hist_mxu, program_total   # noqa: E402
from test_bench_scope_readers import SHIFT_NS, write_xplane   # noqa: E402

ROWS, CODES, PEAK = 1_000_000, 1_000, 197e12
KERNELS = ["^%build_histogram"]
PATH = "jit(grow)/cond/branch_1_fun/Tree.histogram/"
# two calls on one device of a 10 ms window: a 16-slot wave through one
# `pallas_call` (2 ms), and a 2-slot wave padded to 8 whose classed call
# issues two (0.5 + 1.5 ms: a call's work is shared among its events);
# an XLA op under the same part and a kernel with no part are not read
CALLS = [
    ["%build_histogram_wave.3 = (f32[7168,128])", 1_000_000, 2_000_000,
     PATH + "Hist.mxu_n32_f300000_e1/jit(build_histogram_wave)/"
     "build_histogram_wave/pallas_call"],
    ["%build_histogram_wave.4 = (f32[600,128])", 4_000_000, 500_000,
     PATH + "Hist.mxu_n4_f100000_e2/jit(build_histogram_wave)/"
     "build_histogram_wave/pallas_call"],
    ["%build_histogram_wave.5 = (f32[600,128])", 5_000_000, 1_500_000,
     PATH + "Hist.mxu_n4_f100000_e2/jit(build_histogram_wave)/"
     "build_histogram_wave/pallas_call"],
    ["%fusion.9 = f32[16]", 7_000_000, 250_000,
     PATH + "Hist.mxu_n4_f100000_e2/take"],
]
UNLABELLED = ["%build_histogram_rows.1 = f32[8]", 8_000_000, 100_000,
              PATH + "jit(build_histogram_rows_pallas)/pallas_call"]


def _ctx(tmp_path, monkeypatch, ops, codes=CODES, devices=1):
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_total, "totals", lambda kind: (
        {"hist_codes": codes} if codes else {}))
    scope_trace._cache.clear()
    doc = {"window_ns": [0, 10_000_000],
           "devices": {f"/device:TPU:{d}": ops for d in range(devices)},
           "host_spans": [["bench::update", 0, 10_000_000, {}]]}
    trace_dir = tmp_path / "trace" / "cell"
    write_xplane(doc, str(trace_dir / "plugins" / "profile" / "t0"
                          / "host.xplane.pb"))
    tr = bench_trace.from_xplane(str(trace_dir))
    tr.window = tuple(w + SHIFT_NS for w in doc["window_ns"])
    return SimpleNamespace(
        trace=tr, spans={}, peaks={"bf16_flop_per_s": PEAK},
        counters={"iterations": 2, "rows_local": ROWS})


def _metrics(ctx):
    entries = [{"name": "hist_mxu_roofline", "unit": "%"},
               {"name": "hist_mxu_padding", "unit": "count"}]
    return {k: v["value"]
            for k, v in run.layer_metrics(entries, {}, ctx).items()}


def test_useful_work_is_counted_from_the_table():
    assert hist_mxu_flop.cost(ROWS, CODES, 32) == 2 * ROWS * CODES * 32
    assert hist_mxu_flop.cost(2_625_536, 7_140, 128) == 4_799_059_722_240
    for nothing in ((0, CODES, 4), (ROWS, 0, 4), (ROWS, None, 4),
                    (ROWS, CODES, 0)):
        assert hist_mxu_flop.cost(*nothing) is None


def test_two_calls_one_of_two_events(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, CALLS + [UNLABELLED])
    events = hist_mxu.labelled_events(ctx, KERNELS)
    assert sorted(events) == [(4, 100000, 2, 500_000),
                              (4, 100000, 2, 1_500_000),
                              (32, 300000, 1, 2_000_000)]
    useful = 2 * ROWS * CODES * (32 + 4)      # the two halves make one call
    asked = ROWS * (300_000 + 100_000)
    got = _metrics(ctx)
    assert got["hist_mxu_padding"] == pytest.approx(asked / useful)
    assert got["hist_mxu_roofline"] == pytest.approx(
        100.0 * useful / PEAK / 4e-3)
    # their product is the MXU's share on the work as written
    assert (got["hist_mxu_roofline"] * got["hist_mxu_padding"]
            == pytest.approx(100.0 * asked / PEAK / 4e-3))


def test_four_devices_read_what_one_reads(tmp_path, monkeypatch):
    """`dp4`: each chip runs the same calls on its own rows, so FLOP and
    time both sum over the devices and the two ratios are one chip's."""
    one = _metrics(_ctx(tmp_path, monkeypatch, CALLS))
    four = _metrics(_ctx(tmp_path, monkeypatch, CALLS, devices=4))
    assert four == pytest.approx(one) and len(four) == 2


def test_a_parents_trace_reads_nothing(tmp_path, monkeypatch):
    """A program from before the labels, or before `hist_codes`: both
    metrics are left out and nothing raises."""
    bare = [[name, s, d, op_name.replace("Hist.mxu_n32_f300000_e1/", "")
             .replace("Hist.mxu_n4_f100000_e2/", "")]
            for name, s, d, op_name in CALLS]
    ctx = _ctx(tmp_path, monkeypatch, bare)
    assert hist_mxu.labelled_events(ctx, KERNELS) == []
    assert _metrics(ctx) == {}
    assert _metrics(_ctx(tmp_path, monkeypatch, CALLS, codes=None)) == {}
    for trace in (None, bench_trace.from_json(os.path.join(
            ROOT, "benchmarks", "testdata", "trace_small.json"))):
        ctx.trace = trace
        assert _metrics(ctx) == {}


def test_manifest_names_both_metrics_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("hist_mxu_roofline")
    last_two = manifest["per_layer"][at:at + 2]     # PR 39 appended both
    assert [m["name"] for m in last_two] == ["hist_mxu_roofline",
                                             "hist_mxu_padding"]
    for m, (unit, better) in zip(last_two, (("%", "higher"),
                                            ("count", "lower"))):
        assert (m["unit"], m["better"]) == (unit, better)
        assert m["layer"] == "ops kernels" and m["moves"] == "iter_ms"
        assert m["source"] == "device_trace" and "workloads" not in m
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reducer"] == "hist_mxu"
        assert spec["args"]["patterns"] == KERNELS
