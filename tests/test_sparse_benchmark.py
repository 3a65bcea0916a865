"""The one-hot sparse cell of the benchmark (PR 36) on the CPU at small
sizes: the generator, the driver end to end through
`run.execute(..., on_chip=False)` with the configuration's own file at
20,000 rows, its probe of the program's sparse spans and its refusal of
a program without them, the plain reference on the program's tree 0 and
on two trees grown from a corrupted bundle decode, the two part readers
and the decode's roofline on a hand-built trace, the manifest's entries
and the plan's counters."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data as bench_data   # noqa: E402
from benchmarks import run, scope_trace, trace as bench_trace   # noqa: E402
from benchmarks.drivers import sparse_train_loop   # noqa: E402
from benchmarks.generators import expo_like   # noqa: E402
from benchmarks.kernel_costs import efb_decode_bytes   # noqa: E402
from benchmarks.reducers import (scope_part_hbm_roofline_pct,   # noqa: E402
                                 scope_part_ms_per_iter)
from benchmarks.references import sparse_first_tree as ref   # noqa: E402
from test_bench_scope_readers import SHIFT_NS, write_xplane   # noqa: E402
from test_rank_benchmark import _Probe   # noqa: E402  (a child that has said `out`)

CELL = "expo-onehot700-b63.train_sparse"
NEW_METRICS = {"efb_decode_ms", "efb_route_ms", "efb_decode_roofline",
               "efb_bundle_ratio", "sparse_values_per_row"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "expo-onehot700-b63.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ (a) generator
@pytest.fixture(scope="module")
def rows():
    return expo_like.make(30000, 700, 36)


def test_shape_and_eight_stored_values_a_row(rows):
    X, y = rows
    assert X.shape == (30000, 700) and X.dtype == np.float32
    assert X.format == "csr" and X.has_sorted_indices
    assert np.array_equal(np.diff(X.indptr), np.full(30000, 8))
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert 0.17 < y.mean() < 0.23


def test_group_widths_are_the_issues(rows):
    """One stored 1.0 in each of the six one-hot groups, two numerics
    that are never 0."""
    X, _ = rows
    assert expo_like.GROUPS == (12, 31, 7, 22, 313, 313)
    assert sum(expo_like.GROUPS) + expo_like.NUMERIC == 700
    idx = X.indices.reshape(-1, 8)
    val = X.data.reshape(-1, 8)
    starts = expo_like.STARTS
    for j in range(6):
        assert np.all((idx[:, j] >= starts[j]) & (idx[:, j] < starts[j + 1]))
        assert np.all(val[:, j] == 1.0)
    assert np.all(idx[:, 6] == 698) and np.all(idx[:, 7] == 699)
    assert val[:, 6].min() >= 1 and val[:, 6].max() <= 2359
    assert val[:, 7].min() >= 11


def test_keys_are_skewed_as_the_configuration_says(rows):
    X, _ = rows
    per_column = np.bincount(X.indices, minlength=700) / X.shape[0]
    origin = np.sort(per_column[72:385])[::-1]
    assert 0.05 < origin[0] < 0.075           # the largest airport
    assert origin[-1] < 1e-4                  # a long, thin tail
    carrier = per_column[50:72]
    assert carrier.max() / carrier.min() > 10


def test_same_seed_same_bytes_and_heldout_rows_differ():
    a, ya = expo_like.make(4000, 700, 36)
    b, yb = expo_like.make(4000, 700, 36)
    c, yc = expo_like.make(4000, 700, 37)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.data, b.data) and np.array_equal(ya, yb)
    assert not np.array_equal(a.indices, c.indices)
    assert not np.array_equal(ya, yc)


def test_seed_permutes_columns_and_nothing_else():
    config = {"features": 700}
    base, y0 = expo_like.make(3000, 700, 36)
    for seed in (1, 2 ** 31 + 11):
        order = bench_data.column_order(config, seed)
        X, y = expo_like.make(3000, 700, 36, order)
        assert np.array_equal(y, y0)
        assert X.has_sorted_indices
        assert np.array_equal(X.toarray(), base.toarray()[:, order])


def test_other_widths_are_refused():
    with pytest.raises(ValueError, match="700"):
        expo_like.make(10, 28, 1)


# ------------------------------------------- (b) the driver's whole flow
TINY_TRAFFIC = {"driver": "sparse_train_loop", "warmup_iters": 2,
                "quality_trees": 6, "test_rows": 4000, "traced_iters": 2}
CELL_CHECKS = {"no_recompile_in_window", "train_scores_finite",
               "heldout_scores_finite", "quality_at_or_over_floor",
               "bundled", "first_tree_routes_its_rows",
               "first_tree_sums_its_rows", "root_split_is_the_references"}


@pytest.mark.parametrize("trace", [False, True])
def test_sparse_train_loop_end_to_end(trace, tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    config = _config()
    config["rows"] = 20000
    config["params"] = {**config["params"], "verbosity": -1,
                        "num_leaves": 31}
    config["quality"]["floor"] = 0.55
    # the two counter metrics are totals of the process, which under the
    # suite has built other boosters and sparse Datasets before this one
    from lightgbm_tpu.observability import global_registry
    before = dict(global_registry.snapshot()["counters"])
    facts = {}
    res = run.execute(_manifest(), {"name": CELL, "chips": 1}, config,
                      TINY_TRAFFIC, seed=2 ** 31 + 11, seconds=0.3,
                      trace=trace, devices=jax.devices()[:1],
                      on_chip=False, log=lambda **kw: facts.update(kw))
    assert set(facts["checks"]) == CELL_CHECKS
    assert res["correct"] is True, facts["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert facts["stored_values"] == 8 * 20000
    assert facts["device_columns"] < 16 < facts["used_features"]
    assert max(facts["group_num_bin"]) <= 256
    assert facts["first_tree_counts_equal"] is True
    assert facts["root_gain_ratio"] >= 1 - ref.ROOT_GAIN_REL_TOL
    assert facts["root_counts_equal"] is True
    # the 8-bit reading is over the limit the program's is under
    assert (facts["first_tree_weight_rel_err"] <= ref.WEIGHT_REL_TOL
            < facts["first_tree_weight_rel_err_8bit"])
    assert facts["split_scan_traces"]["generic"] >= 1
    assert set(facts["construct_spans_s"]) == set(
        sparse_train_loop.HOST_SPANS)
    if trace:
        got = set(res["metrics"])
        assert {"construct_s", "first_iter_s", "find_bin_s", "binning_s",
                "efb_plan_s", "efb_bundle_ratio",
                "sparse_values_per_row"} <= got
        assert res["metrics"]["sparse_values_per_row"]["value"] == (
            (before.get("sparse_stored_values", 0) + 8 * 20000)
            / (before.get("sparse_rows", 0) + 20000))
        assert res["metrics"]["efb_bundle_ratio"]["value"] == (
            (before.get("efb_features", 0) + facts["used_features"])
            / (before.get("efb_bundles", 0) + facts["device_columns"]))
    else:
        assert set(res["metrics"]) == {"setup_s", "iter_ms",
                                       "heldout_quality"}
        assert 0.55 <= res["metrics"]["heldout_quality"]["value"] <= 1.0


# --------------------------------------------------- (c) the driver's probe
def test_probe_says_yes_on_this_tree():
    """The child process the driver asks on the chip, here on the CPU:
    this program's sparse path records the three spans and labels the
    decode, so nothing is refused."""
    facts = {}
    sparse_train_loop.require_sparse_spans(
        sparse_train_loop.start_sparse_spans_probe(),
        lambda **kw: facts.update(kw))
    assert facts["asked"] is True and facts["decode_scope"] is True
    assert facts["spans"] == list(sparse_train_loop.HOST_SPANS)


@pytest.mark.parametrize("said,match", [
    ({"spans": [], "decode_scope": False}, "Dataset::find_bin"),
    ({"spans": ["Dataset::find_bin", "Dataset::binning"],
      "decode_scope": True}, "GBDT::plan_bundles"),
    ({"spans": list(sparse_train_loop.HOST_SPANS), "decode_scope": False},
     "Efb::decode")])
def test_driver_refuses_a_program_without_the_spans(said, match):
    facts = {}
    with pytest.raises(SystemExit, match=match):
        sparse_train_loop.require_sparse_spans(
            _Probe(json.dumps(said) + "\n"), lambda **kw: facts.update(kw))
    assert facts["asked"] is True


def test_a_program_without_the_decode_scope_reads_unlabelled(monkeypatch):
    """A tree before PR 36 (`device_scope` patched to nothing) lowers its
    decode without the label, and the probe says so."""
    from contextlib import contextmanager
    from lightgbm_tpu.utils import timer

    @contextmanager
    def no_scope(self, name):
        yield
    monkeypatch.setattr(timer.Timer, "device_scope", no_scope)
    assert sparse_train_loop.sparse_spans_in_program()[
        "decode_scope"] is False


@pytest.mark.parametrize("out", ["", "Traceback\n", '{"other": 1}\n'])
def test_a_probe_that_cannot_be_asked_refuses_nothing(out):
    facts = {}
    probe = _Probe(out)
    sparse_train_loop.require_sparse_spans(probe,
                                           lambda **kw: facts.update(kw))
    assert facts["asked"] is False and probe.killed


# ------------------------- (d) the reference, on good and corrupted trees
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1}


def _first_tree(X, y, corrupt=None):
    """(tree 0, bounds) of a booster over the CSR; `corrupt` edits the
    booster's feature meta before the tree is grown."""
    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=y, params=PARAMS)
    booster = lgb.Booster(PARAMS, train_set)
    g = booster._gbdt
    assert g.grow_params.has_bundles
    if corrupt:
        g.meta = corrupt(g.meta)
    booster.update()
    g._sync_model()
    return g.models_[0], sparse_train_loop._reference_bounds(
        train_set._core)


def _shift_offsets(meta):
    return meta._replace(offset=meta.offset + meta.in_bundle.astype(
        meta.offset.dtype))


def _no_default_bin(meta):
    # a bundle member treated as a column of its own: its default bin is
    # read from the histogram (where no row put it), not restored
    return meta._replace(in_bundle=meta.in_bundle & False)


@pytest.fixture(scope="module")
def carrier_rows(rows):
    """The generator's rows with the carrier group's stored 1.0 given a
    sign by the row, under a label that the largest carrier's column
    decides (-1 there is positive; a tenth flipped).  The best root
    split then parts a bundle member's first bin from its default bin
    (-1 | 0, +1), which is where both faults show: the scan runs right
    to left, so the side it sums holds the default bin.  Under the
    generator's own label the root splits on the departure time, a
    column of its own, and a one-hot member's default bin is its first,
    which the right-to-left scan never reads: a tree grown from either
    fault there is still a tree that sums its rows."""
    X, _ = rows
    X = X.copy()
    rs = np.random.RandomState(36)
    carrier = (X.indices >= 50) & (X.indices < 72)
    X.data[carrier] = rs.choice(np.float32([-1, 1]), int(carrier.sum()))
    flip = rs.rand(X.shape[0]) < 0.1
    minus = np.asarray(X[:, 50].todense()).ravel() == -1.0
    return X, (minus ^ flip).astype(np.float32)


@pytest.mark.parametrize("fault,corrupt", [
    ("none", None), ("offsets_shifted_by_one", _shift_offsets),
    ("default_bin_not_restored", _no_default_bin)])
def test_reference_on_the_programs_first_tree(carrier_rows, fault, corrupt):
    X, y = carrier_rows
    tree, bounds = _first_tree(X, y, corrupt)
    checks, facts = ref.check(tree, X.tocsc(), y, bounds, PARAMS)
    assert set(checks) == {"first_tree_routes_its_rows",
                           "first_tree_sums_its_rows",
                           "root_split_is_the_references"}
    if fault == "none":
        assert all(checks.values()), (checks, facts)
        assert facts["root_gain_ratio"] == 1.0
        assert int(tree.split_feature[0]) == 50
    else:
        assert not checks["root_split_is_the_references"], (fault, facts)


def test_route_is_the_host_predictors(rows):
    """The reference's routing on raw columns against the program's own
    leaf index on the densified rows (small enough here to densify)."""
    import lightgbm_tpu as lgb
    X, y = rows
    train_set = lgb.Dataset(X, label=y, params=PARAMS)
    booster = lgb.Booster(PARAMS, train_set)
    booster.update()
    booster._gbdt._sync_model()
    leaf = np.asarray(booster.predict(X.toarray(), pred_leaf=True)
                      ).reshape(X.shape[0], -1)[:, 0]
    assert np.array_equal(ref.route(booster._gbdt.models_[0], X.tocsc()),
                          leaf)


def test_rounding_to_three_bits():
    assert np.array_equal(ref._round_to([0.16, -0.8, 0.2, 1.0], 3),
                          [0.15625, -0.8125, 0.203125, 1.0])


# ------------------------------ (e) the part readers on a hand-built trace
# ops on one device of a 10 ms window; the label in the op_name decides
HAND_TRACE = {
    "window_ns": [0, 10_000_000],
    "devices": {"/device:TPU:0": [
        ["%gather.1", 1_000_000, 400_000,
         "jit(g)/while/body/Tree.split_find/Efb.decode/gather"],
        ["%select.1", 1_500_000, 100_000,
         "jit(g)/while/body/Tree.split_find/Efb.decode/select_n"],
        ["%fusion.1", 1_700_000, 300_000,
         "jit(g)/while/body/Tree.split_find/vmap(jit(scan))/while"],
        ["%reduce.1", 2_100_000, 250_000,
         "jit(g)/while/body/Tree.partition/Efb.route/reduce_sum"],
        ["%dot.1", 2_400_000, 500_000,
         "jit(g)/while/body/Tree.partition/dot_general"],
    ]},
    "host_spans": [["bench::update", 0, 10_000_000, {}]],
}
ITERATIONS = 2


@pytest.fixture
def hand_ctx(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    trace_dir = tmp_path / "trace" / "cell"
    write_xplane(HAND_TRACE, str(trace_dir / "plugins" / "profile" / "t0"
                                 / "host.xplane.pb"))
    tr = bench_trace.from_xplane(str(trace_dir))
    tr.window = tuple(w + SHIFT_NS for w in HAND_TRACE["window_ns"])
    return SimpleNamespace(
        trace=tr, spans={}, peaks={"hbm_bytes_per_s": 819e9},
        counters={"iterations": ITERATIONS, "num_leaves": 255})


@pytest.mark.parametrize("scope,part,own_ns", [
    ("Tree.split_find", "Efb.decode", 500_000),
    ("Tree.partition", "Efb.route", 250_000)])
def test_parts_read_the_hand_counts(hand_ctx, scope, part, own_ns):
    got = scope_part_ms_per_iter.reduce(hand_ctx, scope, part)
    assert got == pytest.approx(own_ns / 1e6 / ITERATIONS)


def test_decode_roofline_is_bytes_over_the_parts_time(hand_ctx, monkeypatch):
    from benchmarks.reducers import program_total
    counted = {"efb_bundle_bins": 1139, "efb_member_bins": 1130}
    monkeypatch.setattr(program_total, "totals", lambda kind: counted)
    per_tree = (2 * 255 - 1) * (1139 + 1130) * 8
    assert efb_decode_bytes.cost(255, counted) == per_tree
    got = scope_part_hbm_roofline_pct.reduce(
        hand_ctx, "Tree.split_find", "Efb.decode", "efb_decode_bytes")
    assert got == pytest.approx(
        100.0 * (per_tree / 819e9) / (500e-6 / ITERATIONS))
    # a program from before the counters: nothing to read, nothing raised
    monkeypatch.setattr(program_total, "totals", lambda kind: {})
    assert efb_decode_bytes.cost(255, {}) is None
    assert scope_part_hbm_roofline_pct.reduce(
        hand_ctx, "Tree.split_find", "Efb.decode",
        "efb_decode_bytes") is None


def test_new_readers_find_nothing_on_the_recorded_trace(tmp_path,
                                                        monkeypatch):
    """The recorded trace of a program from before the parts were named:
    every new reader returns nothing and does not raise."""
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    tr = bench_trace.from_json(os.path.join(ROOT, "benchmarks", "testdata",
                                            "trace_small.json"))
    ctx = SimpleNamespace(trace=tr, spans={},
                          peaks={"hbm_bytes_per_s": 819e9},
                          counters={"iterations": 1, "num_leaves": 255})
    for trace in (tr, None):
        ctx.trace = trace
        assert scope_part_ms_per_iter.reduce(ctx, "Tree.split_find",
                                             "Efb.decode") is None
        assert scope_part_ms_per_iter.reduce(ctx, "Tree.partition",
                                             "Efb.route") is None
        assert scope_part_hbm_roofline_pct.reduce(
            ctx, "Tree.split_find", "Efb.decode",
            "efb_decode_bytes") is None


# ------------------------------------------------------ (f) the manifest
def test_manifest_gives_the_cell_its_five_metrics():
    manifest = _manifest()
    cells = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1
    assert cells[0]["traffic"] == "train_sparse"
    assert len(manifest["workloads"]) >= 6     # 7 since PR 41
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    own = {m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [CELL]}
    assert own == NEW_METRICS
    for name in NEW_METRICS:
        path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                            name + ".json")
        with open(path) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "reducers", spec["reducer"] + ".py"))
    entry = [c for c in manifest["configs"]
             if c["name"] == "expo-onehot700-b63"][0]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    config = _config()
    assert entry["source"] == config["source"]
    assert (config["rows"], config["features"]) == (11_000_000, 700)
    assert config["params"]["enable_bundle"] is True
    assert config["params"]["max_conflict_rate"] == 0.0
    assert "tpu_growth_strategy" not in config["params"]


# ------------------------------------------------- (g) the plan's counters
def test_plan_counters_equal_the_hand_counts(rows):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability import global_registry

    def counters():
        return dict(global_registry.snapshot()["counters"])
    X, y = rows
    before = counters()
    train_set = lgb.Dataset(X, label=y, params=PARAMS)
    g = lgb.Booster(PARAMS, train_set)._gbdt
    gained = {k: v - before.get(k, 0) for k, v in counters().items()}
    plan, core = g.bundle_plan, train_set._core
    assert gained["sparse_rows"] == 30000
    assert gained["sparse_stored_values"] == 8 * 30000
    assert gained["efb_features"] == len(core.used_features)
    assert gained["efb_bundles"] == plan.num_groups == g.binned_dev.shape[0]
    assert gained["efb_bundle_bins"] == int(plan.group_num_bin.sum())
    assert gained["efb_member_bins"] == sum(
        core.bin_mappers[f].num_bin for f in core.used_features)
