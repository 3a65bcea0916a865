"""The categorical cell of the benchmark (PR 41) on the CPU at small
sizes: the generator (the one-hot cell's rows decoded), the driver end to
end through `run.execute(..., on_chip=False)` with the configuration's
own file at 20,000 rows, its probe of the program's categorical scan and
its refusal of a program without it, the plain reference on the
program's tree 0 (also at the chip's bf16 operand grid), on two corrupted
trees, on scans by another rule and on category maps not its own, the
categorical scan
against the reference's float64 rule on both engines' calls, the part
reader and the scan's roofline on a hand-built trace, and the plan's
counters."""

import copy
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
from benchmarks.drivers import cat_train_loop   # noqa: E402
from benchmarks.generators import expo_cat_like, expo_like   # noqa: E402
from benchmarks.kernel_costs import cat_scan_bytes   # noqa: E402
from benchmarks.reducers import (scope_part_hbm_roofline_pct,   # noqa: E402
                                 scope_part_ms_per_iter)
from benchmarks.references import categorical_first_tree as ref   # noqa: E402
from test_bench_scope_readers import SHIFT_NS, write_xplane   # noqa: E402
from test_rank_benchmark import _Probe   # noqa: E402  (a child that has said `out`)

CELL = "expo-cat8-b63.train_cat"
CONFIG = "expo-cat8-b63"


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------ (a) generator
@pytest.fixture(scope="module")
def rows():
    return expo_cat_like.make(20000, 8, 36)


def test_shape_codes_and_numerics(rows):
    X, y = rows
    assert X.shape == (20000, 8) and X.dtype == np.float32
    assert y.dtype == np.float32 and 0.17 < y.mean() < 0.23
    for j, k in enumerate(expo_cat_like.CATEGORIES):
        col = X[:, j]
        assert np.array_equal(col, np.floor(col))
        assert col.min() >= 0 and col.max() <= k - 1
    assert expo_cat_like.CATEGORIES == (12, 31, 7, 22, 313, 313)
    assert X[:, 6].min() >= 1 and X[:, 6].max() <= 2359   # hhmm
    assert X[:, 7].min() >= 11                            # miles


def test_rows_are_the_one_hot_cells(rows):
    """Rows, labels and held-out rows are `expo_like`'s decoded: the
    one-hot matrix rebuilt from the 8 columns is the generator's own."""
    X, y = rows
    for seed in (36, 37):
        csr, y1 = expo_like.make(20000, 700, seed)
        X1, y2 = expo_cat_like.make(20000, 8, seed)
        assert np.array_equal(y1, y2)
        dense = csr.toarray()
        cols = X1[:, :6].astype(np.int64) + expo_like.STARTS[:6]
        assert (dense[np.arange(20000)[:, None], cols] == 1.0).all()
        assert (dense[:, 698] == X1[:, 6]).all()
        assert (dense[:, 699] == X1[:, 7]).all()
        assert (np.count_nonzero(dense, axis=1) == 8).all()
    # the same seed, the same bytes
    X2, y2 = expo_cat_like.make(20000, 8, 36)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)


def test_seed_permutes_columns_and_nothing_else(rows):
    X0, y0 = rows
    config = {**_config(), "rows": 20000}
    for seed in (1, 2 ** 31 + 11):
        X, y = bench_data.make(config, seed)
        order = bench_data.column_order(config, seed)
        assert np.array_equal(y, y0)
        assert np.array_equal(X, X0[:, order])
        # the driver's categorical_feature follows the columns
        cats = cat_train_loop.categorical_feature(config, seed)
        assert sorted(order[cats].tolist()) == config["categorical_columns"]


def test_other_widths_are_refused():
    with pytest.raises(ValueError, match="8"):
        expo_cat_like.make(10, 700, 1)


# ------------------------------------------- (b) the driver's whole flow
TINY_TRAFFIC = {"driver": "cat_train_loop", "warmup_iters": 2,
                "quality_trees": 6, "test_rows": 4000, "traced_iters": 2}
CELL_CHECKS = {"no_recompile_in_window", "train_scores_finite",
               "heldout_scores_finite", "quality_at_or_over_floor",
               "categorical", "categorical_splits_in_model",
               "category_bins_are_the_references",
               "first_tree_routes_its_rows", "first_tree_sums_its_rows",
               "root_split_is_the_references",
               "categorical_splits_are_the_references"}


@pytest.mark.parametrize("trace", [False, True])
def test_cat_train_loop_end_to_end(trace, tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    config = _config()
    config["rows"] = 20000
    config["params"] = {**config["params"], "verbosity": -1,
                        "num_leaves": 31}
    config["quality"]["floor"] = 0.55
    facts = {}
    res = run.execute(_manifest(), {"name": CELL, "chips": 1}, config,
                      TINY_TRAFFIC, seed=2 ** 31 + 11, seconds=0.3,
                      trace=trace, devices=jax.devices()[:1],
                      on_chip=False, log=lambda **kw: facts.update(kw))
    assert set(facts["checks"]) == CELL_CHECKS
    assert res["correct"] is True, facts["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert facts["cat_device_columns"] == 6 and not facts["bundled"]
    assert max(facts["cat_num_bin"]) > 63 and facts["hist_bins"] > 63
    assert facts["cat_splits_tree0"] > 0 and facts["cat_splits_share"] > 0
    assert facts["first_tree_counts_equal"] is True
    assert facts["root_gain_ratio"] >= 1 - ref.SPLIT_GAIN_REL_TOL
    assert facts["root_counts_equal"] is True
    assert facts["category_bins_equal"] is True
    assert facts["cat_nodes_checked"] and all(
        n["left_set_equal"] and n["gain_ratio"] >= 1 - ref.SPLIT_GAIN_REL_TOL
        for n in facts["cat_nodes_checked"])
    # the 8-bit reading is over the limit the program's is under
    assert (facts["first_tree_weight_rel_err"] <= ref.WEIGHT_REL_TOL
            < facts["first_tree_weight_rel_err_8bit"])
    assert facts["split_scan_generic_traces"] >= 1
    assert set(facts["construct_spans_s"]) == {"Dataset::find_bin",
                                               "Dataset::binning"}
    if trace:
        assert {"construct_s", "first_iter_s", "find_bin_s",
                "binning_s"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"setup_s", "iter_ms",
                                       "heldout_quality"}
        assert 0.55 <= res["metrics"]["heldout_quality"]["value"] <= 1.0


# --------------------------------------------------- (c) the driver's probe
def test_probe_says_yes_on_this_tree():
    facts = {}
    cat_train_loop.require_cat_scan(cat_train_loop.start_cat_scan_probe(),
                                    lambda **kw: facts.update(kw))
    assert facts["asked"] is True and facts["scan_scope"] is True
    assert facts["cat_bins_counted"] is True


@pytest.mark.parametrize("said", [
    {"cat_bins_counted": False, "scan_scope": False},
    {"cat_bins_counted": True, "scan_scope": False},
    {"cat_bins_counted": False, "scan_scope": True}])
def test_driver_refuses_a_program_without_the_scope(said):
    facts = {}
    with pytest.raises(SystemExit, match="Cat::scan"):
        cat_train_loop.require_cat_scan(_Probe(json.dumps(said) + "\n"),
                                        lambda **kw: facts.update(kw))
    assert facts["asked"] is True


def test_a_program_without_the_scan_scope_reads_unlabelled(monkeypatch):
    """A tree before PR 41 (`device_scope` patched to nothing) lowers its
    scan without the label, and the probe says so."""
    from contextlib import contextmanager
    from lightgbm_tpu.utils import timer

    @contextmanager
    def no_scope(self, name):
        yield
    monkeypatch.setattr(timer.Timer, "device_scope", no_scope)
    assert cat_train_loop.cat_scan_in_program()["scan_scope"] is False


@pytest.mark.parametrize("out", ["", "Traceback\n", '{"other": 1}\n'])
def test_a_probe_that_cannot_be_asked_refuses_nothing(out):
    facts = {}
    probe = _Probe(out)
    cat_train_loop.require_cat_scan(probe, lambda **kw: facts.update(kw))
    assert facts["asked"] is False and probe.killed


# ------------------------- (d) the reference, on good and corrupted trees
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
          "max_cat_to_onehot": 4, "max_cat_threshold": 32, "cat_smooth": 10,
          "cat_l2": 10, "min_data_per_group": 100}
CATS = [0, 1, 2, 3, 4, 5]


def _first_tree(X, y, params=PARAMS):
    """(tree 0, the reference's columns, booster) of a booster over X."""
    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=y, categorical_feature=CATS,
                            params=params)
    booster = lgb.Booster(params, train_set)
    booster.update()
    booster._gbdt._sync_model()
    return (booster._gbdt.models_[0],
            cat_train_loop.reference_columns(train_set._core, CATS), booster)


def _check(tree, X, y, columns, booster, params=PARAMS):
    """The reference's checks and facts, at the booster's operand
    precision."""
    return ref.check(tree, X, y, columns, params,
                     cat_train_loop.operand_bits(
                         booster._gbdt.grow_params.hist_method))


@pytest.fixture(scope="module")
def first_tree(rows):
    return _first_tree(*rows)


def _shift_bitsets(tree):
    """Every categorical node's category set moved up by one: a bitset
    read from the wrong bit."""
    bad = copy.deepcopy(tree)
    bad.cat_threshold = [(int(w) << 1) & 0xFFFFFFFF
                         for w in tree.cat_threshold]
    return bad


def test_reference_passes_on_the_programs_first_tree(rows, first_tree):
    X, y = rows
    tree, columns, booster = first_tree
    checks, facts = _check(tree, X, y, columns, booster)
    assert all(checks.values()), (checks, facts)
    assert facts["first_tree_categorical_splits"] > 0
    assert facts["root_gain_ratio"] == pytest.approx(1.0, abs=1e-9)
    assert facts["first_tree_weight_rel_err"] < ref.WEIGHT_REL_TOL


def test_reference_fails_on_a_bitset_shifted_by_one_bit(rows, first_tree):
    X, y = rows
    tree, columns, booster = first_tree
    checks, _ = _check(_shift_bitsets(tree), X, y, columns, booster)
    assert not checks["first_tree_routes_its_rows"]


def test_reference_fails_where_the_other_bin_goes_left(rows, monkeypatch):
    """A program whose categorical splits send the "other" bin (bin 0:
    the categories past 99% coverage) left: the saved model cannot name
    those categories, so its rows route right on the raw columns, and
    the leaves' counts are not the program's."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.learner import grow
    real = grow.find_best_split

    def other_left(*args, **kw):
        res = real(*args, **kw)
        return res._replace(cat_bitset=jnp.where(
            res.is_cat, res.cat_bitset | 1, res.cat_bitset))
    jax.clear_caches()
    monkeypatch.setattr(grow, "find_best_split", other_left)
    try:
        X, y = rows
        tree, columns, booster = _first_tree(X, y)
    finally:
        jax.clear_caches()
    checks, _ = _check(tree, X, y, columns, booster)
    assert not checks["first_tree_routes_its_rows"]


def test_route_is_the_host_predictors(rows, first_tree):
    X, _ = rows
    tree, _, booster = first_tree
    leaf = np.asarray(booster.predict(X, pred_leaf=True)
                      ).reshape(X.shape[0], -1)[:, 0]
    assert np.array_equal(ref.route(tree, X.astype(np.float64))[0], leaf)


def test_unseen_negative_and_nan_categories_go_right():
    x = np.array([0, 3, 5, 7, -1, np.nan, 1e9])
    assert ref._cat_left(x, {0, 5, 7}).tolist() == [
        True, False, True, True, False, False, False]


@pytest.mark.parametrize("rule", [{"cat_smooth": 1},
                                  {"min_data_per_group": 10},
                                  {"max_cat_threshold": 4}])
def test_reference_fails_on_a_scan_by_another_rule(rows, rule):
    """A scan that picks valid but lesser subsets (another smoothing,
    another grouping, a shorter prefix) grows a tree whose rows and sums
    agree with themselves: only (c) at the categorical nodes sees it."""
    X, y = rows
    tree, columns, booster = _first_tree(X, y, {**PARAMS, **rule})
    checks, _ = _check(tree, X, y, columns, booster)
    assert checks["first_tree_routes_its_rows"]
    assert not checks["categorical_splits_are_the_references"]


def test_reference_judges_at_the_programs_operand_precision(rows,
                                                           monkeypatch):
    """The chip's histogram rounds each row's gradient and hessian to
    bf16 before any sum: a program rounded so on the CPU passes the
    reference at 7 bits, as the driver asks it."""
    import jax
    from lightgbm_tpu.ops import histogram
    monkeypatch.setattr(histogram, "BF16_OPERAND_METHODS",
                        histogram.BF16_OPERAND_METHODS + ("segment",))
    jax.clear_caches()
    try:
        X, y = rows
        tree, columns, booster = _first_tree(X, y)
    finally:
        jax.clear_caches()
    assert cat_train_loop.operand_bits(
        booster._gbdt.grow_params.hist_method) == 7
    checks, facts = _check(tree, X, y, columns, booster)
    assert all(checks.values()), (checks, facts)
    assert facts["cat_nodes_checked"]


@pytest.mark.parametrize("sample_cnt", [None, 5000])
def test_category_bins_are_the_datasets(rows, sample_cnt):
    """The reference's map, rebuilt from the raw columns of the rows the
    Dataset samples (all 20,000, or 5,000 drawn), is the Dataset's."""
    X, y = rows
    params = dict(PARAMS)
    if sample_cnt:
        params["bin_construct_sample_cnt"] = sample_cnt
    import lightgbm_tpu as lgb
    core = lgb.Dataset(X, label=y, categorical_feature=CATS,
                       params=params).construct()._core
    sample = ref.bin_sample_rows(len(X), params)
    assert len(sample) == (sample_cnt or len(X))
    for f, (kind, spec) in cat_train_loop.reference_columns(
            core, CATS).items():
        if kind == "cat":
            assert ref.category_bins(X[sample, f], 63) == spec


@pytest.mark.parametrize("fault", ["bins_rotated", "made_numeric"])
def test_reference_fails_on_a_category_map_not_its_own(rows, first_tree,
                                                        fault):
    """A Dataset map that keeps other categories, or orders them
    otherwise, or a categorical column binned as numeric, fails (m)."""
    X, y = rows
    tree, columns, booster = first_tree
    f = next(f for f, (kind, _) in columns.items() if kind == "cat")
    spec = columns[f][1]
    if fault == "bins_rotated":
        cats = spec["bin_to_cat"][1:]
        cats = cats[1:] + cats[:1]
        bad = {"num_bin": spec["num_bin"], "bin_to_cat": [-1] + cats,
               "cat_to_bin": {-1: 0, **{c: b + 1 for b, c in
                                        enumerate(cats)}}}
    else:
        bad = None
    checks, _ = _check(tree, X, y, {**columns, f: ("cat", bad)}, booster)
    assert not checks["category_bins_are_the_references"]


# ----------------- (e) the device scan against the float64 rule, two engines
def test_cat_scan_picks_the_references_categories():
    """`tools/kernel_checks.py`'s chip check at small sizes: the scan's
    left set, count and gain against the reference's rule, at 8, 23 and
    189 bins, with and without rare and "other" categories, a leaf a
    call (leaf-wise) and leaves mapped (the wave engine's vmap)."""
    from tools.kernel_checks import _cat_scan_mismatches
    assert _cat_scan_mismatches(widths=(8, 23, 189), B=189) == []


# ------------------------------ (f) the part reader on a hand-built trace
HAND_TRACE = {
    "window_ns": [0, 10_000_000],
    "devices": {"/device:TPU:0": [
        ["%sort.1", 1_000_000, 400_000, "jit(g)/while/body/Tree.split_find/"
         "vmap(jit(find_best_split))/Cat.scan/sort"],
        ["%while.1", 1_500_000, 200_000, "jit(g)/while/body/Tree.split_find/"
         "vmap(jit(find_best_split))/Cat.scan/while"],
        ["%fusion.1", 1_800_000, 300_000, "jit(g)/while/body/Tree.split_find/"
         "vmap(jit(find_best_split))/while"],
        ["%fusion.2", 2_200_000, 100_000,
         "jit(g)/while/body/Tree.split_find/vmap(Cat.scan)/sort"],
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


def test_cat_scan_ms_reads_the_part(hand_ctx):
    """`vmap(Cat.scan)` (a scope entered under `vmap`) is no part."""
    spec = json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       "cat_scan_ms.json")))
    got = scope_part_ms_per_iter.reduce(hand_ctx, **spec["args"])
    assert got == pytest.approx(600_000 / 1e6 / ITERATIONS)


def test_cat_scan_roofline_is_bytes_over_the_parts_time(hand_ctx,
                                                        monkeypatch):
    from benchmarks.reducers import program_total
    counted = {"cat_bins": 435}
    monkeypatch.setattr(program_total, "totals", lambda kind: counted)
    per_tree = (2 * 255 - 1) * 435 * 8
    assert cat_scan_bytes.cost(255, counted) == per_tree
    spec = json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       "cat_scan_roofline.json")))
    got = scope_part_hbm_roofline_pct.reduce(hand_ctx, **spec["args"])
    assert got == pytest.approx(
        100.0 * (per_tree / 819e9) / (600e-6 / ITERATIONS))
    # a program from before the counter: nothing to read, nothing raised
    monkeypatch.setattr(program_total, "totals", lambda kind: {})
    assert cat_scan_bytes.cost(255, {}) is None
    assert scope_part_hbm_roofline_pct.reduce(hand_ctx,
                                              **spec["args"]) is None


def test_readers_find_nothing_on_the_recorded_trace(tmp_path, monkeypatch):
    """The recorded trace of a program from before the part was named."""
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    tr = bench_trace.from_json(os.path.join(ROOT, "benchmarks", "testdata",
                                            "trace_small.json"))
    ctx = SimpleNamespace(trace=tr, spans={},
                          peaks={"hbm_bytes_per_s": 819e9},
                          counters={"iterations": 1, "num_leaves": 255})
    for name, reducer in (("cat_scan_ms", scope_part_ms_per_iter),
                          ("cat_scan_roofline", scope_part_hbm_roofline_pct)):
        spec = json.load(open(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".json")))
        for trace in (tr, None):
            ctx.trace = trace
            assert reducer.reduce(ctx, **spec["args"]) is None


# -------------------------------------- (g) the manifest and the counters
def test_manifest_holds_the_cell_and_its_metrics():
    m = _manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "train_cat", "chips": 1}
    entry = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert entry["reduced"] == [] and entry["file"].endswith(
        CONFIG + ".json")
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name in ("cat_scan_ms", "cat_scan_roofline"):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "iter_ms"
    config = _config()
    assert config["rows"] == 11_000_000 and config["features"] == 8
    assert config["data_seed"] == 36
    assert config["expect"]["cat_features"] == len(
        config["categorical_columns"])


def test_the_plan_and_the_trees_count_themselves(first_tree):
    """`cat_features` / `cat_bins` where the plan is fixed, `splits_total`
    / `cat_splits_total` where a packed tree is decoded."""
    from lightgbm_tpu.observability import global_registry
    tree, _, booster = first_tree
    g = booster._gbdt
    before = dict(global_registry.snapshot()["counters"])
    booster.update()
    g._sync_model()
    after = dict(global_registry.snapshot()["counters"])
    t = g.models_[-1]
    nl = int(t.num_leaves)
    assert after["splits_total"] - before["splits_total"] == nl - 1
    assert (after["cat_splits_total"] - before["cat_splits_total"]
            == int(np.count_nonzero(np.asarray(t.decision_type[:nl - 1])
                                    & 1)))
    is_cat = np.asarray(g.f_is_cat, bool)
    assert after["cat_features"] >= 6
    assert after["cat_bins"] >= int(np.asarray(g.f_num_bin)[is_cat].sum())
