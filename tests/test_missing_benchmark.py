"""The missing-value cell of the benchmark on the CPU at a small size with
the cell's own structure (20,000 rows of the generator's 968 columns, 31
leaves): the generator (NaN share, positive rate, station blocks missing
together, determinism, `--seed` moving the columns alone), the plain
reference on the program's tree 0 (also at the chip's bf16 operand grid)
and on three programs it must refuse (a flipped `default_left`,
`use_missing=False`, no forward scan), the driver end to end through
`run.execute(..., on_chip=False)`, its probe of the program, the part
reader on a hand-built trace, and the counters."""

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
from benchmarks.drivers import missing_train_loop   # noqa: E402
from benchmarks.drivers.cat_train_loop import operand_bits   # noqa: E402
from benchmarks.generators import bosch_like   # noqa: E402
from benchmarks.reducers import scope_part_ms_per_iter   # noqa: E402
from benchmarks.references import missing_first_tree as ref   # noqa: E402
from test_bench_scope_readers import SHIFT_NS, write_xplane   # noqa: E402
from test_rank_benchmark import _Probe   # noqa: E402  (a child that has said `out`)

CELL = "bosch-1000k-b63.train_missing"
CONFIG = "bosch-1000k-b63"
ROWS = 20000


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
    return bosch_like.make(ROWS, 968, _config()["data_seed"])


def test_shape_nan_share_and_grid(rows):
    X, y = rows
    assert X.shape == (ROWS, 968) and X.dtype == np.float32
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert 0.80 <= np.isnan(X).mean() <= 0.82
    v = X[~np.isnan(X)].astype(np.float64) * 1000
    assert np.abs(v - np.rint(v)).max() < 1e-3        # the file's 0.001 grid
    few = bosch_like.layout()["few"]
    assert few.sum() == 242                            # a quarter
    levels = np.array([len(np.unique(c[~np.isnan(c)])) for c in X.T])
    assert levels[few].min() >= 2 and levels[few].max() <= 15
    assert levels[~few].min() >= 100                   # hundreds


def test_positive_rate(rows):
    """The intercept makes the mean probability 0.581% on the drawn rows;
    the drawn labels lie within four binomial deviations of it."""
    X, y = rows
    p = bosch_like.probability(X)
    assert p.mean() == pytest.approx(bosch_like.POSITIVE_RATE, abs=1e-9)
    sd = np.sqrt(0.00581 * (1 - 0.00581) / ROWS)
    assert abs(y.mean() - 0.00581) < 4 * sd


def test_station_blocks_missing_together(rows):
    """A station's columns are NaN together on the rows whose route skips
    it (as many as the route distribution says), and on the rest each is
    NaN with the small visited rate."""
    X, _ = rows
    station = bosch_like.layout()["station"]
    visit = bosch_like.station_visit_share()
    rate = bosch_like.visited_nan_rate()
    assert 0 < rate < 0.05
    assert bosch_like.station_columns().tolist()[:24] == [7] * 24
    assert bosch_like.station_columns()[24:26].sum() == 513
    for s in range(bosch_like.STATIONS):
        nan = np.isnan(X[:, station == s])
        skipped = nan.all(axis=1)
        assert skipped.mean() == pytest.approx(1 - visit[s], abs=0.02)
        assert nan[~skipped].mean() == pytest.approx(rate, abs=0.01)


def test_rows_are_the_data_seeds():
    a = bosch_like.make(2000, 968, 43)
    b = bosch_like.make(2000, 968, 43)
    c = bosch_like.make(2000, 968, 44)
    assert np.array_equal(a[0], b[0], equal_nan=True)
    assert np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0], equal_nan=True)


def test_seed_permutes_columns_and_nothing_else(rows):
    X0, y0 = rows
    config = {**_config(), "rows": ROWS}
    for seed in (1, 2 ** 31 + 11):
        X, y = bench_data.make(config, seed)
        order = bench_data.column_order(config, seed)
        assert np.array_equal(y, y0)
        assert np.array_equal(X, X0[:, order], equal_nan=True)


def test_driver_rows_keep_the_generators_column_order():
    """The cell trains on the generator's column order whatever `--seed`
    says (`missing_train_loop.make_rows`: here another order grows other
    trees), its held-out rows from `data_seed + 1`."""
    config = {**_config(), "rows": 2000}
    X, y = missing_train_loop.make_rows(config)
    X0, y0 = bosch_like.make(2000, 968, config["data_seed"])
    assert np.array_equal(X, X0, equal_nan=True) and np.array_equal(y, y0)
    Xt, yt = missing_train_loop.make_rows(config, 500)
    X1, y1 = bosch_like.make(500, 968, config["data_seed"] + 1)
    assert np.array_equal(Xt, X1, equal_nan=True) and np.array_equal(yt, y1)


def test_other_widths_are_refused():
    with pytest.raises(ValueError, match="968"):
        bosch_like.make(10, 96, 1)


def test_missing_is_informative_both_ways():
    """Half the label's columns carry more risk when missing than a
    value two deviations up, half less than one two deviations down:
    splits on them send NaN right or left."""
    lay = bosch_like.layout()
    effect, weight = lay["missing_effect"], lay["weight"]
    assert (effect > 0).sum() == (effect < 0).sum() == 8
    assert (np.abs(effect) >= 1.2 * np.abs(weight) - 1e-12).all()
    assert effect[0] >= 2 * abs(weight[0])
    assert len(set(lay["station"][lay["informative"]])) == 16


# ---------------------------- (b) the reference on the program's tree 0
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
          "use_missing": True, "zero_as_missing": False}


def _first_tree(X, y, params=PARAMS):
    """(tree 0, the reference's columns, booster) of a booster over X."""
    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=y, params=params)
    booster = lgb.Booster(params, train_set)
    booster.update()
    booster._gbdt._sync_model()
    return (booster._gbdt.models_[0],
            missing_train_loop.reference_columns(train_set._core), booster)


def _check(tree, X, y, columns, booster):
    """The reference's checks and facts under the configuration's
    parameters, at the booster's operand precision."""
    return ref.check(tree, X, y, columns, PARAMS,
                     operand_bits(booster._gbdt.grow_params.hist_method))


@pytest.fixture(scope="module")
def first_tree(rows):
    return _first_tree(*rows)


def test_reference_passes_on_the_programs_first_tree(rows, first_tree):
    X, y = rows
    tree, columns, booster = first_tree
    checks, facts = _check(tree, X, y, columns, booster)
    assert all(checks.values()), (checks, facts)
    assert facts["nan_typed_columns"] == 968
    # the root's best split sends NaN right: the forward scan found it
    assert facts["root_default_left"] is False and facts["root_nan_rows"]
    assert facts["root_direction_gap"] > ref.SPLIT_GAIN_REL_TOL
    assert facts["first_tree_nan_left_splits"] > 0
    assert facts["first_tree_nan_right_splits"] > 0
    assert facts["nan_nodes_checked"]
    assert facts["root_gain_ratio"] == pytest.approx(1.0, abs=1e-9)


def test_route_is_the_host_predictors(rows, first_tree):
    X, _ = rows
    tree, columns, booster = first_tree
    leaf = np.asarray(booster.predict(X, pred_leaf=True)
                      ).reshape(X.shape[0], -1)[:, 0]
    types = ref.missing_types(X, PARAMS)
    assert np.array_equal(ref.route(tree, X, types)[0], leaf)


def test_reference_judges_at_the_programs_operand_precision(rows,
                                                           monkeypatch):
    """The chip's histogram rounds each row's gradient and hessian to
    bf16 before any sum: a program rounded so on the CPU passes the
    reference at 7 bits, as the driver asks it, with its leaf sums under
    the limits that the same sums at 8 bits are over."""
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
    assert operand_bits(booster._gbdt.grow_params.hist_method) == 7
    checks, facts = _check(tree, X, y, columns, booster)
    assert all(checks.values()), (checks, facts)
    assert (facts["first_tree_value_abs_err"] <= ref.VALUE_ABS_TOL
            < facts["first_tree_value_abs_err_8bit"])
    assert (facts["first_tree_weight_rel_err"] <= ref.WEIGHT_REL_TOL
            < facts["first_tree_weight_rel_err_8bit"])


# -------------------------------------------- (c) the reference has teeth
def _flip_default_left(tree, node):
    bad = copy.deepcopy(tree)
    bad.decision_type[node] ^= ref.DEFAULT_LEFT
    return bad


def test_reference_fails_on_a_flipped_default_left(rows, first_tree):
    """The root's NaN sent the other way: the leaves hold other rows,
    and the root's side of NaN is not the better one."""
    X, y = rows
    tree, columns, booster = first_tree
    checks, facts = _check(_flip_default_left(tree, 0), X, y, columns,
                           booster)
    assert not checks["first_tree_routes_its_rows"]
    assert not checks["root_split_is_the_references"]
    assert facts["root_direction_ok"] is False


def test_reference_fails_on_a_program_without_missing_values(rows):
    """`use_missing=False`: every column typed none though its sampled
    rows hold NaN, NaN read as 0.0."""
    X, y = rows
    tree, columns, booster = _first_tree(X, y,
                                         {**PARAMS, "use_missing": False})
    assert all(mt == ref.MISSING_NONE for _, mt in columns.values())
    checks, _ = _check(tree, X, y, columns, booster)
    assert not checks["missing_types_are_the_references"]


def test_reference_fails_on_a_program_without_the_forward_scan(rows,
                                                              monkeypatch):
    """A program that scans only the reverse direction (`has_missing`
    forced False: NaN always joins the left) cannot send the root's NaN
    right, where the best split sends it."""
    import jax
    from lightgbm_tpu.boosting import gbdt
    real = gbdt.SplitParams
    monkeypatch.setattr(gbdt, "SplitParams",
                        lambda **kw: real(**{**kw, "has_missing": False}))
    jax.clear_caches()
    try:
        X, y = rows
        tree, columns, booster = _first_tree(X, y)
    finally:
        jax.clear_caches()
    assert not booster._gbdt.grow_params.split.has_missing
    checks, facts = _check(tree, X, y, columns, booster)
    assert checks["missing_types_are_the_references"]
    assert not checks["root_split_is_the_references"]
    assert facts["root_gain_ratio"] < 1 - ref.SPLIT_GAIN_REL_TOL


# ------------------------------------------- (d) the driver's whole flow
TINY_TRAFFIC = {"driver": "missing_train_loop", "warmup_iters": 2,
                "quality_trees": 6, "test_rows": 4000, "traced_iters": 2}
CELL_CHECKS = {"no_recompile_in_window", "train_scores_finite",
               "heldout_scores_finite", "quality_at_or_over_floor",
               "missing_typed", "nan_both_ways",
               "missing_types_are_the_references",
               "first_tree_routes_its_rows", "first_tree_sums_its_rows",
               "root_split_is_the_references",
               "nan_splits_are_the_references"}


@pytest.mark.parametrize("trace", [False, True])
def test_missing_train_loop_end_to_end(trace, tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    config = _config()
    config["rows"] = ROWS
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
    assert facts["missing_device_columns"] == 968 == facts[
        "missing_features"] and not facts["bundled"]
    assert facts["hist_classes"] == [[16, 225], [32, 17], [64, 726]]
    assert facts["nan_left_splits"] > 0 and facts["nan_right_splits"] > 0
    assert (facts["missing_right_splits_total"]
            == facts["nan_right_splits_grown"])
    assert 0.80 <= facts["nan_share_X"] <= 0.82
    assert facts["first_tree_counts_equal"] is True
    assert facts["root_default_left"] is False
    if trace:
        assert {"construct_s", "first_iter_s", "find_bin_s",
                "binning_s"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"setup_s", "iter_ms",
                                       "heldout_quality"}
        assert 0.55 <= res["metrics"]["heldout_quality"]["value"] <= 1.0


# --------------------------------------------------- (e) the driver's probe
def test_probe_says_yes_on_this_tree():
    facts = {}
    missing_train_loop.require_missing_scan(
        missing_train_loop.start_missing_scan_probe(),
        lambda **kw: facts.update(kw))
    assert facts["asked"] is True and facts["scan_scope"] is True
    assert facts["missing_features_counted"] is True


@pytest.mark.parametrize("said", [
    {"missing_features_counted": False, "scan_scope": False},
    {"missing_features_counted": True, "scan_scope": False},
    {"missing_features_counted": False, "scan_scope": True}])
def test_driver_refuses_a_program_without_the_scope(said):
    facts = {}
    with pytest.raises(SystemExit, match="Split::missing_right"):
        missing_train_loop.require_missing_scan(
            _Probe(json.dumps(said) + "\n"), lambda **kw: facts.update(kw))
    assert facts["asked"] is True


def test_a_program_without_the_scan_scope_reads_unlabelled(monkeypatch):
    """A program whose forward scan carries no label (`device_scope`
    patched to nothing) lowers its scan without it, and the probe says
    so."""
    from contextlib import contextmanager
    from lightgbm_tpu.utils import timer

    @contextmanager
    def no_scope(self, name):
        yield
    monkeypatch.setattr(timer.Timer, "device_scope", no_scope)
    assert missing_train_loop.missing_scan_in_program()[
        "scan_scope"] is False


@pytest.mark.parametrize("out", ["", "Traceback\n", '{"other": 1}\n'])
def test_a_probe_that_cannot_be_asked_refuses_nothing(out):
    facts = {}
    probe = _Probe(out)
    missing_train_loop.require_missing_scan(probe,
                                            lambda **kw: facts.update(kw))
    assert facts["asked"] is False and probe.killed


# ------------------------------ (f) the part reader on a hand-built trace
SCAN = "jit(g)/while/body/Tree.split_find/jit(find_best_split_dense)"
HAND_TRACE = {
    "window_ns": [0, 10_000_000],
    "devices": {"/device:TPU:0": [
        ["%fusion.1", 1_000_000, 300_000,
         SCAN + "/Split.missing_right/while/body/add"],
        ["%while.1", 1_500_000, 100_000, SCAN + "/Split.missing_right/while"],
        ["%fusion.2", 1_800_000, 400_000, SCAN + "/while/body/add"],
        ["%fusion.3", 2_300_000, 50_000,
         "jit(g)/while/body/Tree.split_find/vmap(Split.missing_right)/add"],
    ]},
    "host_spans": [["bench::update", 0, 10_000_000, {}]],
}
ITERATIONS = 2


def _spec():
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           "missing_scan_ms.json")) as f:
        return json.load(f)


def test_missing_scan_ms_reads_the_part(tmp_path, monkeypatch):
    """The forward scan's own ops under `Tree.split_find`; the reverse
    scan's, and a part entered under `vmap`, are not it."""
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    trace_dir = tmp_path / "trace" / "cell"
    write_xplane(HAND_TRACE, str(trace_dir / "plugins" / "profile" / "t0"
                                 / "host.xplane.pb"))
    tr = bench_trace.from_xplane(str(trace_dir))
    tr.window = tuple(w + SHIFT_NS for w in HAND_TRACE["window_ns"])
    ctx = SimpleNamespace(trace=tr, spans={}, peaks={},
                          counters={"iterations": ITERATIONS})
    got = scope_part_ms_per_iter.reduce(ctx, **_spec()["args"])
    assert got == pytest.approx(400_000 / 1e6 / ITERATIONS)


def test_reader_finds_nothing_on_the_recorded_trace(tmp_path, monkeypatch):
    """The recorded trace of a program from before the part was named:
    nothing to read, nothing raised."""
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    tr = bench_trace.from_json(os.path.join(ROOT, "benchmarks", "testdata",
                                            "trace_small.json"))
    ctx = SimpleNamespace(trace=tr, spans={}, peaks={},
                          counters={"iterations": 1})
    for trace in (tr, None):
        ctx.trace = trace
        assert scope_part_ms_per_iter.reduce(ctx, **_spec()["args"]) is None


# -------------------------------------- (g) the manifest and the counters
def test_manifest_holds_the_cell_and_its_metric():
    m = _manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "train_missing", "chips": 1}
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    entry = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert entry["reduced"] == [] and entry["file"].endswith(
        CONFIG + ".json")
    metric = {p["name"]: p for p in m["per_layer"]}["missing_scan_ms"]
    assert metric["workloads"] == [CELL] and metric["moves"] == "iter_ms"
    assert metric["layer"] == "learner"
    config = _config()
    assert config["rows"] == 1_000_000 and config["features"] == 968
    assert config["params"]["use_missing"] is True
    assert config["params"]["zero_as_missing"] is False
    assert config["expect"]["missing_features"] == 968
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "train_missing.json")) as f:
        traffic = json.load(f)
    assert traffic["driver"] == "missing_train_loop"
    assert traffic["test_rows"] == 200_000


def test_the_booster_and_the_trees_count_themselves(first_tree):
    """`missing_features` where the plan is fixed (numerical columns with
    a NaN bin), `missing_right_splits_total` where a packed tree is
    decoded (its numerical splits on them that send NaN right)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability import global_registry
    tree, _, booster = first_tree
    g = booster._gbdt
    before = dict(global_registry.snapshot()["counters"])
    booster.update()
    g._sync_model()
    after = dict(global_registry.snapshot()["counters"])
    t = g.models_[-1]
    assert (after["missing_right_splits_total"]
            - before["missing_right_splits_total"]
            == missing_train_loop.nan_sides([t])[1])
    # a booster counts its NaN-binned columns at init, none where it has
    # none
    X = np.random.RandomState(0).rand(200, 3).astype(np.float32)
    X[::7, 1] = np.nan
    y = (X[:, 0] > 0.5).astype(np.float32)
    for data, added in ((X, 1), (np.nan_to_num(X), 0)):
        start = global_registry.counter("missing_features")
        lgb.Booster(PARAMS, lgb.Dataset(data, label=y, params=PARAMS))
        assert global_registry.counter("missing_features") - start == added
