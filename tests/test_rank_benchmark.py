"""The ranking cell of the benchmark (PR 34) on the CPU at small sizes:
the plain per-query reference against the device program and the host
path, the quality file against the program's NDCG, the generator, the
driver end to end through `run.execute(..., on_chip=False)` and its
refusal of a program whose ranking gradients carry no scope, the two
readers that split `GBDT::gradients` into its parts, the first-tree
check, and the plan's counters."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run, scope_trace, trace as bench_trace   # noqa: E402
from benchmarks.drivers import rank_train_loop   # noqa: E402
from benchmarks.generators import mslr_like   # noqa: E402
from benchmarks.quality import ndcg_at_10   # noqa: E402
from benchmarks.reducers import (scope_hbm_roofline_pct,   # noqa: E402
                                 scope_part_ms_per_iter)
from benchmarks.references import lambdarank_first_tree as ref   # noqa: E402
from test_bench_scope_readers import SHIFT_NS, write_xplane   # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
MSLR_ROWS, MSLR_FEATURES = 2270296, 137
# the lengths the issue names, an all-one-grade query (the 9), and every
# length past 31 is longer than the truncation level
LENGTHS = [1, 2, 9, 31, 129, 600, 1251, 9]


def _objective(y, lengths, **params):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ranking import LambdarankNDCG
    obj = LambdarankNDCG(Config({"objective": "lambdarank", **params}))
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    obj.init(SimpleNamespace(label=y, weight=None, position=None,
                             init_score=None, query_boundaries=bounds),
             len(y))
    return obj


@pytest.fixture(scope="module")
def queries():
    """(y, float32 scores rounded to one decimal so that many tie)."""
    rng = np.random.default_rng(34)
    n = sum(LENGTHS)
    y = rng.choice(5, size=n, p=[.52, .32, .13, .02, .01]).astype(np.float32)
    y[-9:] = 2.0                                   # all one grade
    scores = np.round(rng.normal(size=n), 1).astype(np.float32)
    return y, scores


# ------------------------------------- (a) reference / device / host path
@pytest.mark.parametrize("norm", [True, False])
def test_reference_agrees_with_the_host_path(queries, norm):
    y, scores = queries
    obj = _objective(y, LENGTHS, lambdarank_norm=norm)
    got = obj.get_gradients_host(scores.astype(np.float64))
    ends = np.cumsum(LENGTHS)
    for q, (a, b) in enumerate(zip(ends - LENGTHS, ends)):
        want = ref.query_gradients(y[a:b], scores[a:b], norm=norm)
        for have, w in zip(got, want):
            np.testing.assert_allclose(have[a:b], w, rtol=1e-5, atol=1e-7,
                                       err_msg=f"query {q}")
    assert not got[0][-9:].any() and not got[1][-9:].any()
    assert not got[0][0] and not got[1][0]          # the query of one


@pytest.mark.parametrize("scores_at", ["zero", "live"])
def test_device_program_agrees_with_the_reference(queries, scores_at):
    import jax.numpy as jnp
    y, scores = queries
    if scores_at == "zero":
        scores = np.zeros_like(scores)
    n = len(y)
    obj = _objective(y, LENGTHS)
    fn = obj.make_device_grad_fn(n)
    grad, hess = fn(jnp.asarray(scores)[None, :], None)
    want = ref.gradients(y, scores, LENGTHS)
    for have, w in zip((grad, hess), want):
        np.testing.assert_allclose(np.asarray(have)[0], w,
                                   rtol=rank_train_loop.GRAD_RTOL,
                                   atol=rank_train_loop.GRAD_ATOL)
    got = np.stack([np.asarray(grad)[0], np.asarray(hess)[0]])
    assert rank_train_loop._grad_error(got, np.stack(want)) <= 1.0
    # what the driver's limit is for: lower precision, a dropped branch
    low = np.stack(ref.gradients(y, rank_train_loop._bf16(scores), LENGTHS))
    if scores_at == "live":
        assert rank_train_loop._grad_error(low, np.stack(want)) > 1.0
        ends = np.cumsum(LENGTHS)
        no_norm = np.stack([np.concatenate(part) for part in zip(*(
            ref.query_gradients(y[a:b], scores[a:b], norm=False)
            for a, b in zip(ends - LENGTHS, ends)))])
        assert rank_train_loop._grad_error(no_norm, np.stack(want)) > 1.0


def test_bf16_rounding_is_to_nearest_even():
    import jax.numpy as jnp
    x = np.random.default_rng(0).normal(size=4096).astype(np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(rank_train_loop._bf16(x), want)


# --------------------------------------------------- (b) the quality file
@pytest.mark.parametrize("seed", [0, 1])
def test_ndcg_at_10_is_the_programs_ndcg(seed):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metric import NDCGMetric
    rng = np.random.default_rng(seed)
    group = np.array([1, 2, 5, 10, 11, 40, 7, 3])
    y = rng.choice(5, size=group.sum()).astype(np.float32)
    y[3:8] = 0.0                        # a query with no relevant document
    scores = np.round(rng.normal(size=len(y)), 1)
    metric = NDCGMetric(Config({"objective": "lambdarank", "eval_at": [10]}))
    metric.init(SimpleNamespace(
        label=y, weight=None,
        query_boundaries=np.concatenate([[0], np.cumsum(group)])), len(y))
    (_, want), = metric.eval(scores)
    assert ndcg_at_10.score(y, scores, group) == pytest.approx(want,
                                                               rel=1e-12)
    with pytest.raises(ValueError):
        ndcg_at_10.score(y, scores, group[:-1])


# ------------------------------------------------------ (c) the generator
@pytest.fixture(scope="module")
def published_groups():
    return mslr_like.groups(MSLR_ROWS, 34)


def test_groups_sum_to_the_rows_and_keep_the_real_sets_range(
        published_groups):
    g = published_groups
    assert len(g) == 18919 and int(g.sum()) == MSLR_ROWS
    assert g.min() == 1 and g.max() == 1251
    assert (g == 1).any() and (g == 2).any() and (g > 1024).any()
    assert abs(g.mean() - 120.0) < 0.01
    np.testing.assert_array_equal(g, mslr_like.groups(MSLR_ROWS, 34))
    assert not np.array_equal(g, mslr_like.groups(MSLR_ROWS, 35))


@pytest.mark.parametrize("rows", [1, 119, 4096, 100000])
def test_groups_fit_any_row_count(rows):
    g = mslr_like.groups(rows, 35)
    assert int(g.sum()) == rows and g.min() >= 1 and g.max() <= 1251


def test_published_buckets_are_the_issues(published_groups):
    m = np.maximum(8, 1 << np.ceil(np.log2(published_groups)).astype(int))
    assert sorted(set(m.tolist())) == [8, 16, 32, 64, 128, 256, 512, 1024,
                                       2048]
    assert 3.0e6 < int(m.sum()) < 3.5e6             # padded documents


def test_same_seed_same_bytes():
    X, y = mslr_like.make(20000, MSLR_FEATURES, 34)
    X2, y2 = mslr_like.make(20000, MSLR_FEATURES, 34)
    assert X.dtype == np.float32 and X.shape == (20000, MSLR_FEATURES)
    assert X.tobytes() == X2.tobytes() and y.tobytes() == y2.tobytes()
    X3, y3 = mslr_like.make(20000, MSLR_FEATURES, 35)
    assert X.tobytes() != X3.tobytes() and y.tobytes() != y3.tobytes()


def test_label_shares_and_column_kinds():
    rows = 480000                       # 4,000 queries: a fifth of the set
    X, y = mslr_like.make(rows, MSLR_FEATURES, 35)
    assert np.isfinite(X).all()
    shares = np.bincount(y.astype(int), minlength=5) / len(y)
    np.testing.assert_allclose(shares, [.52, .32, .13, .02, .01], atol=0.02)
    ints = X[:, :MSLR_FEATURES // 3]
    assert (ints == np.floor(ints)).all() and len(np.unique(ints[:, 0])) <= 4
    assert len(np.unique(X[:, -1])) > rows // 2
    # the label mix varies by query, and some queries are all one grade
    ends = np.cumsum(mslr_like.groups(rows, 35))
    share = np.array([float((y[a:b] > 0).mean())
                      for a, b in zip(ends[:-1], ends[1:])])
    assert np.std(share) > 0.15
    assert ((share == 0) | (share == 1)).any()


def test_seed_permutes_columns_and_leaves_labels_and_groups_alone():
    from benchmarks import data as bench_data
    config = {"generator": "mslr_like", "data_seed": 34, "rows": 3000,
              "features": MSLR_FEATURES}
    Xa, ya = bench_data.make(config, 1)
    Xb, yb = bench_data.make(config, 2 ** 31 + 11)
    np.testing.assert_array_equal(ya, yb)
    assert not np.array_equal(Xa, Xb)
    inverse_a = np.argsort(bench_data.column_order(config, 1))
    inverse_b = np.argsort(bench_data.column_order(config, 2 ** 31 + 11))
    np.testing.assert_array_equal(Xa[:, inverse_a], Xb[:, inverse_b])


# ---------------------------------------- (d) the driver, end to end, tiny
TINY_TRAFFIC = {"driver": "rank_train_loop", "warmup_iters": 2,
                "quality_trees": 6, "test_rows": 2400, "traced_iters": 3,
                "grad_check_queries": 8}
TINY_CONFIG = {"generator": "mslr_like", "data_seed": 3, "rows": 6000,
               "features": 24, "reference": "lambdarank_first_tree",
               "params": {"objective": "lambdarank", "num_leaves": 15,
                          "max_bin": 63, "learning_rate": 0.1,
                          "min_data_in_leaf": 20, "verbosity": -1},
               "quality": {"metric": "ndcg_at_10", "floor": 0.3},
               "expect": {"gradients": "device"}}
CELL_CHECKS = {"no_recompile_in_window", "first_tree_sums_its_rows",
                "train_scores_finite", "heldout_scores_finite",
                "quality_at_or_over_floor", "gradients_on_device",
                "gradients_match_reference_at_end"}


@pytest.mark.parametrize("trace", [False, True])
def test_rank_train_loop_end_to_end(trace, tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = {"name": "mslr-2270k-b63.train_rank", "chips": 1}
    facts = {}
    res = run.execute(manifest, cell, TINY_CONFIG, TINY_TRAFFIC,
                      seed=2 ** 31 + 11, seconds=0.3, trace=trace,
                      devices=jax.devices()[:1], on_chip=False,
                      log=lambda **kw: facts.update(kw))
    assert set(facts["checks"]) == CELL_CHECKS
    assert res["correct"] is True, facts["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert facts["queries"] == 50 and facts["grad_err"] <= 1.0
    assert facts["first_tree_counts_equal"] is True
    if trace:
        assert {"construct_s", "first_iter_s", "rank_pad_ratio",
                "rank_plan_s"} <= set(res["metrics"])
        assert res["metrics"]["rank_pad_ratio"]["value"] > 1.0
    else:
        assert set(res["metrics"]) == {"setup_s", "iter_ms",
                                       "heldout_quality"}
        assert 0.3 <= res["metrics"]["heldout_quality"]["value"] <= 1.0


# ------------------- (d2) the driver's refusal of an unscoped gradient program
def test_gradient_scope_probe_passes_this_program():
    """The child process the driver asks on the chip, here on the CPU:
    this program's ranking gradients carry GBDT::gradients, so nothing
    is refused."""
    facts = {}
    rank_train_loop.require_gradient_scope(
        rank_train_loop.start_gradient_scope_probe(),
        lambda **kw: facts.update(kw))
    assert facts["asked"] is True and facts["scoped"] is True


class _Probe:
    """A child that has said `out`."""

    def __init__(self, out):
        self.out, self.killed = out, False

    def communicate(self, timeout=None):
        return self.out, None

    def kill(self):
        self.killed = True

    def wait(self):
        return 1


def test_driver_refuses_an_unscoped_gradient_program(monkeypatch):
    """A program whose gradient program carries no device scope (a tree
    before PR 34: `device_scope` patched to nothing) reads unscoped, and
    the driver exits on that answer."""
    from contextlib import contextmanager
    from lightgbm_tpu.utils import timer

    @contextmanager
    def no_scope(self, name):
        yield
    assert rank_train_loop.gradient_scope_in_program() is True
    monkeypatch.setattr(timer.Timer, "device_scope", no_scope)
    assert rank_train_loop.gradient_scope_in_program() is False
    facts = {}
    with pytest.raises(SystemExit, match="GBDT::gradients"):
        rank_train_loop.require_gradient_scope(
            _Probe('{"gradient_scope": false}\n'),
            lambda **kw: facts.update(kw))
    assert facts["scoped"] is False


@pytest.mark.parametrize("out", ["", "Traceback\n", '{"other": 1}\n'])
def test_a_probe_that_cannot_be_asked_refuses_nothing(out):
    facts = {}
    probe = _Probe(out)
    rank_train_loop.require_gradient_scope(probe,
                                           lambda **kw: facts.update(kw))
    assert facts["asked"] is False and probe.killed


# ------------------------------------------- (e) the two new trace readers
HAND_TRACE = {
    "window_ns": [0, 10_000_000],
    "devices": {"/device:TPU:0": [
        # name, start, duration, op_name
        ["%fusion.1 = f32[64]", 0, 1_000_000,
         "jit(grad_fn)/GBDT.gradients/Rank.gather/jit(_take)/gather:"],
        ["%sort.2 = s32[8,8]", 1_000_000, 2_000_000,
         "jit(grad_fn)/GBDT.gradients/Rank.sort/jit(argsort)/sort:"],
        ["%fusion.3 = f32[8,8]", 3_000_000, 500_000,
         "jit(grad_fn)/GBDT.gradients/Rank.sort/jit(take_along_axis)/gather:"],
        ["%fusion.4 = f32[8,7,8]", 3_500_000, 250_000,
         "jit(grad_fn)/GBDT.gradients/Rank.pairs/reduce_sum:"],
        ["%fusion.5 = f32[64]", 3_750_000, 1_250_000,
         "jit(grad_fn)/GBDT.gradients/Rank.scatter/scatter-add:"],
        ["%fusion.6 = f32[64]", 5_000_000, 100_000,
         "jit(grad_fn)/GBDT.gradients/mul:"],            # carries no part
        ["%fusion.7 = f32[64]", 5_100_000, 700_000,
         "jit(f)/Tree.partition/Rank.sortish/select_n:"],  # another scope
        ["%fusion.8 = f32[64]", 5_800_000, 300_000,
         "jit(f)/Rank.sort/add:"],                       # no scope at all
    ]},
    "host_spans": [["bench::update", 0, 10_000_000, {}]],
}
PARTS_MS = {"Rank.gather": 0.5, "Rank.sort": 1.25, "Rank.pairs": 0.125,
            "Rank.scatter": 0.625}


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
        trace=tr, spans={}, peaks={"hbm_bytes_per_s": 800e9},
        counters={"iterations": 2, "rows_local": 1_000_000, "features": 137})


@pytest.mark.parametrize("part", sorted(PARTS_MS))
def test_scope_part_reads_the_hand_counts(hand_ctx, part):
    assert scope_part_ms_per_iter.reduce(
        hand_ctx, "GBDT.gradients", part) == pytest.approx(PARTS_MS[part])
    assert scope_part_ms_per_iter.reduce(
        hand_ctx, "GBDT.gradients", part,
        skip=[r"^%(fusion|sort)"]) is None
    assert scope_part_ms_per_iter.reduce(
        hand_ctx, "GBDT.gradients", "Rank.nothing") is None


def test_parts_add_up_to_the_scope_less_what_carries_none(hand_ctx):
    entries = [{"name": n, "unit": "-"} for n in
               ("rank_gather_ms", "rank_sort_ms", "rank_pairs_ms",
                "rank_scatter_ms", "gradients_ms", "rank_grad_roofline")]
    got = {k: v["value"] for k, v in
           run.layer_metrics(entries, {}, hand_ctx).items()}
    assert got["gradients_ms"] == pytest.approx(2.55)
    assert sum(v for k, v in got.items() if k.endswith("_ms")
               and k != "gradients_ms") == pytest.approx(2.55 - 0.05)
    # 2 iterations x 16 MB at 800 GB/s = 40 us, over 5.1 ms under the scope
    assert got["rank_grad_roofline"] == pytest.approx(100 * 40e-6 / 5.1e-3)
    assert scope_hbm_roofline_pct.reduce(
        hand_ctx, "Tree.nothing", "rank_grad_bytes") is None


def test_new_readers_find_nothing_on_the_recorded_trace(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(scope_trace, "OUT_DIR", str(tmp_path))
    scope_trace._cache.clear()
    tr = bench_trace.from_json(os.path.join(TESTDATA, "trace_small.json"))
    ctx = SimpleNamespace(trace=tr, spans={}, peaks={"hbm_bytes_per_s": 1.0},
                          counters={"iterations": 1, "rows_local": 1,
                                    "features": 1})
    assert scope_part_ms_per_iter.reduce(ctx, "GBDT.gradients",
                                         "Rank.sort") is None
    assert scope_hbm_roofline_pct.reduce(ctx, "GBDT.gradients",
                                         "rank_grad_bytes") is None
    ctx.trace = None
    assert scope_part_ms_per_iter.reduce(ctx, "GBDT.gradients",
                                         "Rank.sort") is None


def test_manifest_gives_the_cell_its_seven_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["mslr-2270k-b63.train_rank"]]
    assert [m["name"] for m in mine] == [
        "rank_gather_ms", "rank_sort_ms", "rank_pairs_ms",
        "rank_scatter_ms", "rank_grad_roofline", "rank_pad_ratio",
        "rank_plan_s"]
    assert {m["layer"] for m in mine} == {"objective"}
    at = manifest["per_layer"].index(mine[0])     # entries are appended,
    assert manifest["per_layer"][at:at + 7] == mine    # so later PRs' follow
    assert [w["name"] for w in manifest["workloads"]].count(
        "mslr-2270k-b63.train_rank") == 1
    entry = [c for c in manifest["configs"]
             if c["name"] == "mslr-2270k-b63"][0]
    assert entry["reduced"] == []
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert (config["rows"], config["features"], config["queries"]) == (
        MSLR_ROWS, MSLR_FEATURES, 18919)
    assert config["params"]["label_gain"] == [2 ** g - 1 for g in range(5)]


# ------------------------------------------------- (f) the first-tree check
@pytest.fixture(scope="module")
def first_tree():
    rng = np.random.default_rng(5)
    group = np.array([40, 3, 77, 1, 9, 130, 55])
    y = rng.choice(5, size=group.sum(), p=[.5, .3, .15, .03, .02])
    leaf = rng.integers(0, 6, size=len(y))
    lam, hes = ref.gradients(y, np.zeros(len(y)), group)
    return SimpleNamespace(y=y, group=group, leaf=leaf, lam=lam, hes=hes)


def _tree(leaf, lam, hes, lr=0.1, nl=6):
    sum_g = np.bincount(leaf, weights=lam, minlength=nl)
    sum_h = np.bincount(leaf, weights=hes, minlength=nl)
    return SimpleNamespace(num_leaves=nl, leaf_count=np.bincount(leaf,
                                                                 minlength=nl),
                           leaf_weight=sum_h, leaf_value=-lr * sum_g / sum_h)


@pytest.mark.parametrize("fault", ["none", "bf16", "weight", "value",
                                   "count", "unsorted"])
def test_first_tree_check(first_tree, fault):
    t = first_tree
    tree = _tree(t.leaf, t.lam, t.hes)
    if fault == "bf16":       # what the kernels' operand rounding does
        tree = _tree(t.leaf, rank_train_loop._bf16(t.lam).astype(float),
                     rank_train_loop._bf16(t.hes).astype(float))
    elif fault == "weight":
        tree.leaf_weight[2] *= 1.02
    elif fault == "value":
        tree.leaf_value[4] *= 0.98
    elif fault == "count":
        tree.leaf_count[1] += 1
    elif fault == "unsorted":
        # gradients of each query's documents taken in another order
        # than score order (all scores 0: row order)
        lam, hes = np.zeros(len(t.y)), np.zeros(len(t.y))
        ends = np.cumsum(t.group)
        for a, b in zip(ends - t.group, ends):
            flip = np.arange(b - a)[::-1]
            lam[a:b][flip], hes[a:b][flip] = ref.query_gradients(
                t.y[a:b][flip], np.zeros(b - a))
        tree = _tree(t.leaf, lam, hes)
    ok, facts = ref.check(tree, t.leaf, t.y, t.group, 0.1)
    assert ok is (fault in ("none", "bf16")), facts
    assert facts["first_tree_leaves_checked"] == 6


# --------------------------------------------------- (g) the plan's counters
def test_plan_counters_equal_the_hand_counts():
    from lightgbm_tpu.observability import global_registry
    from lightgbm_tpu.utils.timer import global_timer
    lengths = [1, 9, 16, 40, 3]      # padded to 8, 16, 16, 64, 8
    y = np.random.default_rng(1).choice(5, size=sum(lengths)).astype(
        np.float32)
    names = ("rank_queries", "rank_docs", "rank_padded_docs", "rank_pairs",
             "rank_buckets", "rank_window_rows")
    before = {n: global_registry.counter(n) for n in names}
    spans = global_timer.snapshot()
    _objective(y, lengths).make_device_grad_fn(sum(lengths))
    got = {n: global_registry.counter(n) - before[n] for n in names}
    assert got == {
        "rank_queries": 5, "rank_docs": 69, "rank_buckets": 3,
        "rank_padded_docs": 2 * 8 + 2 * 16 + 64,
        # ceil(m / 128) + 1 rows of the score vector's 128-wide view a query
        "rank_window_rows": 5 * 2,
        # [Qb, min(30, m - 1), m] a bucket
        "rank_pairs": 2 * 7 * 8 + 2 * 15 * 16 + 1 * 30 * 64}
    after = global_timer.snapshot()
    for span in ("Rank::init", "Rank::plan"):
        assert after[span][1] == spans.get(span, (0, 0))[1] + 1
