"""The ranking gradient programs move no document by its own index (PR 35).

`lightgbm_tpu/ranking.py` reads a bucket's scores as whole 128-wide rows
of the score vector and adds its results back the same way, and lets the
scores, gains and labels ride a sort; `tools/rank_index_map_reference.py`
keeps the formulation that was replaced, one index a document.  The two
hold the same arithmetic in the same order, so they have to agree in
every bit.

How the bits are compared.  Two XLA CPU programs with the same
arithmetic behind different data movement are fused, and then vectorised
by LLVM, differently, and a vectorised `exp` or a contracted multiply-add
differs from the scalar one in the last bit (read here: 824 of 2,048
gradients off by one ulp under the default compile).  So the whole
programs are compared compiled with the backend's optimiser off
(`xla_backend_optimization_level` 0: the same HLO, every op compiled the
plain way in both), where any difference is a wrong element; the pieces
that only move data (`_read_block`, `_add_block`, the two sorts) are held
to `array_equal` under the default compile too, and one case holds the
default-compiled whole to 2e-6 of each value.  On the chip `tools/kernel_checks.py`
compares the default compile.
"""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from lightgbm_tpu import ranking   # noqa: E402
from lightgbm_tpu.config import Config   # noqa: E402
from lightgbm_tpu.metric import bucket_queries   # noqa: E402
from lightgbm_tpu.observability import global_registry   # noqa: E402
from lightgbm_tpu.ranking import LambdarankNDCG, RankXENDCG   # noqa: E402
from tools import rank_index_map_reference as reference   # noqa: E402

PLAIN = {"xla_backend_optimization_level": 0}
LENGTHS = [1, 2, 7, 8, 9, 127, 128, 129, 1251]
OFFSETS = [0, 1, 127]


def _objective(cls, y, lengths, weight=None, position=None, **params):
    obj = cls(Config({"objective": cls.name, **params}))
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    obj.init(SimpleNamespace(label=y, weight=weight, position=position,
                             init_score=None, query_boundaries=bounds),
             len(y))
    return obj


def _draw(lengths, n_pad, seed, tie=0.1):
    """Grades 0-4 and float32 scores rounded to `tie` (so many tie), the
    padding's scores drawn too: nothing may read them."""
    rng = np.random.default_rng(seed)
    y = rng.choice(5, size=sum(lengths)).astype(np.float32)
    scores = (np.round(rng.normal(size=n_pad) / tie) * tie).astype(np.float32)
    return y, jnp.asarray(scores)[None, :]


def _compiled(fn, *args):
    """`fn(*args)` as one program compiled the plain way."""
    return jax.jit(fn).lower(*args).compile(compiler_options=PLAIN)(*args)


def _both(obj, n_pad, scores, weight=None):
    """(the program's (g, h), the reference's) of an objective without
    positions, both compiled the plain way."""
    new = obj.make_device_grad_fn(n_pad)
    if isinstance(obj, RankXENDCG):
        old = reference.xendcg(obj, n_pad)
        want = _compiled(lambda s, w: old(s, w, jnp.int32(0)), scores, weight)
    else:
        old = reference.lambdarank(obj, n_pad)
        want = _compiled(lambda s, w: old(s, w, jnp.zeros(1))[:2],
                         scores, weight)
    return _compiled(new, scores, weight), want


def _assert_equal(got, want, live_rows):
    for name, have, ref in zip(("gradients", "hessians"), got, want):
        np.testing.assert_array_equal(np.asarray(have), np.asarray(ref),
                                      err_msg=name)
    assert np.abs(np.asarray(got[0])[0, :live_rows]).sum() > 0


# ----------------------------------------------- the whole programs' bits
@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("length", LENGTHS)
def test_lambdarank_equals_the_index_map_reference(length, offset):
    """A query of every awkward length at every awkward start modulo 128,
    queries before and after it, tied scores."""
    lengths = ([offset] if offset else []) + [length, 40, 3]
    n = sum(lengths)
    n_pad = -(-n // 1024) * 1024
    y, scores = _draw(lengths, n_pad, seed=1000 * length + offset)
    got, want = _both(_objective(LambdarankNDCG, y, lengths), n_pad, scores)
    _assert_equal(got, want, n)
    assert not np.asarray(got[0])[0, n:].any()


MIXED = [5, 130, 1, 64, 257, 2, 33, 20]              # 512 rows, 5 buckets
# name -> (objective class, lengths, n_pad, parameters, weighted, tie)
CASES = {
    # the last query ends at the last row of the vector: its window's
    # second row is past the end
    "last_query_ends_at_last_row": (LambdarankNDCG, MIXED, 512, {}, False, .1),
    # the same where the vector is no whole number of 128-wide rows
    "vector_of_no_whole_rows": (LambdarankNDCG, MIXED[:-1] + [7], 499, {},
                                False, .1),
    "tied_scores": (LambdarankNDCG, MIXED, 1024, {}, False, 1e9),
    "weights": (LambdarankNDCG, MIXED, 1024, {}, True, .1),
    "norm_off": (LambdarankNDCG, MIXED, 1024, {"lambdarank_norm": False},
                 False, .1),
    "truncation_3": (LambdarankNDCG, MIXED, 1024,
                     {"lambdarank_truncation_level": 3}, False, .1),
    "label_gain_not_injective": (LambdarankNDCG, MIXED, 1024,
                                 {"label_gain": [0, 1, 1, 7, 7]}, False, .1),
    "rank_xendcg": (RankXENDCG, MIXED, 1024, {}, False, .1),
    "rank_xendcg_weights_at_last_row": (RankXENDCG, MIXED, 512, {}, True, .1),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_named_case_equals_the_index_map_reference(name):
    cls, lengths, n_pad, params, weighted, tie = CASES[name]
    n = sum(lengths)
    y, scores = _draw(lengths, n_pad, seed=len(name), tie=tie)
    weight = None
    if weighted:
        weight = jnp.asarray(np.random.default_rng(3).uniform(
            0.5, 2.0, n_pad).astype(np.float32))
    got, want = _both(_objective(cls, y, lengths, **params), n_pad, scores,
                      weight)
    _assert_equal(got, want, n)


def test_all_one_grade_query_gets_zeros_and_its_neighbours_the_same():
    lengths = [50, 9, 70]
    y, scores = _draw(lengths, 1024, seed=9)
    y[50:59] = 2.0
    got, want = _both(_objective(LambdarankNDCG, y, lengths), 1024, scores)
    _assert_equal(got, want, 129)
    assert not np.asarray(got[0])[0, 50:59].any()
    assert not np.asarray(got[1])[0, 50:59].any()


def test_lambdarank_with_positions_threads_the_same_biases():
    """Two steps: the second reads the biases the first left."""
    lengths = [5, 130, 1, 64, 257, 2, 33, 20]
    n, n_pad = sum(lengths), 1024
    y, scores = _draw(lengths, n_pad, seed=77)
    position = np.concatenate([np.arange(k) % 10 for k in lengths])
    obj = _objective(LambdarankNDCG, y, lengths, position=position)
    new = obj.make_device_grad_fn(n_pad)
    old = reference.lambdarank(obj, n_pad)

    def step_new(s, biases):
        obj._pos_biases_dev = biases
        g, h = new(s, None)
        return g, h, obj._pos_biases_dev

    biases = [jnp.zeros(10, jnp.float32)] * 2
    for it in range(2):
        got = _compiled(step_new, scores, biases[0])
        want = _compiled(lambda s, b: old(s, None, b), scores, biases[1])
        _assert_equal(got[:2], want[:2], n)
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
        biases = [got[2], want[2]]
    assert np.asarray(biases[0]).any()
    obj.pos_biases = np.zeros(10)          # no tracer left on the objective


def test_default_compile_differs_in_the_last_bits_alone():
    """What the optimiser may do to the same arithmetic."""
    lengths = [5, 130, 1, 64, 257, 2, 33, 20, 1251]
    n_pad = 2048
    y, scores = _draw(lengths, n_pad, seed=5)
    obj = _objective(LambdarankNDCG, y, lengths)
    got = obj.make_device_grad_fn(n_pad)(scores, None)
    want = reference.lambdarank(obj, n_pad)(scores, None, jnp.zeros(1))
    for have, ref in zip(got, want):
        np.testing.assert_allclose(np.asarray(have), np.asarray(ref),
                                   rtol=2e-6, atol=1e-7)


# --------------------------------------- the pieces that only move data
@pytest.fixture(scope="module")
def plan():
    """A plan over all widths of window (W 2, 3, 11), queries that share
    a view row, and a last query that ends at the vector's last row."""
    lengths = [1, 2, 7, 130, 128, 1251, 9, 64, 127, 129, 40, 72]
    n = sum(lengths)
    assert n % ranking.LANES                          # n_pad == n, no whole rows
    y = np.zeros(n, np.float32)
    obj = _objective(LambdarankNDCG, y, lengths)
    return n, obj._plan_buckets(n, obj.label_gain)


def test_read_block_is_the_take_of_the_index_map(plan):
    n, buckets = plan
    vec = jnp.asarray(np.random.default_rng(0).normal(size=n)
                      .astype(np.float32))
    read = jax.jit(ranking._read_block, static_argnums=3)
    for b in buckets:
        got = read(ranking._as_rows(vec), jnp.asarray(b["rows"]),
                   jnp.asarray(b["shift"]), b["m"])
        want = jnp.take(vec, jnp.asarray(b["idx"]))
        np.testing.assert_array_equal(np.asarray(got)[b["val"]],
                                      np.asarray(want)[b["val"]])


def test_add_block_is_the_scatter_add_of_the_index_map(plan):
    n, buckets = plan
    n_rows = -(-n // ranking.LANES)
    rng = np.random.default_rng(1)
    got = jnp.zeros((n_rows, 2, ranking.LANES), jnp.float32)
    want = jnp.zeros((2, n), jnp.float32)
    add = jax.jit(ranking._add_block)
    for b in buckets:
        block = np.where(b["val"][:, None, :], rng.normal(
            size=(len(b["qs"]), 2, b["m"])), 0.0).astype(np.float32)
        got = add(got, jnp.asarray(block), jnp.asarray(b["rows"]),
                  jnp.asarray(b["shift"]))
        want = want.at[:, b["idx"].reshape(-1)].add(
            jnp.asarray(block).transpose(1, 0, 2).reshape(2, -1))
    got = np.asarray(got).transpose(1, 0, 2).reshape(2, -1)
    assert np.asarray(want).all(axis=0).sum() == n       # every row written
    np.testing.assert_array_equal(got[:, :n], np.asarray(want))
    assert not got[:, n:].any()


def test_sort_payloads_are_the_take_along_axis_of_the_argsort():
    """`bucket_lambdas`' two sorts against the argsort and gathers they
    replaced, on tied scores and padded slots."""
    rng = np.random.default_rng(2)
    Qb, m = 37, 64
    cnt = rng.integers(1, m + 1, Qb)
    cnt[:2] = (1, m)
    val = np.arange(m)[None, :] < cnt[:, None]
    sc = np.round(rng.normal(size=(Qb, m)), 1).astype(np.float32)
    lab = rng.integers(0, 5, (Qb, m)).astype(np.int32)

    @jax.jit
    def both(sc, lab, val):
        key = jnp.where(val, sc, -jnp.inf)
        neg, order, sl = jax.lax.sort(
            (-key, jax.lax.broadcasted_iota(jnp.int32, key.shape, 1), lab),
            dimension=1, is_stable=False, num_keys=2)
        sv = jnp.arange(m)[None, :] < jnp.sum(val, axis=1)[:, None]
        ssz = jnp.where(sv, -neg, 0.0)
        worst = jnp.min(jnp.where(val, sc, jnp.inf), axis=1)
        back = jax.lax.sort((order, ssz), dimension=1, is_stable=False,
                            num_keys=1)[1]
        new = (order, sl, sv, ssz, worst, back)
        o = jnp.argsort(-key, axis=1, stable=True)
        o_sv = jnp.take_along_axis(val, o, 1)
        o_ssz = jnp.where(o_sv, jnp.take_along_axis(sc, o, 1), 0.0)
        o_worst = jnp.take_along_axis(
            o_ssz, jnp.maximum(jnp.sum(o_sv, axis=1) - 1, 0)[:, None], 1)[:, 0]
        o_back = jnp.take_along_axis(o_ssz, jnp.argsort(o, axis=1), 1)
        return new, (o, jnp.take_along_axis(lab, o, 1), o_sv, o_ssz, o_worst,
                     o_back)

    for have, want in zip(*both(sc, lab, val)):
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want))


# ------------------------------------------------------- the maps are gone
def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _index_entries(fn, *args):
    """{primitive: [index vectors of each gather / scatter of `fn`]}."""
    found = {}
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            idx = eqn.invars[1].aval.shape
            found.setdefault(name, []).append(int(np.prod(idx[:-1])))
    return found


def test_no_gather_or_scatter_holds_an_index_a_document():
    lengths = [1, 9, 16, 40, 3, 200, 130, 64, 700]
    n_pad = 2048
    y, scores = _draw(lengths, n_pad, seed=8)
    obj = _objective(LambdarankNDCG, y, lengths)
    before = global_registry.counter("rank_window_rows")
    new = obj.make_device_grad_fn(n_pad)
    window_rows = global_registry.counter("rank_window_rows") - before
    # 8, 16 and 64 read 2 rows a query, 256 reads 3, 1024 reads 9
    assert window_rows == 2 * 6 + 3 * 2 + 9 * 1
    padded = sum(b["idx"].size for b in bucket_queries(obj.query_boundaries,
                                                       n_pad))
    assert padded == 8 * 2 + 16 * 2 + 64 * 2 + 256 * 2 + 1024
    most = window_rows + len(lengths)
    got = _index_entries(lambda s: new(s, None), scores)
    assert got and set(got) <= {"gather", "scatter-add", "scatter_add"}
    assert all(k <= most for ks in got.values() for k in ks), got
    # the reading is not blind: the reference holds an index a document
    old = reference.lambdarank(obj, n_pad)
    ref = _index_entries(lambda s: old(s, None, jnp.zeros(1)), scores)
    assert max(ref["gather"]) >= 1024 and max(ref["scatter-add"]) >= 1024

    xe = _objective(RankXENDCG, y, lengths)
    got = _index_entries(lambda s: xe.make_device_grad_fn(n_pad)(s, None),
                         scores)
    assert all(k <= most for ks in got.values() for k in ks), got


# ------------------------------------- the eval plans that share the plan
@pytest.mark.parametrize("metric_name", ["ndcg", "map"])
def test_eval_plans_share_what_the_objective_still_holds(metric_name):
    """`metric.ndcg_device_plan` / `map_device_plan` took `idx` and `val`
    from the objective's device buckets; it holds no `idx` any more, so
    they share `val`, upload `idx`, and evaluate as the host metric."""
    from lightgbm_tpu.metric import (MapMetric, NDCGMetric, map_device_plan,
                                     ndcg_device_plan)
    lengths = [5, 130, 1, 64, 257, 2, 33, 20]
    n = sum(lengths)
    y, scores = _draw(lengths, n, seed=21)
    obj = _objective(LambdarankNDCG, y, lengths)
    obj.make_device_grad_fn(n)
    assert all("idx" not in bk for bk in obj._dev_buckets)
    cls, plan_of = {"ndcg": (NDCGMetric, ndcg_device_plan),
                    "map": (MapMetric, map_device_plan)}[metric_name]
    metric = cls(Config({"objective": "lambdarank", "eval_at": [1, 10]}))
    metric.init(SimpleNamespace(label=y, weight=None,
                                query_boundaries=obj.query_boundaries), n)
    buckets, eval_fn = plan_of(metric, n, shared_buckets=obj._dev_buckets)
    for bk, shared, host in zip(buckets, obj._dev_buckets,
                                bucket_queries(obj.query_boundaries, n)):
        assert bk["val"] is shared["val"]
        np.testing.assert_array_equal(np.asarray(bk["idx"]), host["idx"])
    alone, _ = plan_of(metric, n)
    assert all(a["val"] is not b["val"] for a, b in zip(alone, buckets))
    got = np.asarray(eval_fn(scores[0], buckets))
    want = [v for _, v in metric.eval(np.asarray(scores[0], np.float64))]
    np.testing.assert_allclose(got, want, rtol=1e-5)
