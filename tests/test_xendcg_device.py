"""Device rank_xendcg gradients (ranking.py RankXENDCG.make_device_grad_fn;
ref: rank_objective.hpp:362, cuda_rank_objective.cu:385-624).

The device program's math must equal the host _one_query formulas given
the SAME per-query uniform draws; the RNG streams themselves differ by
design (fold_in vs numpy RandomState, documented deviation)."""

import numpy as np
import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata
from lightgbm_tpu.ranking import RankXENDCG


def _problem(seed=0, n_q=40):
    rng = np.random.RandomState(seed)
    lens = rng.randint(1, 40, n_q)
    n = int(lens.sum())
    labels = rng.randint(0, 5, n).astype(np.float64)
    score = rng.randn(n)
    return lens, n, labels, score


class _FixedRand:
    """RandomState stand-in feeding the device path's uniforms."""
    def __init__(self, u):
        self._u = u
    def random_sample(self, cnt):
        return np.asarray(self._u[:cnt], np.float64)


def test_device_xendcg_math_matches_host_given_same_uniforms():
    lens, n, labels, score = _problem()
    md = Metadata(n)
    md.set_label(labels)
    md.set_group(lens.astype(np.int64))
    obj = RankXENDCG(Config({"objective": "rank_xendcg",
                             "objective_seed": 11}))
    obj.init(md, n)
    n_pad = (n + 1023) // 1024 * 1024
    fn = obj.make_device_grad_fn(n_pad)
    sc = jnp.zeros((1, n_pad)).at[0, :n].set(jnp.asarray(score, jnp.float32))
    g, h = fn(sc, None)          # iteration 0 -> key fold_in(seed, 0)
    g = np.asarray(g)[0, :n]
    h = np.asarray(h)[0, :n]
    assert np.isfinite(g).all() and np.isfinite(h).all()

    # replicate the device draws per query and feed the HOST formulas
    key_it = jax.random.fold_in(jax.random.PRNGKey(11), 0)
    qb = obj.query_boundaries
    from lightgbm_tpu.metric import bucket_queries
    m_of = {}
    for b in bucket_queries(qb, n_pad):
        for q in b["qs"]:
            m_of[int(q)] = b["m"]
    g_ref = np.zeros(n)
    h_ref = np.zeros(n)
    for q in range(obj.num_queries):
        a, e = int(qb[q]), int(qb[q + 1])
        u = np.asarray(jax.random.uniform(
            jax.random.fold_in(key_it, q), (m_of[q],)), np.float64)
        obj.rands[q] = _FixedRand(u)
        # host math in float32 resolution to match the device program
        lq, hq = obj._one_query(q, labels[a:e],
                                score[a:e].astype(np.float32))
        g_ref[a:e], h_ref[a:e] = lq, hq
    np.testing.assert_allclose(g, g_ref, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(h, h_ref, rtol=2e-3, atol=2e-4)


def test_device_xendcg_zero_for_single_doc_queries():
    lens = np.array([1, 5, 1, 7])
    n = int(lens.sum())
    rng = np.random.RandomState(1)
    labels = rng.randint(0, 4, n).astype(np.float64)
    md = Metadata(n)
    md.set_label(labels)
    md.set_group(lens.astype(np.int64))
    obj = RankXENDCG(Config({"objective": "rank_xendcg"}))
    obj.init(md, n)
    n_pad = 1024
    fn = obj.make_device_grad_fn(n_pad)
    sc = jnp.zeros((1, n_pad)).at[0, :n].set(
        jnp.asarray(rng.randn(n), jnp.float32))
    g, h = fn(sc, None)
    g = np.asarray(g)[0]
    assert g[0] == 0.0 and g[6] == 0.0          # single-doc queries
    assert np.abs(g[1:6]).sum() > 0             # real queries move
    assert np.abs(g[n:]).sum() == 0             # padding untouched


def test_device_xendcg_deterministic_per_iteration():
    lens, n, labels, score = _problem(seed=3)
    md = Metadata(n)
    md.set_label(labels)
    md.set_group(lens.astype(np.int64))
    obj = RankXENDCG(Config({"objective": "rank_xendcg"}))
    obj.init(md, n)
    n_pad = (n + 1023) // 1024 * 1024
    sc = jnp.zeros((1, n_pad)).at[0, :n].set(jnp.asarray(score, jnp.float32))
    fn1 = obj.make_device_grad_fn(n_pad)
    g1, _ = fn1(sc, None)
    obj2 = RankXENDCG(Config({"objective": "rank_xendcg"}))
    obj2.init(md, n)
    fn2 = obj2.make_device_grad_fn(n_pad)
    g2, _ = fn2(sc, None)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    # successive iterations draw fresh uniforms
    g3, _ = fn1(sc, None)
    assert not np.array_equal(np.asarray(g1), np.asarray(g3))


def _seeded_ranking_set():
    """A seeded stand-in for the reference's rank.train (/root/reference
    is not mounted here): 150 queries of 5-40 documents, graded 0-4
    relevance that depends on the features plus noise."""
    rng = np.random.RandomState(11)
    group = rng.randint(5, 41, size=150)
    n = int(group.sum())
    X = rng.rand(n, 20)
    util = 2.5 * X[:, 0] + 1.5 * X[:, 1] * X[:, 2] - X[:, 3] + rng.randn(n) * 0.4
    label = np.clip(np.floor((util - util.min())
                             / (np.ptp(util) + 1e-9) * 5), 0, 4)
    return lgb.Dataset(X, label=label, group=group)


def test_xendcg_training_quality_matches_host():
    b_dev = lgb.train(
        {"objective": "rank_xendcg", "num_leaves": 15, "verbosity": -1,
         "learning_rate": 0.1, "metric": "ndcg", "eval_at": [3]},
        _seeded_ranking_set(), num_boost_round=10)
    assert getattr(b_dev._gbdt, "_ranking_dev_fn", None), \
        "device path not engaged"
    orig = RankXENDCG.make_device_grad_fn
    RankXENDCG.make_device_grad_fn = lambda self, n: None
    try:
        b_host = lgb.train(
            {"objective": "rank_xendcg", "num_leaves": 15,
             "verbosity": -1, "learning_rate": 0.1, "metric": "ndcg",
             "eval_at": [3]},
            _seeded_ranking_set(), num_boost_round=10)
    finally:
        RankXENDCG.make_device_grad_fn = orig
    # quality proxy: training NDCG via booster eval on the SAME data
    d = dict(b_dev._gbdt.eval_train())["ndcg@3"]
    h = dict(b_host._gbdt.eval_train())["ndcg@3"]
    assert abs(d - h) < 0.03, (d, h)
