"""Evaluation metrics (ref: src/metric/: regression_metric.hpp, binary_metric.hpp,
multiclass_metric.hpp, rank_metric.hpp, map_metric.hpp, xentropy_metric.hpp,
dcg_calculator.cpp; factory src/metric/metric.cpp:19).

Host-side NumPy implementations: metrics run once per `metric_freq` iterations
on scores pulled from device; pointwise transforms mirror the reference's use of
ObjectiveFunction::ConvertOutput.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .config import Config
from .utils import log


class Metric:
    """Base (ref: include/LightGBM/metric.h)."""

    name: str = ""
    is_higher_better = False

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = np.asarray(metadata.label, dtype=np.float64)
        self.weight = (None if metadata.weight is None
                       else np.asarray(metadata.weight, dtype=np.float64))
        self.sum_weights = (float(num_data) if self.weight is None
                            else float(self.weight.sum()))
        self.query_boundaries = metadata.query_boundaries

    def eval(self, score: np.ndarray, objective=None) -> List[Tuple[str, float]]:
        raise NotImplementedError

    def _convert(self, score, objective):
        # host metrics evaluate host-resident scores (valid sets, loaded
        # boosters): convert on host too — the old jnp round trip cost
        # one H2D + one D2H per (dataset, metric) every eval tick and
        # quietly downcast the float64 valid scores to f32
        # (docs/Performance.md host-boundary inventory)
        if objective is not None:
            return np.asarray(objective.convert_output_host(score))
        return score

    def _avg(self, pointwise: np.ndarray) -> float:
        if self.weight is None:
            return float(pointwise.sum() / self.sum_weights)
        return float((pointwise * self.weight).sum() / self.sum_weights)


# ------------------------------------------------------------------ regression
class _PointwiseRegression(Metric):
    def loss(self, label, score):
        raise NotImplementedError

    def eval(self, score, objective=None):
        conv = self._convert(score, objective)
        return [(self.name, self._avg(self.loss(self.label, conv)))]


class L2Metric(_PointwiseRegression):
    name = "l2"
    def loss(self, label, score):
        return (score - label) ** 2


class RMSEMetric(_PointwiseRegression):
    name = "rmse"
    def eval(self, score, objective=None):
        conv = self._convert(score, objective)
        return [(self.name, float(np.sqrt(self._avg((conv - self.label) ** 2))))]


class L1Metric(_PointwiseRegression):
    name = "l1"
    def loss(self, label, score):
        return np.abs(score - label)


class QuantileMetric(_PointwiseRegression):
    name = "quantile"
    def loss(self, label, score):
        alpha = self.config.alpha
        delta = label - score
        return np.where(delta < 0, (alpha - 1.0) * delta, alpha * delta)


class HuberMetric(_PointwiseRegression):
    name = "huber"
    def loss(self, label, score):
        a = self.config.alpha
        diff = np.abs(score - label)
        return np.where(diff <= a, 0.5 * diff * diff, a * (diff - 0.5 * a))


class FairMetric(_PointwiseRegression):
    name = "fair"
    def loss(self, label, score):
        c = self.config.fair_c
        x = np.abs(score - label)
        return c * x - c * c * np.log1p(x / c)


class PoissonMetric(_PointwiseRegression):
    name = "poisson"
    def loss(self, label, score):
        eps = 1e-10
        s = np.maximum(score, eps)
        return s - label * np.log(s)


class MAPEMetric(_PointwiseRegression):
    name = "mape"
    def loss(self, label, score):
        return np.abs((label - score) / np.maximum(1.0, np.abs(label)))


class GammaMetric(_PointwiseRegression):
    """Gamma negative log-likelihood, psi=1 (ref: regression_metric.hpp GammaMetric)."""
    name = "gamma"
    def loss(self, label, score):
        eps = 1e-10
        s = np.maximum(score, eps)
        return np.maximum(label, eps) / s + np.log(s)


class GammaDevianceMetric(_PointwiseRegression):
    name = "gamma_deviance"
    def loss(self, label, score):
        eps = 1e-10
        frac = label / np.maximum(score, eps)
        return 2.0 * (-np.log(np.maximum(frac, eps)) + frac - 1.0)


class TweedieMetric(_PointwiseRegression):
    name = "tweedie"
    def loss(self, label, score):
        rho = self.config.tweedie_variance_power
        eps = 1e-10
        s = np.maximum(score, eps)
        a = label * np.power(s, 1.0 - rho) / (1.0 - rho)
        b = np.power(s, 2.0 - rho) / (2.0 - rho)
        return -a + b


# ---------------------------------------------------------------------- binary
class BinaryLoglossMetric(Metric):
    name = "binary_logloss"
    def eval(self, score, objective=None):
        prob = self._convert(score, objective)
        eps = 1e-15
        prob = np.clip(prob, eps, 1 - eps)
        is_pos = self.label > 0
        pt = np.where(is_pos, -np.log(prob), -np.log(1.0 - prob))
        return [(self.name, self._avg(pt))]


class BinaryErrorMetric(Metric):
    name = "binary_error"
    def eval(self, score, objective=None):
        prob = self._convert(score, objective)
        pred_pos = prob > 0.5
        is_pos = self.label > 0
        return [(self.name, self._avg((pred_pos != is_pos).astype(np.float64)))]


class AUCMetric(Metric):
    """ref: binary_metric.hpp:159 AUCMetric (weighted rank-sum form)."""
    name = "auc"
    is_higher_better = True

    def eval(self, score, objective=None):
        order = np.argsort(-score, kind="stable")
        s = score[order]
        lab = self.label[order] > 0
        w = (np.ones(len(s)) if self.weight is None else self.weight[order])
        # group ties: process equal-score blocks together
        boundaries = np.nonzero(np.diff(s))[0] + 1
        idx = np.concatenate([[0], boundaries, [len(s)]])
        sum_pos = 0.0
        accum = 0.0
        cur_neg = 0.0
        for a, b in zip(idx[:-1], idx[1:]):
            blk_pos = float((w[a:b] * lab[a:b]).sum())
            blk_neg = float((w[a:b] * ~lab[a:b]).sum())
            accum += blk_neg * (sum_pos + blk_pos * 0.5)
            sum_pos += blk_pos
            cur_neg += blk_neg
        if sum_pos == 0 or cur_neg == 0:
            return [(self.name, 1.0)]
        return [(self.name, accum / (sum_pos * cur_neg))]


class AveragePrecisionMetric(Metric):
    """ref: binary_metric.hpp AveragePrecisionMetric."""
    name = "average_precision"
    is_higher_better = True

    def eval(self, score, objective=None):
        order = np.argsort(-score, kind="stable")
        lab = self.label[order] > 0
        w = (np.ones(len(order)) if self.weight is None else self.weight[order])
        tp = np.cumsum(w * lab)
        fp = np.cumsum(w * ~lab)
        precision = tp / np.maximum(tp + fp, 1e-20)
        delta_tp = w * lab
        total_pos = tp[-1]
        if total_pos == 0:
            return [(self.name, 1.0)]
        return [(self.name, float((precision * delta_tp).sum() / total_pos))]


# ------------------------------------------------------------------ multiclass
class MultiLoglossMetric(Metric):
    name = "multi_logloss"
    def eval(self, score, objective=None):
        # score [K, n] raw -> softmax
        prob = self._convert(score, objective)
        if prob.ndim == 1:
            k = self.config.num_class
            prob = prob.reshape(k, -1)
        li = self.label.astype(np.int64)
        p = np.clip(prob[li, np.arange(prob.shape[1])], 1e-15, 1.0)
        return [(self.name, self._avg(-np.log(p)))]


class MultiErrorMetric(Metric):
    name = "multi_error"
    def eval(self, score, objective=None):
        prob = self._convert(score, objective)
        if prob.ndim == 1:
            k = self.config.num_class
            prob = prob.reshape(k, -1)
        top_k = self.config.multi_error_top_k
        li = self.label.astype(np.int64)
        true_p = prob[li, np.arange(prob.shape[1])]
        # error if true-class prob is not within top_k; ties count AGAINST
        # the row (ref: multiclass_metric.hpp:142 LossOnPoint counts
        # num_larger with >= including the class itself, error when
        # num_larger > top_k)
        num_ge = (prob >= true_p[None, :]).sum(axis=0)
        err = (num_ge > top_k).astype(np.float64)
        return [(self.name, self._avg(err))]


class AucMuMetric(Metric):
    """AUC-mu multiclass ranking metric (ref: multiclass_metric.hpp:183
    AucMuMetric; Kleiman & Page, ICML'19).  For every class pair (i, j)
    the rows of the two classes are projected on the separating direction
    v = W[i] - W[j] (W the auc_mu weight matrix, default all-ones with
    zero diagonal, config.cpp:220) and a pairwise AUC S[i][j] is
    accumulated with the reference's tie handling: rows within kEpsilon
    (1e-15, meta.h:54) of the last j-class distance contribute 0.5 per
    tied j row.  Result = 2 * sum_{i<j} S[i][j]/(n_i*n_j) / (K*(K-1))."""
    name = "auc_mu"
    is_higher_better = True
    _EPS = 1e-15

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        K = self.config.num_class
        w = list(self.config.auc_mu_weights or [])
        if w:
            if len(w) != K * K:
                log.fatal(f"auc_mu_weights must have {K * K} elements, "
                          f"but found {len(w)}")
            W = np.asarray(w, np.float64).reshape(K, K)
            if np.abs(np.diag(W)).max() > 1e-35:
                log.info("AUC-mu matrix must have zeros on diagonal. "
                         "Overwriting.")
            np.fill_diagonal(W, 0.0)
        else:
            W = np.ones((K, K), np.float64)
            np.fill_diagonal(W, 0.0)
        self.class_weights = W
        li = self.label.astype(np.int64)
        self.class_idx = [np.nonzero(li == k)[0] for k in range(K)]
        self.class_sizes = np.array([len(ix) for ix in self.class_idx])
        if self.weight is not None:
            self.class_data_weights = np.array(
                [float(self.weight[ix].sum()) for ix in self.class_idx])

    def _pair_auc(self, score, i, j):
        """S[i][j] of the reference's Eval loop, vectorized."""
        idx = np.concatenate([self.class_idx[i], self.class_idx[j]])
        if len(self.class_idx[i]) == 0 or len(self.class_idx[j]) == 0:
            return 0.0
        v = self.class_weights[i] - self.class_weights[j]      # curr_v
        t1 = v[i] - v[j]
        dist = t1 * (v @ score[:, idx])                        # [n_i+n_j]
        lab = np.concatenate([np.full(len(self.class_idx[i]), i),
                              np.full(len(self.class_idx[j]), j)])
        w = (np.ones(len(idx)) if self.weight is None
             else self.weight[idx])
        # sort by distance; exact ties put class j first (the reference
        # comparator orders near-ties by label descending; exact-tie
        # grouping below covers the epsilon credit)
        order = np.lexsort((-lab, dist))
        dist, lab, w = dist[order], lab[order], w[order]
        is_j = lab == j
        wj = np.where(is_j, w, 0.0)
        cum_wj = np.cumsum(wj)                 # num_j including position
        # j-distance groups: a new group starts when the j row's distance
        # moves >= eps from the previous j row's (the reference chains
        # from the group-start distance; consecutive chaining is
        # equivalent except for pathological sub-eps ladders)
        jpos = np.nonzero(is_j)[0]
        if len(jpos) == 0:
            return 0.0
        jd = dist[jpos]
        new_grp = np.empty(len(jpos), bool)
        new_grp[0] = True
        new_grp[1:] = np.abs(np.diff(jd)) >= self._EPS
        grp_of_j = np.cumsum(new_grp) - 1
        starts = np.nonzero(new_grp)[0]
        grp_start_dist = jd[starts]
        grp_start_cumwj_before = cum_wj[jpos[starts]] - wj[jpos[starts]]
        # per row: index of the last j row at/before it
        last_j = np.searchsorted(jpos, np.arange(len(dist)), "right") - 1
        ipos = np.nonzero(~is_j)[0]
        li_ = last_j[ipos]
        has_j = li_ >= 0
        g = grp_of_j[np.maximum(li_, 0)]
        num_j_before = np.where(has_j, cum_wj[np.maximum(jpos[np.maximum(
            li_, 0)], 0)], 0.0) * has_j
        tie = has_j & (np.abs(dist[ipos] - grp_start_dist[g]) < self._EPS)
        num_cur_j = np.where(tie, num_j_before
                             - grp_start_cumwj_before[g], 0.0)
        contrib = w[ipos] * (num_j_before - 0.5 * num_cur_j)
        return float(contrib.sum())

    def eval(self, score, objective=None):
        K = self.config.num_class
        if score.ndim == 1:
            score = score.reshape(K, -1)
        score = np.asarray(score, np.float64)
        ans = 0.0
        for i in range(K):
            for j in range(i + 1, K):
                s = self._pair_auc(score, i, j)
                if self.weight is None:
                    den = (self.class_sizes[i] * self.class_sizes[j])
                else:
                    den = (self.class_data_weights[i]
                           * self.class_data_weights[j])
                if den > 0:
                    ans += s / den
        return [(self.name, 2.0 * ans / (K * (K - 1)))]


# --------------------------------------------------------------------- ranking
DEFAULT_LABEL_GAIN_SIZE = 31


def default_label_gain() -> List[float]:
    return [float((1 << i) - 1) for i in range(DEFAULT_LABEL_GAIN_SIZE)]


class NDCGMetric(Metric):
    """NDCG@k (ref: rank_metric.hpp:20, dcg_calculator.cpp)."""
    name = "ndcg"
    is_higher_better = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.eval_at = list(config.eval_at) or [1, 2, 3, 4, 5]
        self.label_gain = list(config.label_gain) or default_label_gain()

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.query_boundaries is None:
            log.fatal("The NDCG metric requires query information")

    def eval(self, score, objective=None):
        qb = self.query_boundaries
        gains = np.asarray(self.label_gain)
        results = {k: [] for k in self.eval_at}
        for qi in range(len(qb) - 1):
            a, b = int(qb[qi]), int(qb[qi + 1])
            lab = self.label[a:b].astype(np.int64)
            sc = score[a:b]
            g = gains[lab]
            order = np.argsort(-sc, kind="stable")
            ideal = np.sort(g)[::-1]
            discounts = 1.0 / np.log2(np.arange(len(lab)) + 2.0)
            for k in self.eval_at:
                kk = min(k, len(lab))
                idcg = float((ideal[:kk] * discounts[:kk]).sum())
                if idcg > 0:
                    dcg = float((g[order][:kk] * discounts[:kk]).sum())
                    results[k].append(dcg / idcg)
                else:
                    results[k].append(1.0)
        return [(f"ndcg@{k}", float(np.mean(results[k]))) for k in self.eval_at]


class MapMetric(Metric):
    """MAP@k (ref: map_metric.hpp:17)."""
    name = "map"
    is_higher_better = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.eval_at = list(config.eval_at) or [1, 2, 3, 4, 5]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.query_boundaries is None:
            log.fatal("The MAP metric requires query information")

    def eval(self, score, objective=None):
        qb = self.query_boundaries
        results = {k: [] for k in self.eval_at}
        for qi in range(len(qb) - 1):
            a, b = int(qb[qi]), int(qb[qi + 1])
            rel = (self.label[a:b] > 0)[np.argsort(-score[a:b], kind="stable")]
            npos = int(rel.sum())
            cum = np.cumsum(rel)
            prec_at_hit = np.where(rel, cum / (np.arange(len(rel)) + 1.0), 0.0)
            for k in self.eval_at:
                kk = min(k, len(rel))
                denom = min(npos, kk)
                if denom > 0:
                    results[k].append(float(prec_at_hit[:kk].sum()) / denom)
                else:
                    results[k].append(1.0)
        return [(f"map@{k}", float(np.mean(results[k]))) for k in self.eval_at]


# ---------------------------------------------------------------- cross-entropy
class CrossEntropyMetric(Metric):
    name = "cross_entropy"
    def eval(self, score, objective=None):
        p = np.clip(self._convert(score, objective), 1e-15, 1 - 1e-15)
        y = self.label
        pt = -y * np.log(p) - (1 - y) * np.log(1 - p)
        return [(self.name, self._avg(pt))]


class CrossEntropyLambdaMetric(Metric):
    name = "cross_entropy_lambda"
    def eval(self, score, objective=None):
        hhat = self._convert(score, objective)  # log1p(exp(score))
        y = self.label
        w = self.weight if self.weight is not None else 1.0
        z = 1.0 - np.exp(-w * hhat)
        z = np.clip(z, 1e-15, 1 - 1e-15)
        pt = -y * np.log(z) - (1 - y) * np.log(1 - z)
        return [(self.name, float(np.mean(pt)))]


class KLDivergenceMetric(Metric):
    name = "kullback_leibler"
    def eval(self, score, objective=None):
        p = np.clip(self._convert(score, objective), 1e-15, 1 - 1e-15)
        y = np.clip(self.label, 1e-15, 1 - 1e-15)
        pt = y * np.log(y / p) + (1 - y) * np.log((1 - y) / (1 - p))
        return [(self.name, self._avg(pt))]


# --------------------------------------------------------------------- factory
_METRIC_ALIASES = {
    "l2": "l2", "mean_squared_error": "l2", "mse": "l2", "regression": "l2",
    "regression_l2": "l2",
    "l2_root": "rmse", "root_mean_squared_error": "rmse", "rmse": "rmse",
    "l1": "l1", "mean_absolute_error": "l1", "mae": "l1", "regression_l1": "l1",
    "quantile": "quantile", "huber": "huber", "fair": "fair", "poisson": "poisson",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "gamma": "gamma", "gamma_deviance": "gamma_deviance", "tweedie": "tweedie",
    "binary_logloss": "binary_logloss", "binary": "binary_logloss",
    "binary_error": "binary_error",
    "auc": "auc", "average_precision": "average_precision",
    "multi_logloss": "multi_logloss", "multiclass": "multi_logloss",
    "softmax": "multi_logloss", "multiclassova": "multi_logloss",
    "multi_error": "multi_error", "auc_mu": "auc_mu",
    "ndcg": "ndcg", "lambdarank": "ndcg", "rank_xendcg": "ndcg",
    "xendcg": "ndcg", "map": "map", "mean_average_precision": "map",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "kullback_leibler": "kullback_leibler", "kldiv": "kullback_leibler",
}

_METRIC_CLASSES = {
    "l2": L2Metric, "rmse": RMSEMetric, "l1": L1Metric,
    "quantile": QuantileMetric, "huber": HuberMetric, "fair": FairMetric,
    "poisson": PoissonMetric, "mape": MAPEMetric, "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric, "tweedie": TweedieMetric,
    "binary_logloss": BinaryLoglossMetric, "binary_error": BinaryErrorMetric,
    "auc": AUCMetric, "average_precision": AveragePrecisionMetric,
    "multi_logloss": MultiLoglossMetric, "multi_error": MultiErrorMetric,
    "auc_mu": AucMuMetric,
    "ndcg": NDCGMetric, "map": MapMetric,
    "cross_entropy": CrossEntropyMetric,
    "cross_entropy_lambda": CrossEntropyLambdaMetric,
    "kullback_leibler": KLDivergenceMetric,
}

# objective -> default metric (ref: config.cpp Config::GetMetricType)
_DEFAULT_FOR_OBJECTIVE = {
    "regression": "l2", "regression_l1": "l1", "huber": "huber", "fair": "fair",
    "poisson": "poisson", "quantile": "quantile", "mape": "mape",
    "gamma": "gamma", "tweedie": "tweedie", "binary": "binary_logloss",
    "multiclass": "multi_logloss", "multiclassova": "multi_logloss",
    "cross_entropy": "cross_entropy", "cross_entropy_lambda": "cross_entropy_lambda",
    "lambdarank": "ndcg", "rank_xendcg": "ndcg",
}


def create_metrics(config: Config, for_objective: Optional[str] = None) -> List[Metric]:
    """ref: src/metric/metric.cpp:19 Metric::CreateMetric + config metric parsing."""
    names = [str(m).strip().lower() for m in (config.metric or [])]
    if not names:
        obj = for_objective or config.objective
        if obj in _DEFAULT_FOR_OBJECTIVE:
            names = [_DEFAULT_FOR_OBJECTIVE[obj]]
    out: List[Metric] = []
    seen = set()
    for nm in names:
        if nm in ("", "na", "null", "none", "custom"):
            continue
        canon = _METRIC_ALIASES.get(nm)
        if canon is None:
            log.warning(f"Unknown metric: {nm}")
            continue
        if canon in seen:
            continue
        seen.add(canon)
        out.append(_METRIC_CLASSES[canon](config))
    return out


# ----------------------------------------------------- device (sharded) eval
def device_pointwise_loss(name: str, config: Config):
    """jnp pointwise-loss builder for the sharded train-metric evaluator
    (gbdt._eval_train_sharded): fn(converted_score, label) -> loss, or
    None when the metric has no device form.  Formulas mirror the host
    classes above exactly (which mirror src/metric/*_metric.hpp)."""
    import jax.numpy as jnp
    eps10, eps15 = 1e-10, 1e-15

    def clip_pos(s):
        return jnp.maximum(s, eps10)

    fns = {
        "l2": lambda s, y: (s - y) ** 2,
        "rmse": lambda s, y: (s - y) ** 2,          # sqrt after averaging
        "l1": lambda s, y: jnp.abs(s - y),
        "quantile": lambda s, y: jnp.where(
            (y - s) < 0, (config.alpha - 1.0) * (y - s),
            config.alpha * (y - s)),
        "huber": lambda s, y: jnp.where(
            jnp.abs(s - y) <= config.alpha,
            0.5 * (s - y) ** 2,
            config.alpha * (jnp.abs(s - y) - 0.5 * config.alpha)),
        "fair": lambda s, y: (config.fair_c * jnp.abs(s - y)
                              - config.fair_c ** 2
                              * jnp.log1p(jnp.abs(s - y) / config.fair_c)),
        "poisson": lambda s, y: clip_pos(s) - y * jnp.log(clip_pos(s)),
        "mape": lambda s, y: jnp.abs((y - s)
                                     / jnp.maximum(1.0, jnp.abs(y))),
        "gamma": lambda s, y: (jnp.maximum(y, eps10) / clip_pos(s)
                               + jnp.log(clip_pos(s))),
        "gamma_deviance": lambda s, y: 2.0 * (
            -jnp.log(jnp.maximum(y / clip_pos(s), eps10))
            + y / clip_pos(s) - 1.0),
        "tweedie": lambda s, y: (
            -y * clip_pos(s) ** (1.0 - config.tweedie_variance_power)
            / (1.0 - config.tweedie_variance_power)
            + clip_pos(s) ** (2.0 - config.tweedie_variance_power)
            / (2.0 - config.tweedie_variance_power)),
        "binary_logloss": lambda s, y: jnp.where(
            y > 0, -jnp.log(jnp.clip(s, eps15, 1 - eps15)),
            -jnp.log(1.0 - jnp.clip(s, eps15, 1 - eps15))),
        "binary_error": lambda s, y: ((s > 0.5) != (y > 0)).astype(
            jnp.float32),
        "xentropy": lambda s, y: -(y * jnp.log(jnp.clip(s, eps15, 1.0))
                                   + (1.0 - y)
                                   * jnp.log(jnp.clip(1.0 - s, eps15,
                                                      1.0))),
    }
    return fns.get(name)


def device_binned_auc(prob, label, w, num_bins: int = 16384):
    """Weighted AUC from a global score-bin histogram — the
    multi-process form (each term is a plain sum, so GSPMD reduces the
    sharded rows with one all-reduce).  Resolution 1/num_bins of
    probability space; ties within a bin get the same half-credit the
    host block form gives exact ties (binary_metric.hpp:159)."""
    import jax.numpy as jnp
    # scores need not be probabilities (regression/ranking objectives
    # report raw scores): min-max normalize over the weighted rows first
    # — AUC is invariant under monotone maps, so this only sets the
    # binning resolution.  Zero-weight (padding) rows are excluded from
    # the range so they cannot skew it.
    lo = jnp.min(jnp.where(w > 0, prob, jnp.inf))
    hi = jnp.max(jnp.where(w > 0, prob, -jnp.inf))
    span = jnp.maximum(hi - lo, 1e-30)
    unit = jnp.clip((prob - lo) / span, 0.0, 1.0)
    b = jnp.clip((unit * num_bins).astype(jnp.int32), 0, num_bins - 1)
    is_pos = label > 0
    pos_h = jnp.zeros(num_bins, jnp.float32).at[b].add(
        jnp.where(is_pos, w, 0.0))
    neg_h = jnp.zeros(num_bins, jnp.float32).at[b].add(
        jnp.where(is_pos, 0.0, w))
    # descending-score accumulation: higher bins first
    pos_above = (jnp.cumsum(pos_h[::-1])[::-1]) - pos_h
    accum = jnp.sum(neg_h * (pos_above + 0.5 * pos_h))
    tp, tn = jnp.sum(pos_h), jnp.sum(neg_h)
    return jnp.where((tp == 0) | (tn == 0), 1.0, accum
                     / jnp.maximum(tp * tn, 1e-30))


def device_binned_average_precision(prob, label, w, num_bins: int = 16384):
    """Weighted average precision from the same global score-bin
    histogram device_binned_auc uses (multi-process form of
    binary_metric.hpp AveragePrecisionMetric).  Within-bin ordering is
    quantized to 1/num_bins of score space, like the binned AUC."""
    import jax.numpy as jnp
    lo = jnp.min(jnp.where(w > 0, prob, jnp.inf))
    hi = jnp.max(jnp.where(w > 0, prob, -jnp.inf))
    span = jnp.maximum(hi - lo, 1e-30)
    unit = jnp.clip((prob - lo) / span, 0.0, 1.0)
    b = jnp.clip((unit * num_bins).astype(jnp.int32), 0, num_bins - 1)
    is_pos = label > 0
    pos_h = jnp.zeros(num_bins, jnp.float32).at[b].add(
        jnp.where(is_pos, w, 0.0))
    neg_h = jnp.zeros(num_bins, jnp.float32).at[b].add(
        jnp.where(is_pos, 0.0, w))
    # descending-score traversal: inclusive cumulative tp/fp from above
    tp = jnp.cumsum(pos_h[::-1])[::-1]
    fp = jnp.cumsum(neg_h[::-1])[::-1]
    prec = tp / jnp.maximum(tp + fp, 1e-20)
    total_pos = jnp.sum(pos_h)
    ap = jnp.sum(prec * pos_h) / jnp.maximum(total_pos, 1e-30)
    return jnp.where(total_pos == 0, 1.0, ap)


def device_auc_mu(prob, label, w, class_weights: np.ndarray,
                  num_bins: int = 4096):
    """auc_mu over sharded rows (multi-process form of AucMuMetric):
    each class pair's rows are projected on v = W[i]-W[j] (row-local),
    then a binned two-class AUC runs per pair — every term is a plain
    sum, so GSPMD reduces the sharded rows.  Tie credit is quantized to
    the bin resolution like device_binned_auc."""
    import jax.numpy as jnp
    K = prob.shape[0]
    Wm = np.asarray(class_weights, np.float32)
    total = 0.0
    for i in range(K):
        for j in range(i + 1, K):
            v = jnp.asarray(Wm[i] - Wm[j])
            t1 = float(Wm[i, i] - Wm[j, i] - (Wm[i, j] - Wm[j, j]))
            dist = t1 * jnp.einsum("k,kn->n", v, prob)
            in_pair = (label == i) | (label == j)
            wp = jnp.where(in_pair, w, 0.0)
            total = total + device_binned_auc(dist, (label == i), wp,
                                              num_bins=num_bins)
    return 2.0 * total / (K * (K - 1))


def map_device_plan(metric: "MapMetric", n_pad: int, shared_buckets=None):
    """Device evaluation plan for MAP@k over sharded scores (the
    multi-process form of MapMetric.eval; ref map_metric.hpp:17):
    per-query sorted-precision sums from bucketed sort programs, with
    per-query positive counts and denominators precomputed host-side
    (labels are static).  Returns (bucket_args, eval_fn)."""
    import jax.numpy as jnp
    lab_all = metric.label
    ks = list(metric.eval_at)
    buckets = []
    nq = 0
    for bi, b in enumerate(bucket_queries(metric.query_boundaries, n_pad)):
        Qb, m = len(b["qs"]), b["m"]
        rel = np.zeros((Qb, m), np.float32)
        denom = np.zeros((Qb, len(ks)), np.float32)
        for r, q in enumerate(b["qs"]):
            a, e = (int(metric.query_boundaries[q]),
                    int(metric.query_boundaries[q + 1]))
            rq = (lab_all[a:e] > 0)
            rel[r, :e - a] = rq
            npos = int(rq.sum())
            for ki, k in enumerate(ks):
                denom[r, ki] = min(npos, min(k, e - a))
        buckets.append({**_shared_or_uploaded(b, shared_buckets, bi),
                        "rel": jnp.asarray(rel),
                        "denom": jnp.asarray(denom)})
        nq += Qb

    def eval_fn(sc, bucket_args):
        sums = jnp.zeros(len(ks), jnp.float32)
        for bk in bucket_args:
            m = bk["idx"].shape[1]
            scb = jnp.take(sc, bk["idx"])
            key = jnp.where(bk["val"], scb, -jnp.inf)
            order = jnp.argsort(-key, axis=1, stable=True)
            rel_sorted = jnp.take_along_axis(bk["rel"], order, 1)
            cum = jnp.cumsum(rel_sorted, axis=1)
            pos_idx = jnp.arange(m, dtype=jnp.float32) + 1.0
            prec_at_hit = jnp.where(rel_sorted > 0,
                                    cum / pos_idx[None, :], 0.0)
            terms = []
            for ki, k in enumerate(ks):
                kk = min(k, m)
                s = jnp.sum(prec_at_hit[:, :kk], axis=1)
                d = bk["denom"][:, ki]
                terms.append(jnp.sum(jnp.where(d > 0,
                                               s / jnp.maximum(d, 1.0),
                                               1.0)))
            sums = sums + jnp.stack(terms)
        return sums / nq

    return buckets, eval_fn


def _shared_or_uploaded(b, shared_buckets, bi):
    """A bucket's `idx` and `val` on the device.  A ranking objective's
    plan is `bucket_queries`' too (deterministic), so what its bucket
    `bi` already holds on the device under the same key and shape is
    shared instead of kept twice; it holds `val`, and no `idx` since its
    programs read a query's rows as the run they are (ranking.py)."""
    import jax.numpy as jnp
    have = (shared_buckets[bi] if shared_buckets is not None
            and bi < len(shared_buckets) else {})
    return {k: have[k] if k in have and have[k].shape == b[k].shape
            else jnp.asarray(b[k]) for k in ("idx", "val")}


def bucket_queries(query_boundaries, n_pad: int):
    """Group queries by pow2-padded length for device-side per-query
    tensor programs: returns a list of dicts {qs: [query ids], m: the
    padded length, idx: [Qb, m] int32 global row indices (padding ->
    n_pad - 1), val: [Qb, m] bool}.  The ndcg and map eval plans take a
    bucket's scores through `idx`; the ranking gradients' plan
    (ranking.py `_plan_buckets`) fills its labels through it on the host
    and reaches the rows on the device by the queries' starts instead."""
    qb = np.asarray(query_boundaries)
    lens = np.diff(qb).astype(np.int64)
    groups = {}
    for q, ln in enumerate(lens):
        m = max(8, 1 << int(ln - 1).bit_length())
        groups.setdefault(m, []).append(q)
    out = []
    for m, qs in sorted(groups.items()):
        Qb = len(qs)
        idx = np.full((Qb, m), n_pad - 1, np.int32)
        val = np.zeros((Qb, m), bool)
        for r, q in enumerate(qs):
            a, b = int(qb[q]), int(qb[q + 1])
            idx[r, :b - a] = np.arange(a, b)
            val[r, :b - a] = True
        out.append({"qs": qs, "m": m, "idx": idx, "val": val})
    return out


def ndcg_device_plan(metric: "NDCGMetric", n_pad: int,
                     shared_buckets=None):
    """Device evaluation plan for NDCG@k over sharded scores: per-query
    DCG from bucketed sort programs, ideal DCG precomputed host-side
    (labels are static).  Returns (bucket_args pytree, eval_fn) where
    eval_fn(scores_1d, bucket_args) -> [len(eval_at)] means — the
    multi-process form of NDCGMetric.eval (rank_metric.hpp:20)."""
    import jax.numpy as jnp
    gains_np = np.asarray(metric.label_gain, np.float64)
    lab_all = metric.label.astype(np.int64)
    ks = list(metric.eval_at)
    buckets = []
    nq = 0
    for bi, b in enumerate(bucket_queries(metric.query_boundaries, n_pad)):
        Qb, m = len(b["qs"]), b["m"]
        g = np.zeros((Qb, m), np.float32)
        idcg = np.zeros((Qb, len(ks)), np.float32)
        disc = 1.0 / np.log2(np.arange(m) + 2.0)
        for r, q in enumerate(b["qs"]):
            a, e = (int(metric.query_boundaries[q]),
                    int(metric.query_boundaries[q + 1]))
            gq = gains_np[lab_all[a:e]]
            ideal = np.sort(gq)[::-1]
            g[r, :e - a] = gq
            for ki, k in enumerate(ks):
                kk = min(k, e - a)
                idcg[r, ki] = (ideal[:kk] * disc[:kk]).sum()
        buckets.append({**_shared_or_uploaded(b, shared_buckets, bi),
                        "g": jnp.asarray(g),
                        "idcg": jnp.asarray(idcg)})
        nq += Qb

    def eval_fn(sc, bucket_args):
        sums = jnp.zeros(len(ks), jnp.float32)
        for bk in bucket_args:
            m = bk["idx"].shape[1]
            scb = jnp.take(sc, bk["idx"])
            key = jnp.where(bk["val"], scb, -jnp.inf)
            order = jnp.argsort(-key, axis=1, stable=True)
            g_sorted = jnp.take_along_axis(bk["g"], order, 1)
            disc = (1.0 / jnp.log2(
                jnp.arange(m, dtype=jnp.float32) + 2.0))
            terms = []
            for ki, k in enumerate(ks):
                kk = min(k, m)
                dcg = jnp.sum(g_sorted[:, :kk] * disc[None, :kk], axis=1)
                nd = jnp.where(bk["idcg"][:, ki] > 0,
                               dcg / jnp.maximum(bk["idcg"][:, ki], 1e-30),
                               1.0)
                terms.append(jnp.sum(nd))
            sums = sums + jnp.stack(terms)
        return sums / nq

    return buckets, eval_fn
