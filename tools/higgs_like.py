"""Seeded Higgs-shaped binary-classification data and the AUC it is
judged by — shared by bench.py and chip_smoke.py, so both train on the
same generator (the real Higgs file cannot be downloaded where these
run)."""

import numpy as np


def make_higgs_like(n, F, seed=0):
    rng = np.random.RandomState(seed)
    X = np.empty((n, F), dtype=np.float32)
    # mix of gaussian "low-level" and heavy-tailed "high-level" features
    for f in range(F):
        if f % 3 == 0:
            X[:, f] = rng.randn(n)
        elif f % 3 == 1:
            X[:, f] = np.abs(rng.randn(n)) ** 1.5
        else:
            X[:, f] = rng.rand(n)
    # the label function is FIXED across seeds so train/test share it
    w = np.random.RandomState(1234).randn(F) / np.sqrt(F)
    logit = X @ w + 0.5 * X[:, 0] * X[:, 1]
    y = (rng.rand(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y


def auc(y, s):
    """Tie-averaged rank-sum AUC (ties get 0.5 credit per pos/neg pair, as
    binary_metric.hpp's AUCMetric does via equal-score blocks)."""
    _, inv, counts = np.unique(s, return_inverse=True, return_counts=True)
    cum = np.cumsum(counts) - counts
    ranks = (cum + (counts + 1) / 2.0)[inv]
    pos = y > 0
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / max(n_pos * n_neg, 1))
