"""Area under the ROC curve (copy of `tools/higgs_like.py auc`)."""

import numpy as np


def score(y, s):
    """Tie-averaged rank-sum AUC (ties get 0.5 credit per pos/neg pair)."""
    _, inv, counts = np.unique(s, return_inverse=True, return_counts=True)
    cum = np.cumsum(counts) - counts
    ranks = (cum + (counts + 1) / 2.0)[inv]
    pos = y > 0
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / max(n_pos * n_neg, 1))
