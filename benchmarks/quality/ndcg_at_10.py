"""NDCG@10 over whole queries, in plain NumPy on the host.

As upstream's `ndcg` metric (rank_metric.hpp, dcg_calculator.cpp): a
document's gain is `2^label - 1`, position p (from 0) is discounted by
`1 / log2(p + 2)`, a query's DCG@10 is over its ten highest-scored
documents (ties in the order of the rows), divided by the DCG@10 of the
ideal order; a query with no relevant document counts 1; the result is
the mean over queries.  Written apart from `lightgbm_tpu/metric.py`,
which a test holds it against.
"""

import numpy as np

K = 10


def score(y, s, group):
    """`y`, `s`: grades and scores of all rows; `group`: query lengths
    (rows of a query contiguous), summing to `len(y)`."""
    y = np.asarray(y).astype(np.int64)
    s = np.asarray(s, dtype=np.float64)
    group = np.asarray(group, dtype=np.int64)
    ends = np.cumsum(group)
    if len(ends) == 0 or ends[-1] != len(y) or len(s) != len(y):
        raise ValueError("ndcg_at_10: group does not sum to the rows")
    discount = 1.0 / np.log2(np.arange(K) + 2.0)
    gain = 2.0 ** y - 1.0
    total = 0.0
    for a, b in zip(ends - group, ends):
        k = min(K, b - a)
        ideal = float(np.sort(gain[a:b])[::-1][:k] @ discount[:k])
        if ideal <= 0.0:
            total += 1.0
            continue
        top = np.argsort(-s[a:b], kind="stable")[:k]
        total += float(gain[a:b][top] @ discount[:k]) / ideal
    return total / len(ends)
