"""Higgs-shaped binary-classification rows from a seed.

`tools/higgs_like.py make_higgs_like` kept with the benchmark, so a
later PR may change the program's tools and not what the benchmark
trains on.  It draws the same law in bulk, in float32, from numpy's
SFC64 stream: 5x faster than the original's column loop over the legacy
stream (1.2 s against 6.2 s of host time for 2,625,000 x 28), because
every run of every cell pays it as set-up.  The real Higgs file cannot
be fetched where this runs, so quality is comparable only between runs
of this generator.
"""

import numpy as np


def make(n, F, seed):
    """Higgs-shaped rows: a mix of gaussian "low-level", heavy-tailed
    "high-level" and uniform features, and a label drawn from a fixed
    logistic function of them."""
    rng = np.random.Generator(np.random.SFC64(seed))
    X = np.empty((n, F), dtype=np.float32)
    X[:, 0::3] = rng.standard_normal((n, len(range(0, F, 3))),
                                     dtype=np.float32)
    X[:, 1::3] = np.abs(rng.standard_normal(
        (n, len(range(1, F, 3))), dtype=np.float32)) ** np.float32(1.5)
    X[:, 2::3] = rng.random((n, len(range(2, F, 3))), dtype=np.float32)
    # the label function is FIXED across seeds so train/test share it
    w = (np.random.RandomState(1234).randn(F) / np.sqrt(F)).astype(
        np.float32)
    logit = X @ w + np.float32(0.5) * X[:, 0] * X[:, 1]
    y = (rng.random(n, dtype=np.float32)
         < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y
