"""The Expo one-hot cell's rows as the Data Expo set's eight columns: six
integer-coded categoricals and two numerics, a dense float32 matrix.

Upstream's categorical claim (LightGBM docs/Advanced-Topics.rst,
"Categorical Feature Support": use categorical features directly, without
one-hot coding, ~8x faster) on upstream's own data set (Expo,
docs/Experiments.rst: 11,000,000 rows, the categorical columns one-hot
coded).  The rows ARE `expo_like`'s: its CSR is drawn from the same seed
and decoded, so rows, labels, key skews and held-out rows are the one-hot
cell's bit for bit, and the two cells train on the same data through two
mechanisms.  Each row's 8 stored indices are, in order, one a one-hot
group's (month, day of month, weekday, carrier, origin, destination) and
the two numerics': an index less its group's start is the category code,
a contiguous integer from 0 (as Advanced-Topics.rst advises); the two
numerics (departure time, distance) are kept as stored.

`benchmarks/data.py make` permutes the 8 columns by `--seed` as it does
everywhere; the categorical columns of the layout below are 0-5.
"""

import numpy as np

from benchmarks.generators import expo_like

CATEGORIES = expo_like.GROUPS          # 12 / 31 / 7 / 22 / 313 / 313
CATEGORICAL = tuple(range(len(CATEGORIES)))
FEATURES = len(CATEGORIES) + expo_like.NUMERIC     # 8


def decode(csr):
    """A CSR of `expo_like`'s layout (columns in their own order, 8 stored
    values a row) -> the dense float32 [rows, 8] matrix."""
    n = csr.shape[0]
    k = expo_like.STORED_PER_ROW
    indices = csr.indices.reshape(n, k)
    X = np.empty((n, FEATURES), np.float32)
    X[:, :len(CATEGORIES)] = indices[:, :len(CATEGORIES)] - expo_like.STARTS[
        :len(CATEGORIES)]
    X[:, len(CATEGORIES):] = csr.data.reshape(n, k)[:, len(CATEGORIES):]
    return X


def make(rows, features, seed):
    """(X, y): `rows` x 8 float32 and the float32 labels of
    `expo_like.make(rows, 700, seed)`."""
    if features != FEATURES:
        raise ValueError(f"expo_cat_like draws {FEATURES} columns, "
                         f"not {features}")
    csr, y = expo_like.make(rows, expo_like.FEATURES, seed)
    return decode(csr), y
