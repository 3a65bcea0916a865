"""MS-LTR-shaped learning-to-rank rows from a seed.

MSLR-WEB30K Fold1's training part (LightGBM docs/Experiments.rst "MS
LTR": 2,270,296 x 137) is 18,919 queries of 1 to 1,251 documents (mean
120), each document 136 numeric features and a relevance grade 0-4.  The
file cannot be fetched where this runs, so rows are drawn:

* `groups(rows, seed)`: query lengths log-normal with mean 120, clipped
  to 1-1,251 and rescaled to sum to `rows` exactly; `rows // 120`
  queries (18,919 at the published rows).  Where there are at least
  `FORCE_FROM` queries, lengths 1, 2 and 1,251 are written in, as the
  real set holds them and a log-normal of 18,919 draws need not.
* `make(rows, features, seed)`: dense float32.  A third of the columns
  are integer-valued with few distinct values and half zeros (term
  counts, URL lengths), the rest continuous and heavy-tailed (odds
  `u / (1 - u)`, and a two-sided twin).  Each column is a monotone
  function of one uniform draw (numpy draws a float32 uniform in a
  quarter of a gaussian's time, and bins are quantiles, so a tree sees
  the ranks either way); the grade is a FIXED function of those draws (so train
  and held-out queries share it): a power-law weight vector, four
  pairwise terms, a per-query offset (so the label mix varies by query
  and some queries are all one grade) and per-document noise, cut at
  fixed thresholds into shares of about 0.52 / 0.32 / 0.13 / 0.02 / 0.01.

Rows of a query are contiguous.  Drawn in row blocks from numpy's SFC64
stream in float32, transformed in place: no float64 copy of the matrix.
Quality is comparable only between runs of this generator.
"""

import numpy as np

MEAN_LEN = 120
MAX_LEN = 1251              # MSLR-WEB30K's longest query
LOG_SIGMA = 0.7             # of the log-normal: median 94, ~6 in 18,919 over 1,024
FORCE_FROM = 256            # queries from which lengths 1, 2, 1,251 are written in
BLOCK_ROWS = 1 << 17        # 72 MB of float32 a block at 137 features
PAIRS = 4                   # pairwise terms of the grade function
QUERY_SD = 1.0              # per-query offset: 75 of 18,919 queries all one grade
NOISE_SD = 0.5              # per-document noise
# cuts of the latent relevance, read once off 2,270,296 drawn rows at the
# aggregate shares 0.52 / 0.32 / 0.13 / 0.02 / 0.01 (cumulative 0.52,
# 0.84, 0.97, 0.99) and FIXED since: the grade function may not move
# with the seed
CUTS = np.array([0.0854, 1.6541, 3.1167, 3.8456], dtype=np.float32)
ONE_PLUS = np.float32(1) + np.float32(2.0 ** -23)   # u = 0 is drawn: no 1 / 0
INT_CAPS = (3, 7, 15, 31, 63, 255)   # largest value of the integer columns, in turn


def groups(rows, seed):
    """int64 query lengths summing to `rows`, from the seed alone."""
    rows = int(rows)
    nq = max(1, rows // MEAN_LEN)
    rng = np.random.Generator(np.random.SFC64([int(seed), 1]))
    mu = np.log(MEAN_LEN) - 0.5 * LOG_SIGMA ** 2
    lens = np.clip(np.rint(np.exp(rng.normal(mu, LOG_SIGMA, nq))), 1,
                   MAX_LEN).astype(np.int64)
    free = np.ones(nq, bool)
    if nq >= FORCE_FROM:
        at = rng.choice(nq, size=3, replace=False)
        lens[at] = (1, 2, MAX_LEN)
        free[at] = False
    # rescale the free lengths to the exact row count, then hand the
    # rounding's remainder out one document a query
    want = rows - int(lens[~free].sum())
    scaled = lens[free] * (want / max(int(lens[free].sum()), 1))
    lens[free] = np.clip(np.rint(scaled), 1, MAX_LEN).astype(np.int64)
    order = np.flatnonzero(free)[rng.permutation(int(free.sum()))]
    while (rest := rows - int(lens.sum())) != 0:
        step = 1 if rest > 0 else -1
        can = order[(lens[order] < MAX_LEN) if step > 0
                    else (lens[order] > 1)][:abs(rest)]
        if not len(can):
            raise ValueError(f"mslr_like: {rows} rows do not fit "
                             f"{nq} queries of 1-{MAX_LEN} documents")
        lens[can] += step
    return lens


def grade_weights(F):
    """The grade function's fixed part: a unit weight vector whose
    entries fall off as a power law, and `PAIRS` interacting pairs."""
    rs = np.random.RandomState(4321)
    w = rs.randn(F) * (1.0 + np.arange(F)) ** -0.7
    w = w[rs.permutation(F)]
    pairs = rs.choice(F, size=(min(PAIRS, F // 2), 2), replace=False)
    return (w / np.linalg.norm(w)).astype(np.float32), pairs


def latent(n, F, seed, lens):
    """(X, latent relevance): `make` before the cut into grades."""
    rng = np.random.Generator(np.random.SFC64([int(seed), 2]))
    w, pairs = grade_weights(F)
    offset = np.repeat(
        (QUERY_SD * rng.standard_normal(len(lens), dtype=np.float32)), lens)
    X = np.empty((n, F), dtype=np.float32)
    z = np.empty(n, dtype=np.float32)
    third = F // 3
    root12 = np.float32(np.sqrt(12.0))      # a uniform draw's 1 / sd
    for r0 in range(0, n, BLOCK_ROWS):
        blk = X[r0:r0 + BLOCK_ROWS]
        rng.random(out=blk, dtype=np.float32)
        zb = (blk @ w - np.float32(0.5) * w.sum()) * root12
        for a, b in pairs:
            zb += (np.float32(0.35 * 12) * (blk[:, a] - np.float32(0.5))
                   * (blk[:, b] - np.float32(0.5)))
        z[r0:r0 + BLOCK_ROWS] = zb
        # the columns, each a monotone function of its draw u
        odds = blk[:, :2 * third]           # u / (1 - u): median 1, P(> k) = 1 / (k + 1)
        np.divide(odds, np.float32(1) - odds, out=odds)
        ints = blk[:, :third]               # its floor: half zeros, capped
        np.floor(ints, out=ints)
        for k, cap in enumerate(INT_CAPS):
            np.minimum(ints[:, k::len(INT_CAPS)], np.float32(cap),
                       out=ints[:, k::len(INT_CAPS)])
        sym = blk[:, 2 * third:]            # c / (1 - |c|), c = 2u - 1: both tails
        np.multiply(sym, np.float32(2), out=sym)
        np.subtract(sym, np.float32(1), out=sym)
        np.divide(sym, ONE_PLUS - np.abs(sym), out=sym)
    z += offset
    z += NOISE_SD * rng.standard_normal(n, dtype=np.float32)
    return X, z


def make(n, F, seed):
    """(X [n, F] float32, y [n] float32 grades 0-4), rows of a query
    contiguous in the order of `groups(n, seed)`."""
    X, z = latent(n, F, seed, groups(n, seed))
    return X, np.searchsorted(CUTS, z).astype(np.float32)
