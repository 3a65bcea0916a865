"""Epsilon-shaped binary-classification rows from a seed.

The PASCAL challenge's Epsilon set (LightGBM docs/GPU-Performance.rst,
400,000 x 2,000) is dense: no value is zero, a column holds (nearly) as
many distinct values as rows, and every row is scaled to unit L2 norm.
The file cannot be fetched where this runs, so the rows are drawn: each
value uniform on (-1, 1), each row then divided by its norm, the label
from a fixed logistic function of the row.  (Uniform and not gaussian:
numpy draws a float32 uniform in a quarter of a gaussian's time, and
bins are quantiles, so a tree sees the ranks either way.)  Quality is
comparable only between runs of this generator.

Drawn in row blocks from numpy's SFC64 stream in float32, normalised in
place: 800 million values cost seconds of host time, every run of the
cell pays them as set-up, and no float64 copy of the matrix exists.

A matrix of `HOST_PASS_CELLS` cells or more is drawn only for a program
that says it will bin it on the device (`require_device_binning`).
"""

import sys
from types import SimpleNamespace

import numpy as np

BLOCK_ROWS = 16384          # 131 MB of float32 a block at 2,000 features
PAIRS = 8                   # pairwise terms of the label function
TINY = np.float32(1e-30)    # stands in for a drawn 0.0: no structural zeros
HOST_PASS_CELLS = 1 << 28   # ~13 s of host `searchsorted` at 47 ns a cell


def label_weights(F):
    """The label function, FIXED across seeds so train and held-out rows
    share it: a weight vector of unit norm whose entries fall off as a
    power law (a few dozen columns carry most of the signal, every
    column a little), and `PAIRS` pairs of columns that interact."""
    rs = np.random.RandomState(1234)
    w = rs.randn(F) * (1.0 + np.arange(F)) ** -0.5
    w = w[rs.permutation(F)]
    pairs = rs.choice(F, size=(min(PAIRS, F // 2), 2), replace=False)
    return (w / np.linalg.norm(w)).astype(np.float32), pairs


def require_device_binning(n, F):
    """Exit non-zero, before a value is drawn, on a TPU whose program
    would bin an [n, F] float32 matrix of `HOST_PASS_CELLS` cells or
    more on the host.  The program is asked through its own gate
    (`io/device_bin.py device_binnable`, on stand-ins for F numerical
    mappers of 64 bins); where the gate cannot be asked, or there is no
    TPU (tests, selftest.py), nothing is refused.

    Why: the tree before PR 30 gated device binning on rows (2^20) and
    ran 400,000 x 2,000 through a per-value `find_bin`, a host
    `searchsorted` pass and an unbounded EFB planner: `setup_s` 860.9 s
    (my chip run, PR 30, PERF.md section 6), which no run's time limit
    holds.  It cannot run this configuration, and says so here in
    seconds instead of being killed in minutes."""
    if n * F < HOST_PASS_CELLS:
        return
    try:
        import jax
        from lightgbm_tpu.io.binning import BIN_NUMERICAL
        from lightgbm_tpu.io.device_bin import device_binnable
        if jax.default_backend() != "tpu":
            return
        mapper = SimpleNamespace(bin_type=BIN_NUMERICAL, num_bin=64)
        on_device = device_binnable([mapper] * F, range(F), np.float32, n)
    except Exception:       # the gate has moved: not the program refused
        return
    if not on_device:
        sys.exit(f"epsilon_like: this program would bin {n:,} x {F:,} "
                 "float32 values on the host (io/device_bin.py "
                 "device_binnable says no): a set-up of many minutes "
                 "that the cell's run cannot hold; the configuration "
                 "needs the device pass for wide matrices (PR 30)")


def make(n, F, seed):
    """(X [n, F] float32 with unit-norm rows and no zero, y [n] float32
    in {0, 1})."""
    require_device_binning(n, F)
    rng = np.random.Generator(np.random.SFC64(seed))
    w, pairs = label_weights(F)
    scale = np.float32(np.sqrt(F))     # a unit-norm row's x @ w ~ N(0, 1/F)
    X = np.empty((n, F), dtype=np.float32)
    logit = np.empty(n, dtype=np.float32)
    for r0 in range(0, n, BLOCK_ROWS):
        blk = X[r0:r0 + BLOCK_ROWS]
        rng.random(out=blk, dtype=np.float32)
        blk *= np.float32(2)
        blk -= np.float32(1)
        blk /= np.sqrt(np.einsum("ij,ij->i", blk, blk))[:, None]
        np.copyto(blk, TINY, where=blk == 0)
        z = blk @ w * scale
        for a, b in pairs:
            z += np.float32(0.25) * F * blk[:, a] * blk[:, b]
        logit[r0:r0 + BLOCK_ROWS] = np.float32(4) * z
    y = (rng.random(n, dtype=np.float32)
         < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y
