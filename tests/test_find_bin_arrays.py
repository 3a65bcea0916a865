"""`BinMapper.find_bin` from array operations against the reference's
own loops (`reference_loops=True`): the same mapping bit for bit.

The loops take one Python step per distinct value of the sample: 0.19 s
a feature at 200,000 distinct values, 390 s at Epsilon's 2,000 features
(PERF.md, PR 30).  The array versions must give the bounds the loops
give — `_double_equal_ordered`'s one-ulp runs, the zero splice, the
big-count bins, the `max_bin - 1` break — on every kind of column.
"""

import math

import numpy as np
import pytest

from lightgbm_tpu.io.binning import (BinMapper, _count_in_bins,
                                     _count_in_bins_loop, _distinct_values,
                                     _distinct_values_loop, greedy_find_bin,
                                     greedy_find_bin_loop,
                                     prep_find_bin_values)


def _columns(n, seed):
    rng = np.random.RandomState(seed)
    gauss = rng.randn(n)
    heavy = np.abs(rng.standard_cauchy(n)) ** 1.5 * np.sign(rng.randn(n))
    few = rng.choice([-3.0, -0.5, 0.25, 1.0, 7.0, 100.0], n,
                     p=[.05, .1, .5, .2, .1, .05])
    zero_heavy = np.where(rng.rand(n) < 0.9, 0.0, rng.randn(n))
    nan_bearing = np.where(rng.rand(n) < 0.1, np.nan, rng.randn(n))
    positive = rng.rand(n) + 0.5
    negative = -rng.rand(n) - 0.5
    # runs of values one ulp apart, and a spike that is a "big" count
    near = np.repeat(rng.randn(n // 8), 8)[:n]
    near[1::8] = np.nextafter(near[1::8], np.inf)
    near[2::8] = np.nextafter(near[1::8], np.inf)
    spike = np.where(rng.rand(n) < 0.4, 1.25,
                     np.where(rng.rand(n) < 0.3, -2.0, rng.randn(n)))
    f32 = rng.randn(n).astype(np.float32).astype(np.float64)
    ints = rng.randint(-20, 400, n).astype(np.float64)
    return {"gaussian": gauss, "heavy_tailed": heavy, "few_valued": few,
            "zero_heavy": zero_heavy, "nan_bearing": nan_bearing,
            "positive": positive, "negative": negative, "one_ulp_runs": near,
            "spikes": spike, "float32": f32, "integers": ints}


KINDS = sorted(_columns(64, 0))


def _same_mapper(a, b):
    da, db = a.to_dict(), b.to_dict()
    ba, bb = da.pop("bin_upper_bound"), db.pop("bin_upper_bound")
    assert da == db
    # bit for bit, NaN (the missing bin's bound) included
    assert (np.array(ba).view(np.int64).tolist()
            == np.array(bb).view(np.int64).tolist())


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("max_bin", [2, 16, 63, 255])
def test_find_bin_arrays_equal_the_loops(kind, max_bin):
    for seed, n in ((1, 3000), (2, 517), (3, 40)):
        col = _columns(n, seed)[kind]
        vals = prep_find_bin_values(col)
        for kw in ({}, {"min_data_in_bin": 1}, {"zero_as_missing": True},
                   {"use_missing": False, "pre_filter": True},
                   {"min_data_in_bin": 50}):
            fast, loop = BinMapper(), BinMapper()
            fast.find_bin(vals, n, max_bin, **kw)
            loop.find_bin(vals, n, max_bin, reference_loops=True, **kw)
            _same_mapper(fast, loop)


@pytest.mark.parametrize("kind", ["gaussian", "zero_heavy", "spikes"])
def test_find_bin_arrays_equal_the_loops_with_forced_bounds(kind):
    col = _columns(2000, 5)[kind]
    vals = prep_find_bin_values(col)
    for forced in ([0.1], [-1.0, 0.0, 0.5, 2.0], [1e-40, 3.0]):
        fast, loop = BinMapper(), BinMapper()
        fast.find_bin(vals, 2000, 32, forced_upper_bounds=forced)
        loop.find_bin(vals, 2000, 32, forced_upper_bounds=forced,
                      reference_loops=True)
        _same_mapper(fast, loop)


def test_find_bin_arrays_equal_the_loops_at_the_sample_size():
    """The benchmark's shape: 200,000 sampled values, all distinct."""
    col = _columns(200_000, 7)["gaussian"].astype(np.float32)
    vals = prep_find_bin_values(col)
    fast, loop = BinMapper(), BinMapper()
    fast.find_bin(vals, len(col), 63)
    loop.find_bin(vals, len(col), 63, reference_loops=True)
    _same_mapper(fast, loop)
    assert fast.num_bin == 63


@pytest.mark.parametrize("seed", range(6))
def test_distinct_values_equal_the_loop(seed):
    rng = np.random.RandomState(seed)
    for n in (0, 1, 2, 50, 700):
        for make in (lambda: rng.randn(n), lambda: -rng.rand(n),
                     lambda: rng.rand(n),
                     lambda: rng.randint(-3, 4, n).astype(float),
                     lambda: np.concatenate(
                         [rng.randn(n), [-5e-324, 5e-324][:min(n, 2)]])):
            svals = np.sort(make())
            for zero_cnt in (0, 11):
                dv, cnt = _distinct_values(svals, zero_cnt)
                ldv, lcnt = _distinct_values_loop(svals, zero_cnt)
                assert (np.asarray(dv, np.float64).view(np.int64).tolist()
                        == np.asarray(ldv, np.float64).view(np.int64)
                        .tolist())
                assert np.asarray(cnt).tolist() == lcnt


@pytest.mark.parametrize("seed", range(8))
def test_greedy_find_bin_equals_the_loop(seed):
    """Random counts with big ones among them: every close rule fires."""
    rng = np.random.RandomState(100 + seed)
    for _ in range(60):
        nd = int(rng.randint(2, 400))
        dv = np.sort(rng.randn(nd))
        cnt = rng.randint(1, 6, nd)
        for _ in range(int(rng.randint(0, 5))):
            cnt[rng.randint(nd)] = int(rng.randint(20, 2000))
        total = int(cnt.sum())
        for max_bin in (1, 2, 3, 7, 16, 63, 255):
            for min_data in (0, 1, 3, 40):
                try:
                    want = greedy_find_bin_loop(dv.tolist(), cnt.tolist(),
                                                max_bin, total, min_data)
                except ZeroDivisionError:
                    # the loop's own fault (big counts late in the order
                    # use up the bins; C++ divides to inf): the arrays
                    # repeat it rather than bin differently
                    with pytest.raises(ZeroDivisionError):
                        greedy_find_bin(dv, cnt, max_bin, total, min_data)
                    continue
                got = greedy_find_bin(dv, cnt, max_bin, total, min_data)
                assert got == want, (nd, max_bin, min_data)
                assert all(isinstance(b, float) for b in got)


def test_count_in_bins_equals_the_loop():
    rng = np.random.RandomState(3)
    dv = np.sort(rng.randn(500))
    cnt = rng.randint(1, 9, 500)
    for bounds in ([-1.0, 0.0, 0.5, math.inf],
                   [-1.0, 0.0, 0.5, math.inf, math.nan],
                   [math.inf], sorted(dv[::50].tolist()) + [math.inf]):
        nb = len(bounds)
        assert (_count_in_bins(dv, cnt, np.array(bounds), nb)
                == _count_in_bins_loop(dv.tolist(), cnt.tolist(), bounds,
                                       nb))
