"""Feature binning: value -> bin mapping built from sampled values.

Behavioral parity with the reference's BinMapper (ref: src/io/bin.cpp:78-506,
include/LightGBM/bin.h:84-258,611-647): GreedyFindBin, FindBinWithZeroAsOneBin,
missing handling (None/Zero/NaN), categorical count-sorted bins, trivial-feature
pre-filtering.  Host-side NumPy — binning runs once at dataset construction; the
resulting integer codes are what live on TPU.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..utils import log

K_ZERO_THRESHOLD = 1e-35  # ref: include/LightGBM/meta.h:56
K_SPARSE_THRESHOLD = 0.8  # ref: include/LightGBM/bin.h kSparseThreshold

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

BIN_NUMERICAL = 0
BIN_CATEGORICAL = 1

_MISSING_TYPE_STR = {MISSING_NONE: "none", MISSING_ZERO: "zero", MISSING_NAN: "nan"}
_MISSING_TYPE_FROM_STR = {v: k for k, v in _MISSING_TYPE_STR.items()}


def _next_after_up(a: float) -> float:
    return math.nextafter(a, math.inf)


def _double_equal_ordered(a: float, b: float) -> bool:
    # ref: utils/common.h:845 CheckDoubleEqualOrdered
    return b <= math.nextafter(a, math.inf)


def _bounds_between(upper_bounds: List[float], lower_bounds: List[float],
                    bin_cnt: int) -> List[float]:
    """Upper bounds of `bin_cnt` bins from each bin's largest value and
    the next bin's smallest: the midpoint's successor, a bound within one
    ulp of the last dropped, infinity last (ref: bin.cpp:143-154)."""
    bin_upper_bound: List[float] = []
    for i in range(bin_cnt - 1):
        val = _next_after_up((upper_bounds[i] + lower_bounds[i + 1]) / 2.0)
        if not bin_upper_bound or not _double_equal_ordered(bin_upper_bound[-1], val):
            bin_upper_bound.append(val)
    bin_upper_bound.append(math.inf)
    return bin_upper_bound


def greedy_find_bin_loop(distinct_values: Sequence[float],
                         counts: Sequence[int], max_bin: int, total_cnt: int,
                         min_data_in_bin: int) -> List[float]:
    """Greedy equal-ish-frequency bin boundaries (ref: src/io/bin.cpp:78-155),
    as the reference writes them: one Python step per distinct value.
    `greedy_find_bin` below gives the same bounds from array operations;
    this one is what the tests hold it to."""
    num_distinct = len(distinct_values)
    bin_upper_bound: List[float] = []
    assert max_bin > 0
    if num_distinct == 0:
        return [math.inf]
    if num_distinct <= max_bin:
        cur_cnt_inbin = 0
        for i in range(num_distinct - 1):
            cur_cnt_inbin += counts[i]
            if cur_cnt_inbin >= min_data_in_bin:
                val = _next_after_up((distinct_values[i] + distinct_values[i + 1]) / 2.0)
                if not bin_upper_bound or not _double_equal_ordered(bin_upper_bound[-1], val):
                    bin_upper_bound.append(val)
                    cur_cnt_inbin = 0
        bin_upper_bound.append(math.inf)
        return bin_upper_bound

    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, total_cnt // min_data_in_bin))
    mean_bin_size = total_cnt / max_bin
    rest_bin_cnt = max_bin
    rest_sample_cnt = total_cnt
    is_big = [c >= mean_bin_size for c in counts]
    for i in range(num_distinct):
        if is_big[i]:
            rest_bin_cnt -= 1
            rest_sample_cnt -= counts[i]
    mean_bin_size = rest_sample_cnt / rest_bin_cnt if rest_bin_cnt else math.inf

    upper_bounds = [math.inf] * max_bin
    lower_bounds = [math.inf] * max_bin
    bin_cnt = 0
    lower_bounds[0] = distinct_values[0]
    cur_cnt_inbin = 0
    for i in range(num_distinct - 1):
        if not is_big[i]:
            rest_sample_cnt -= counts[i]
        cur_cnt_inbin += counts[i]
        if (is_big[i] or cur_cnt_inbin >= mean_bin_size or
                (is_big[i + 1] and cur_cnt_inbin >= max(1.0, mean_bin_size * 0.5))):
            upper_bounds[bin_cnt] = distinct_values[i]
            bin_cnt += 1
            lower_bounds[bin_cnt] = distinct_values[i + 1]
            if bin_cnt >= max_bin - 1:
                break
            cur_cnt_inbin = 0
            if not is_big[i]:
                rest_bin_cnt -= 1
                mean_bin_size = rest_sample_cnt / rest_bin_cnt
    return _bounds_between(upper_bounds, lower_bounds, bin_cnt + 1)


def find_bin_with_zero_as_one_bin_loop(distinct_values: Sequence[float],
                                       counts: Sequence[int], max_bin: int,
                                       total_sample_cnt: int,
                                       min_data_in_bin: int) -> List[float]:
    """Split negative/zero/positive ranges so zero gets its own bin
    (ref: src/io/bin.cpp:242-298), in Python steps: the tests' reference
    for `find_bin_with_zero_as_one_bin`."""
    num_distinct = len(distinct_values)
    left_cnt_data = cnt_zero = right_cnt_data = 0
    for v, c in zip(distinct_values, counts):
        if v <= -K_ZERO_THRESHOLD:
            left_cnt_data += c
        elif v > K_ZERO_THRESHOLD:
            right_cnt_data += c
        else:
            cnt_zero += c

    left_cnt = next((i for i, v in enumerate(distinct_values) if v > -K_ZERO_THRESHOLD),
                    num_distinct)

    bin_upper_bound: List[float] = []
    if left_cnt > 0 and max_bin > 1:
        denom = total_sample_cnt - cnt_zero
        left_max_bin = int(left_cnt_data / denom * (max_bin - 1)) if denom else 1
        left_max_bin = max(1, left_max_bin)
        bin_upper_bound = greedy_find_bin_loop(distinct_values[:left_cnt], counts[:left_cnt],
                                          left_max_bin, left_cnt_data, min_data_in_bin)
        if bin_upper_bound:
            bin_upper_bound[-1] = -K_ZERO_THRESHOLD

    right_start = next((i for i in range(left_cnt, num_distinct)
                        if distinct_values[i] > K_ZERO_THRESHOLD), -1)

    right_max_bin = max_bin - 1 - len(bin_upper_bound)
    if right_start >= 0 and right_max_bin > 0:
        right_bounds = greedy_find_bin_loop(distinct_values[right_start:], counts[right_start:],
                                       right_max_bin, right_cnt_data, min_data_in_bin)
        bin_upper_bound.append(K_ZERO_THRESHOLD)
        bin_upper_bound.extend(right_bounds)
    else:
        bin_upper_bound.append(math.inf)
    assert len(bin_upper_bound) <= max_bin
    return bin_upper_bound


def _int_at_least(x: float):
    """The least integer count c with `c >= x`, as the loops compare an
    int with a float (exactly); None where no count reaches x."""
    return math.ceil(x) if math.isfinite(x) else None


def greedy_find_bin(distinct_values: Sequence[float], counts: Sequence[int],
                    max_bin: int, total_cnt: int,
                    min_data_in_bin: int) -> List[float]:
    """`greedy_find_bin_loop`'s bounds, bit for bit, without a Python step
    per distinct value: between two bin closes the loop's state is a
    prefix sum, so each close is three binary searches (the next big
    count, the count that fills the bin, the big count a half-full bin
    stops before) and a feature costs at most `max_bin` steps whatever
    its sample holds — 200,000 distinct values at 2,000 features took
    the loop 0.19 s a feature (PERF.md, PR 30)."""
    dv = np.asarray(distinct_values, np.float64)
    cnt = np.asarray(counts, np.int64)
    num_distinct = len(dv)
    assert max_bin > 0
    if num_distinct <= max_bin:
        # at most max_bin steps: the loop as it is
        return greedy_find_bin_loop(dv.tolist(), cnt.tolist(), max_bin,
                                    total_cnt, min_data_in_bin)
    total_cnt = int(total_cnt)
    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, total_cnt // min_data_in_bin))
    mean_bin_size = total_cnt / max_bin
    is_big = cnt >= mean_bin_size
    big_idx = np.flatnonzero(is_big)
    rest_bin_cnt = max_bin - len(big_idx)
    rest_sample_cnt0 = total_cnt - int(cnt[big_idx].sum())
    mean_bin_size = (rest_sample_cnt0 / rest_bin_cnt if rest_bin_cnt
                     else math.inf)

    upto = np.concatenate([[0], np.cumsum(cnt)])     # upto[k] = sum cnt[:k]
    upto_big = upto[big_idx]
    small_upto = np.cumsum(np.where(is_big, 0, cnt))   # inclusive
    upper_bounds = [math.inf] * max_bin
    lower_bounds = [math.inf] * max_bin
    bin_cnt = 0
    lower_bounds[0] = float(dv[0])
    start = 0                   # first value of the open bin
    last = num_distinct - 2     # the loop's last step
    while start <= last:
        i = last + 1
        # is_big[i]
        k = int(np.searchsorted(big_idx, start))
        if k < len(big_idx):
            i = min(i, int(big_idx[k]))
        # cur_cnt_inbin >= mean_bin_size
        need = _int_at_least(mean_bin_size)
        if need is not None:
            at = int(np.searchsorted(upto, upto[start] + need))
            i = min(i, max(at - 1, start))
        # is_big[i + 1] and cur_cnt_inbin >= max(1.0, mean_bin_size * 0.5)
        need = _int_at_least(max(1.0, mean_bin_size * 0.5))
        if need is not None:
            k = max(int(np.searchsorted(upto_big, upto[start] + need)),
                    int(np.searchsorted(big_idx, start + 1)))
            if k < len(big_idx):
                i = min(i, int(big_idx[k]) - 1)
        if i > last:
            break
        upper_bounds[bin_cnt] = float(dv[i])
        bin_cnt += 1
        lower_bounds[bin_cnt] = float(dv[i + 1])
        if bin_cnt >= max_bin - 1:
            break
        if not is_big[i]:
            rest_bin_cnt -= 1
            mean_bin_size = ((rest_sample_cnt0 - int(small_upto[i]))
                             / rest_bin_cnt)
        start = i + 1
    return _bounds_between(upper_bounds, lower_bounds, bin_cnt + 1)


def find_bin_with_zero_as_one_bin(distinct_values: Sequence[float],
                                  counts: Sequence[int], max_bin: int,
                                  total_sample_cnt: int,
                                  min_data_in_bin: int) -> List[float]:
    """`find_bin_with_zero_as_one_bin_loop` from array operations: the
    same three counts, the same two ranges, `greedy_find_bin` on each."""
    dv = np.asarray(distinct_values, np.float64)
    cnt = np.asarray(counts, np.int64)
    num_distinct = len(dv)
    left_cnt_data = int(cnt[dv <= -K_ZERO_THRESHOLD].sum())
    right_cnt_data = int(cnt[dv > K_ZERO_THRESHOLD].sum())
    cnt_zero = int(cnt.sum()) - left_cnt_data - right_cnt_data

    not_left = np.flatnonzero(dv > -K_ZERO_THRESHOLD)
    left_cnt = int(not_left[0]) if len(not_left) else num_distinct

    bin_upper_bound: List[float] = []
    if left_cnt > 0 and max_bin > 1:
        denom = total_sample_cnt - cnt_zero
        left_max_bin = int(left_cnt_data / denom * (max_bin - 1)) if denom else 1
        left_max_bin = max(1, left_max_bin)
        bin_upper_bound = greedy_find_bin(dv[:left_cnt], cnt[:left_cnt],
                                          left_max_bin, left_cnt_data,
                                          min_data_in_bin)
        if bin_upper_bound:
            bin_upper_bound[-1] = -K_ZERO_THRESHOLD

    right = np.flatnonzero(dv[left_cnt:] > K_ZERO_THRESHOLD)
    right_start = left_cnt + int(right[0]) if len(right) else -1

    right_max_bin = max_bin - 1 - len(bin_upper_bound)
    if right_start >= 0 and right_max_bin > 0:
        right_bounds = greedy_find_bin(dv[right_start:], cnt[right_start:],
                                       right_max_bin, right_cnt_data,
                                       min_data_in_bin)
        bin_upper_bound.append(K_ZERO_THRESHOLD)
        bin_upper_bound.extend(right_bounds)
    else:
        bin_upper_bound.append(math.inf)
    assert len(bin_upper_bound) <= max_bin
    return bin_upper_bound


def _distinct_values_loop(svals: np.ndarray, zero_cnt: int):
    """Distinct values of the sorted sample with zero spliced into its
    sorted position, carrying the implied zero count (ref:
    bin.cpp:343-375), one Python step per value: the tests' reference
    for `_distinct_values`."""
    distinct_values: List[float] = []
    counts: List[int] = []
    if len(svals) == 0 or (svals[0] > 0.0 and zero_cnt > 0):
        distinct_values.append(0.0)
        counts.append(zero_cnt)
    if len(svals) > 0:
        distinct_values.append(float(svals[0]))
        counts.append(1)
    for i in range(1, len(svals)):
        prev, cur = float(svals[i - 1]), float(svals[i])
        if not _double_equal_ordered(prev, cur):
            if prev < 0.0 and cur > 0.0:
                distinct_values.append(0.0)
                counts.append(zero_cnt)
            distinct_values.append(cur)
            counts.append(1)
        else:
            distinct_values[-1] = cur  # use the larger value
            counts[-1] += 1
    if len(svals) > 0 and svals[-1] < 0.0 and zero_cnt > 0:
        distinct_values.append(0.0)
        counts.append(zero_cnt)
    return distinct_values, counts


def _distinct_values(svals: np.ndarray, zero_cnt: int):
    """`_distinct_values_loop` as arrays (float64 values, int64 counts):
    a run of values each within one ulp of the one before it
    (`_double_equal_ordered`) is one distinct value, its largest."""
    n = len(svals)
    if n == 0:
        return np.array([0.0]), np.array([zero_cnt], np.int64)
    starts = np.concatenate(
        [[0], np.flatnonzero(svals[1:] > np.nextafter(svals[:-1], np.inf))
         + 1])
    ends = np.concatenate([starts[1:], [n]])
    dv = svals[ends - 1]
    cnt = ends - starts
    # zero's place: before the first run, between the run that ends
    # negative and the one that starts positive, or after the last run
    at = None
    if svals[0] > 0.0:
        at = 0 if zero_cnt > 0 else None
    elif svals[-1] < 0.0:
        at = len(dv) if zero_cnt > 0 else None
    else:
        j = int(np.searchsorted(svals, 0.0, side="right"))  # first > 0
        if 0 < j < n and svals[j - 1] < 0.0:
            # a negative and a positive value are never one run
            at = int(np.searchsorted(starts, j))
    if at is not None:
        dv = np.insert(dv, at, 0.0)
        cnt = np.insert(cnt, at, zero_cnt)
    return dv, cnt


def _count_in_bins_loop(distinct_values, counts, bounds, num_bin: int):
    """Sample count of each bin, one Python step per distinct value: the
    tests' reference for `_count_in_bins`."""
    cnt_in_bin = [0] * num_bin
    i_bin = 0
    for v, c in zip(distinct_values, counts):
        while i_bin < num_bin - 1 and v > bounds[i_bin]:
            i_bin += 1
        cnt_in_bin[i_bin] += c
    return cnt_in_bin


def _count_in_bins(distinct_values, counts, bounds, num_bin: int):
    """`_count_in_bins_loop` as one search: values and bounds both
    ascend, so a value's bin is the number of bounds below it."""
    bins = np.searchsorted(np.asarray(bounds)[:num_bin - 1],
                           distinct_values, side="left")
    # float64 sums of integer counts: exact below 2**53
    return np.bincount(bins, weights=counts,
                       minlength=num_bin).astype(np.int64).tolist()


def find_bin_with_predefined_bin(distinct_values: Sequence[float],
                                 counts: Sequence[int], max_bin: int,
                                 total_sample_cnt: int, min_data_in_bin: int,
                                 forced_upper_bounds: Sequence[float],
                                 greedy=None) -> List[float]:
    """Forced bin upper bounds (forcedbins_filename), remaining bins
    allocated greedily per forced interval in proportion to its sample
    count (ref: src/io/bin.cpp:157-240 FindBinWithPredefinedBin)."""
    num_distinct = len(distinct_values)
    left_cnt = next((i for i, v in enumerate(distinct_values)
                     if v > -K_ZERO_THRESHOLD), num_distinct)
    right_start = next((i for i in range(left_cnt, num_distinct)
                        if distinct_values[i] > K_ZERO_THRESHOLD), -1)

    # zero bounds and the infinity bound come first (zero keeps its own
    # bin exactly like FindBinWithZeroAsOneBin)
    bin_upper_bound: List[float] = []
    if max_bin == 2:
        bin_upper_bound.append(K_ZERO_THRESHOLD if left_cnt == 0
                               else -K_ZERO_THRESHOLD)
    elif max_bin >= 3:
        if left_cnt > 0:
            bin_upper_bound.append(-K_ZERO_THRESHOLD)
        if right_start >= 0:
            bin_upper_bound.append(K_ZERO_THRESHOLD)
    bin_upper_bound.append(math.inf)

    # forced bounds, excluding zeros (already bounded above)
    max_to_insert = max_bin - len(bin_upper_bound)
    num_inserted = 0
    for v in forced_upper_bounds:
        if num_inserted >= max_to_insert:
            break
        if abs(v) > K_ZERO_THRESHOLD:
            bin_upper_bound.append(float(v))
            num_inserted += 1
    bin_upper_bound.sort()

    # remaining bins: greedy inside each forced interval, proportional to
    # its sample count; the last interval takes every remaining bin
    free_bins = max_bin - len(bin_upper_bound)
    bounds_to_add: List[float] = []
    value_ind = 0
    for i in range(len(bin_upper_bound)):
        cnt_in_bin = 0
        distinct_cnt_in_bin = 0
        bin_start = value_ind
        while (value_ind < num_distinct
               and distinct_values[value_ind] < bin_upper_bound[i]):
            cnt_in_bin += counts[value_ind]
            distinct_cnt_in_bin += 1
            value_ind += 1
        bins_remaining = (max_bin - len(bin_upper_bound)
                          - len(bounds_to_add))
        # std::lround (half away from zero; operand is non-negative)
        num_sub_bins = int(math.floor(cnt_in_bin * free_bins
                                      / total_sample_cnt + 0.5))
        num_sub_bins = min(num_sub_bins, bins_remaining) + 1
        if i == len(bin_upper_bound) - 1:
            num_sub_bins = bins_remaining + 1
        new_bounds = (greedy or greedy_find_bin)(
            distinct_values[bin_start:bin_start + distinct_cnt_in_bin],
            counts[bin_start:bin_start + distinct_cnt_in_bin],
            num_sub_bins, cnt_in_bin, min_data_in_bin)
        bounds_to_add.extend(new_bounds[:-1])   # last bound is infinity
    bin_upper_bound.extend(bounds_to_add)
    bin_upper_bound.sort()
    assert len(bin_upper_bound) <= max_bin
    return bin_upper_bound


def _need_filter(cnt_in_bin: List[int], total_cnt: int, filter_cnt: int,
                 bin_type: int) -> bool:
    """Pre-filter features that can never produce a valid split
    (ref: src/io/bin.cpp:33-76 NeedFilter)."""
    if bin_type == BIN_NUMERICAL:
        sum_left = 0
        for i in range(len(cnt_in_bin) - 1):
            sum_left += cnt_in_bin[i]
            if sum_left >= filter_cnt and total_cnt - sum_left >= filter_cnt:
                return False
        return True
    else:
        if len(cnt_in_bin) <= 2:
            for i in range(len(cnt_in_bin) - 1):
                sum_left = cnt_in_bin[i]
                if sum_left >= filter_cnt and total_cnt - sum_left >= filter_cnt:
                    return False
            return True
        return False


def prep_find_bin_values(col: np.ndarray) -> np.ndarray:
    """Sample column -> the `values` array find_bin expects: non-zero
    finite values followed by the NaNs; zeros are implied by
    total_sample_cnt - len(values) (find_bin's contract — keep every
    caller on this one helper so the zero/NaN sampling convention cannot
    diverge between the single-host and distributed binning paths)."""
    col = np.asarray(col, np.float64)
    nonzero = col[~((col == 0) | np.isnan(col))]
    nan_vals = col[np.isnan(col)]
    return np.concatenate([nonzero, nan_vals])


class BinMapper:
    """Per-feature value->bin mapping (ref: include/LightGBM/bin.h:84)."""

    def __init__(self):
        self.num_bin: int = 1
        self.missing_type: int = MISSING_NONE
        self.is_trivial: bool = True
        self.sparse_rate: float = 1.0
        self.bin_type: int = BIN_NUMERICAL
        self.min_val: float = 0.0
        self.max_val: float = 0.0
        self.default_bin: int = 0
        self.most_freq_bin: int = 0
        self.bin_upper_bound: np.ndarray = np.array([np.inf])
        self.bin_2_categorical: List[int] = []
        self.categorical_2_bin: Dict[int, int] = {}

    # -- construction ------------------------------------------------------
    def find_bin(self, values: np.ndarray, total_sample_cnt: int, max_bin: int,
                 min_data_in_bin: int = 3, min_split_data: int = 20,
                 pre_filter: bool = False, bin_type: int = BIN_NUMERICAL,
                 use_missing: bool = True, zero_as_missing: bool = False,
                 forced_upper_bounds: Optional[Sequence[float]] = None,
                 reference_loops: bool = False) -> None:
        """Build the mapping from sampled values (ref: src/io/bin.cpp:311-506).

        `values` are the sampled non-zero values; zeros are implied by
        total_sample_cnt - len(values).  `reference_loops` takes the
        reference's own loops (one Python step per distinct value) where
        the default takes array operations: the same mapping bit for bit,
        for the tests to compare.
        """
        values = np.asarray(values, dtype=np.float64)
        num_sample_values = len(values)
        non_na = values[~np.isnan(values)]
        na_cnt = 0
        if not use_missing:
            self.missing_type = MISSING_NONE
        elif zero_as_missing:
            self.missing_type = MISSING_ZERO
        else:
            if len(non_na) == num_sample_values:
                self.missing_type = MISSING_NONE
            else:
                self.missing_type = MISSING_NAN
                na_cnt = num_sample_values - len(non_na)

        self.bin_type = bin_type
        self.default_bin = 0
        zero_cnt = int(total_sample_cnt - len(non_na) - na_cnt)

        # distinct values with zero spliced into its sorted position,
        # carrying the implied zero count (ref: bin.cpp:343-375).  The
        # default sort, not a stable one (12x its time at 200,000
        # values): equal floats are indistinguishable but for the sign of
        # a zero, and zeros are not among `values`
        svals = np.sort(non_na)
        if reference_loops:
            distinct_values, counts = _distinct_values_loop(svals, zero_cnt)
        else:
            distinct_values, counts = _distinct_values(svals, zero_cnt)
        if bin_type != BIN_NUMERICAL or forced_upper_bounds:
            # the categorical scan and FindBinWithPredefinedBin walk lists
            distinct_values = list(map(float, distinct_values))
            counts = list(map(int, counts))
        self.min_val = float(distinct_values[0])
        self.max_val = float(distinct_values[-1])
        num_distinct = len(distinct_values)
        cnt_in_bin: List[int] = []

        if bin_type == BIN_NUMERICAL:
            forced = list(forced_upper_bounds) if forced_upper_bounds else []

            def _find(mb, tc):
                # ref: bin.cpp:302-309 FindBin dispatch — forced bounds
                # select FindBinWithPredefinedBin
                if forced:
                    return find_bin_with_predefined_bin(
                        distinct_values, counts, mb, tc, min_data_in_bin,
                        forced, greedy=(greedy_find_bin_loop
                                        if reference_loops else None))
                zero_as_one_bin = (find_bin_with_zero_as_one_bin_loop
                                   if reference_loops
                                   else find_bin_with_zero_as_one_bin)
                return zero_as_one_bin(distinct_values, counts, mb, tc,
                                       min_data_in_bin)

            if self.missing_type == MISSING_ZERO:
                bounds = _find(max_bin, total_sample_cnt)
                if len(bounds) == 2:
                    self.missing_type = MISSING_NONE
            elif self.missing_type == MISSING_NONE:
                bounds = _find(max_bin, total_sample_cnt)
            else:  # NaN: last bin reserved for missing (ref: bin.cpp:391-394)
                bounds = _find(max_bin - 1, total_sample_cnt - na_cnt)
                bounds = bounds + [math.nan]
            self.bin_upper_bound = np.array(bounds, dtype=np.float64)
            self.num_bin = len(bounds)
            count_in_bins = (_count_in_bins_loop if reference_loops
                             else _count_in_bins)
            cnt_in_bin = count_in_bins(distinct_values, counts,
                                       self.bin_upper_bound, self.num_bin)
            if self.missing_type == MISSING_NAN:
                cnt_in_bin[self.num_bin - 1] = na_cnt
            assert self.num_bin <= max_bin
        else:
            # categorical: count-sorted category->bin, bin 0 = NaN/other
            # (ref: bin.cpp:410-477)
            dv_int: List[int] = []
            cnt_int: List[int] = []
            for v, c in zip(distinct_values, counts):
                iv = int(v)
                if iv < 0:
                    na_cnt += c
                    log.warning("Met negative value in categorical features, "
                                "will convert it to NaN")
                elif dv_int and iv == dv_int[-1]:
                    cnt_int[-1] += c
                else:
                    dv_int.append(iv)
                    cnt_int.append(c)
            rest_cnt = total_sample_cnt - na_cnt
            if rest_cnt > 0 and dv_int:
                order = sorted(range(len(dv_int)), key=lambda i: (-cnt_int[i], i))
                dv_int = [dv_int[i] for i in order]
                cnt_int = [cnt_int[i] for i in order]
                cut_cnt = int(round((total_sample_cnt - na_cnt) * 0.99))
                distinct_cnt = len(dv_int) + (1 if na_cnt > 0 else 0)
                eff_max_bin = min(distinct_cnt, max_bin)
                self.bin_2_categorical = [-1]
                self.categorical_2_bin = {-1: 0}
                cnt_in_bin = [0]
                self.num_bin = 1
                used_cnt = 0
                cur = 0
                while cur < len(dv_int) and (used_cnt < cut_cnt or self.num_bin < eff_max_bin):
                    if cnt_int[cur] < min_data_in_bin and cur > 1:
                        break
                    self.bin_2_categorical.append(dv_int[cur])
                    self.categorical_2_bin[dv_int[cur]] = self.num_bin
                    used_cnt += cnt_int[cur]
                    cnt_in_bin.append(cnt_int[cur])
                    self.num_bin += 1
                    cur += 1
                if cur == len(dv_int) and na_cnt == 0:
                    self.missing_type = MISSING_NONE
                else:
                    self.missing_type = MISSING_NAN
                cnt_in_bin[0] = total_sample_cnt - used_cnt

        self.is_trivial = self.num_bin <= 1
        if not self.is_trivial and pre_filter and _need_filter(
                cnt_in_bin, total_sample_cnt, min_split_data, bin_type):
            self.is_trivial = True

        if not self.is_trivial:
            self.default_bin = int(self.value_to_bin(0.0))
            self.most_freq_bin = int(np.argmax(cnt_in_bin))
            max_sparse_rate = cnt_in_bin[self.most_freq_bin] / total_sample_cnt
            if self.most_freq_bin != self.default_bin and max_sparse_rate < K_SPARSE_THRESHOLD:
                self.most_freq_bin = self.default_bin
            self.sparse_rate = cnt_in_bin[self.most_freq_bin] / total_sample_cnt
        else:
            self.sparse_rate = 1.0

    # -- mapping -----------------------------------------------------------
    def value_to_bin(self, value: float) -> int:
        """Scalar value->bin (ref: bin.h:611-647)."""
        return int(self.values_to_bins(np.array([value]))[0])

    def values_to_bins(self, values: np.ndarray) -> np.ndarray:
        """Vectorized value->bin for a full column."""
        values = np.asarray(values, dtype=np.float64)
        out = np.zeros(len(values), dtype=np.int32)
        nan_mask = np.isnan(values)
        if self.bin_type == BIN_CATEGORICAL:
            iv = np.where(nan_mask, -1, values).astype(np.int64)
            cats = np.array(sorted(self.categorical_2_bin), dtype=np.int64)
            bins = np.array([self.categorical_2_bin[c] for c in cats], dtype=np.int32)
            pos = np.searchsorted(cats, iv)
            pos = np.clip(pos, 0, len(cats) - 1)
            hit = (cats[pos] == iv) & (iv >= 0)
            return np.where(hit, bins[pos], 0).astype(np.int32)
        vals = values.copy()
        if self.missing_type != MISSING_NAN:
            vals = np.where(nan_mask, 0.0, vals)
        n_search = self.num_bin - (1 if self.missing_type == MISSING_NAN else 0)
        # bin = first index with value <= upper_bound  (upper bounds ascending)
        bounds = self.bin_upper_bound[:n_search - 1] if n_search > 0 else np.array([])
        out = np.searchsorted(bounds, vals, side="left").astype(np.int32)
        # searchsorted 'left' gives first idx with bounds[idx] >= v; reference uses
        # v <= bound, identical for total order except exact equality, which matches.
        if self.missing_type == MISSING_NAN:
            out = np.where(nan_mask, self.num_bin - 1, out).astype(np.int32)
        return out

    def bin_to_value(self, bin_idx: int) -> float:
        """Representative threshold value for a bin (used in model text output;
        ref: tree.cpp RealThreshold via bin_upper_bound)."""
        if self.bin_type == BIN_CATEGORICAL:
            return float(self.bin_2_categorical[bin_idx])
        return float(self.bin_upper_bound[bin_idx])

    @property
    def missing_type_str(self) -> str:
        return _MISSING_TYPE_STR[self.missing_type]

    # -- serialization (model text "feature_infos" + binary) ---------------
    def feature_info_str(self) -> str:
        """Model-text feature info (ref: gbdt_model_text.cpp DumpModel feature_infos)."""
        if self.is_trivial:
            return "none"
        if self.bin_type == BIN_CATEGORICAL:
            cats = sorted(c for c in self.bin_2_categorical if c >= 0)
            return "[" + ":".join(str(c) for c in cats) + "]"
        return f"[{self.min_val:g}:{self.max_val:g}]"

    def to_dict(self) -> dict:
        return {
            "num_bin": self.num_bin,
            "missing_type": self.missing_type,
            "is_trivial": self.is_trivial,
            "sparse_rate": self.sparse_rate,
            "bin_type": self.bin_type,
            "min_val": self.min_val,
            "max_val": self.max_val,
            "default_bin": self.default_bin,
            "most_freq_bin": self.most_freq_bin,
            "bin_upper_bound": [float(x) for x in self.bin_upper_bound],
            "bin_2_categorical": list(self.bin_2_categorical),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "BinMapper":
        m = cls()
        m.num_bin = int(d["num_bin"])
        m.missing_type = int(d["missing_type"])
        m.is_trivial = bool(d["is_trivial"])
        m.sparse_rate = float(d["sparse_rate"])
        m.bin_type = int(d["bin_type"])
        m.min_val = float(d["min_val"])
        m.max_val = float(d["max_val"])
        m.default_bin = int(d["default_bin"])
        m.most_freq_bin = int(d["most_freq_bin"])
        m.bin_upper_bound = np.array(d["bin_upper_bound"], dtype=np.float64)
        m.bin_2_categorical = [int(x) for x in d.get("bin_2_categorical", [])]
        m.categorical_2_bin = {c: i for i, c in enumerate(m.bin_2_categorical)}
        return m
