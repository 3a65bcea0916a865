"""Expo-shaped one-hot rows from a seed, handed over as scipy CSR.

Upstream's "Expo" benchmark (LightGBM docs/Experiments.rst: 11,000,000 x
700, binary, "the categorical features are one-hot coded"; the EFB
paper's "Flight Delay") is the Data Expo 2009 airline on-time set with
eight input columns: month, day of month, day of week, carrier, origin,
destination (categorical, one-hot coded) and departure time, distance
(numeric); the label is "delayed 15 minutes or more".  The file cannot
be fetched where this runs, so rows are drawn:

* six one-hot groups of 12 / 31 / 7 / 22 / 313 / 313 columns and two
  numeric columns: 12 + 31 + 7 + 22 + 313 + 313 + 2 = 700.  Every row
  stores exactly 8 values: a 1.0 in each group and its two numerics
  (scheduled departure as hhmm, 1-2359; distance in miles, log-normal
  with a heavy right tail, at least 11), neither ever 0;
* keys skewed as real ones are: carriers Zipf (exponent 1), airports
  Zipf-Mandelbrot `1 / (k + 3)` with an exponential tail past rank 150
  (`exp(-(k - 150) / 30)`), so the largest airport holds ~6% of the
  rows and the smallest a few dozen of 11,000,000: a long tail of
  columns with a few hundred rows or fewer, which the Dataset's
  pre-filter drops as it would the real set's.  The destination depends
  on the origin: half the flights go to one of the origin's eight
  partner hubs, the rest anywhere by the same law;
* the label is a FIXED logistic (train and held-out rows share it) of
  the hour (delays build through the day), per-carrier / per-airport /
  per-month / per-weekday effects, origin-hub x evening and carrier x month interactions, a distance
  term and per-flight noise, with ~20% positive.

`order` is the permutation `benchmarks/data.py column_order` draws from
`--seed` (new column j is column `order[j]` of the layout above): it is
written into the CSR's indices and decides nothing else.  Quality is
comparable only between runs of this generator.
"""

import numpy as np
from scipy import sparse

GROUPS = (12, 31, 7, 22, 313, 313)     # month, day, weekday, carrier, origin, dest
NUMERIC = 2                            # departure time, distance
FEATURES = sum(GROUPS) + NUMERIC       # 700
STORED_PER_ROW = len(GROUPS) + NUMERIC
STARTS = np.concatenate([[0], np.cumsum(GROUPS)]).astype(np.int32)
DAYS = np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31], np.float32)
HUBS = 40                              # partner hubs are among the largest airports
PARTNERS = 8
INTERCEPT = np.float32(-2.10)          # ~20% positive; read once off 1,000,000 rows, FIXED


def _airport_law():
    k = np.arange(1, GROUPS[4] + 1, dtype=np.float64)
    p = 1.0 / (k + 3.0)
    p[k > 150] *= np.exp(-(k[k > 150] - 150.0) / 30.0)
    return p / p.sum()


def _zipf(n):
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64)
    return p / p.sum()


def _draw(rng, law, n):
    """`n` keys from the law `law` (probabilities by key)."""
    cdf = np.cumsum(law).astype(np.float32)
    cdf[-1] = 1.0
    return np.searchsorted(cdf, rng.random(n, dtype=np.float32),
                           side="right").astype(np.int32)


def _effects():
    """The label function's tables: fixed, whatever the seed."""
    rs = np.random.RandomState(2009)
    return dict(
        month=rs.normal(0, 0.25, 12), weekday=rs.normal(0, 0.15, 7),
        carrier=rs.normal(0, 0.35, GROUPS[3]),
        origin=rs.normal(0, 0.30, GROUPS[4]),
        dest=rs.normal(0, 0.20, GROUPS[5]),
        hub_evening=rs.normal(0.5, 0.25, HUBS),
        carrier_month=rs.normal(0, 0.25, (GROUPS[3], 12)))


def make(rows, features, seed, order=None):
    """(X, y): `rows` x 700 scipy CSR float32 with 8 stored values a row,
    and the float32 labels."""
    if features != FEATURES:
        raise ValueError(f"expo_like draws {FEATURES} columns, not {features}")
    n = int(rows)
    rng = np.random.Generator(np.random.SFC64(seed))
    f32 = np.float32

    month = _draw(rng, np.full(12, 1 / 12.0), n)
    day = (rng.random(n, dtype=f32) * DAYS[month]).astype(np.int32)
    weekday = _draw(rng, np.array([.148, .146, .147, .148, .148, .125, .138]), n)
    carrier = _draw(rng, _zipf(GROUPS[3]), n)
    airports = _airport_law()
    origin = _draw(rng, airports, n)
    dest = _draw(rng, airports, n)
    partner = (origin * 5 + _draw(rng, _zipf(PARTNERS), n) ** 2 + 1) % HUBS
    dest = np.where(rng.random(n, dtype=f32) < 0.5, partner, dest)
    dest = np.where(dest == origin, (dest + 1) % GROUPS[5], dest)

    # scheduled departure: a morning and an evening bank over a flat day
    u = rng.random(n, dtype=f32)
    hour = np.where(u < 0.35, 8.0 + 1.8 * rng.standard_normal(n, dtype=f32),
                    np.where(u < 0.70,
                             17.5 + 2.0 * rng.standard_normal(n, dtype=f32),
                             5.0 + 18.0 * rng.random(n, dtype=f32)))
    hour = np.clip(hour, 0.02, 23.98).astype(f32)
    whole = np.floor(hour)
    dep_time = np.maximum(whole * 100 + np.floor((hour - whole) * 60), 1
                          ).astype(f32)
    distance = np.maximum(np.rint(np.exp(
        6.4 + 0.75 * rng.standard_normal(n, dtype=f32))), 11).astype(f32)

    eff = _effects()
    evening = np.clip((hour - 15.0) / 6.0, 0.0, 1.0)
    logit = (INTERCEPT
             + 1.2 * np.clip((hour - 6.0) / 15.0, 0.0, 1.0) ** 1.5
             + eff["month"][month] + eff["weekday"][weekday]
             + eff["carrier"][carrier] + eff["origin"][origin]
             + eff["dest"][dest]
             + np.where(origin < HUBS, eff["hub_evening"][origin % HUBS], 0.0)
             * evening
             + eff["carrier_month"][carrier, month]
             + 0.15 * (np.log(distance) - 6.4)
             + 0.5 * rng.standard_normal(n, dtype=f32))
    y = (rng.random(n, dtype=f32)
         < 1.0 / (1.0 + np.exp(-logit.astype(f32)))).astype(f32)

    indices = np.empty((n, STORED_PER_ROW), np.int32)
    for j, key in enumerate((month, day, weekday, carrier, origin, dest)):
        indices[:, j] = STARTS[j] + key
    indices[:, 6] = STARTS[6]
    indices[:, 7] = STARTS[6] + 1
    data = np.ones((n, STORED_PER_ROW), f32)
    data[:, 6] = dep_time
    data[:, 7] = distance
    if order is not None:
        # new column j is old column order[j]: an old column goes to the
        # place the permutation's inverse names
        indices = np.argsort(np.asarray(order)).astype(np.int32)[indices]
    X = sparse.csr_matrix(
        (data.ravel(), indices.ravel(),
         np.arange(0, STORED_PER_ROW * (n + 1), STORED_PER_ROW,
                   dtype=np.int64 if STORED_PER_ROW * n >= 2 ** 31
                   else np.int32)),
        shape=(n, FEATURES))
    X.sort_indices()
    return X, y
