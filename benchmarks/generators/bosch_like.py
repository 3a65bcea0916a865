"""Bosch-shaped binary-classification rows from a seed: 968 numeric
measurements in station blocks, about 81% of the cells missing.

Upstream's GPU benchmark table (LightGBM docs/GPU-Performance.rst) has a
Bosch row, 1,000,000 x 968, binary: Kaggle's "Bosch Production Line
Performance" `train_numeric.csv`.  Its columns are anonymised
measurements named `L<line>_S<station>_F<n>`, 4 lines and 52 stations
(L0 S0-S23, L1 S24-S25, L2 S26-S28, L3 S29-S51), written to 3 decimals;
`Response` is 1 for 6,879 of 1,183,747 parts (0.581%), and about 81% of
the cells are empty because a part visits only some stations.  The file
cannot be fetched where this runs, so the rows are drawn with those
shapes; quality is comparable only between runs of this generator.

* **Stations.** Each line's columns (`LINE_COLUMNS`, assumed) are split
  evenly over its stations.  A part takes a route, the set of stations
  it visits: L0 (one station of each of its 12 pairs, by one of four
  patterns) or L1 (one of its two stations) at the front, L2 (two of its
  three stations) for some parts, and one of six fixed subsets of L3's
  stations, all of which hold L3's first station, which a sixteenth of
  the parts skip whatever their route.  Every column of a station the route skips is NaN on that
  row; a visited station's column is NaN with the small probability
  `visited_nan_rate()`, set so that `NAN_SHARE` of all cells are NaN.
* **Values** lie on the file's 0.001 grid.  A quarter of the columns
  (`FEW_LEVEL_SHARE`, assumed) take 2-15 levels; the rest are rounded
  triangular draws (a normal's bell at a third of its cost) with hundreds
  of distinct values.
* **Label.** A logistic model of `INFORMATIVE` columns, each in a
  station of its own: a visited row adds its column's weight times its
  standardised value, a row that skipped the station adds the column's
  missing effect instead.  For half of them a missing value carries more
  risk than any usual value, for the other half less, so a split on one
  sends NaN right (the gain scan's forward pass wins) or left.  The
  intercept is solved on each draw's own rows so that the mean
  probability is `POSITIVE_RATE`.

The layout (stations, levels, routes, label weights) is FIXED across
seeds, so training and held-out rows share it; `seed` draws the rows.
Drawn in row blocks from numpy's SFC64 stream in float32, so 1,000,000
rows cost seconds of host time and no float64 copy of the matrix exists.
"""

import functools

import numpy as np

FEATURES = 968
LINE_STATIONS = ((0, 24), (24, 26), (26, 29), (29, 52))
LINE_COLUMNS = (168, 513, 42, 245)      # assumed: the file's split is not public
STATIONS = 52
NAN_SHARE = 0.81
POSITIVE_RATE = 0.00581
FEW_LEVEL_SHARE = 0.25
INFORMATIVE = 16
BLOCK_ROWS = 32768          # 127 MB of float32 a block
FRONT_L0 = 0.88             # the share of parts whose front line is L0 (else L1)
L0_PATTERN_P = (0.4, 0.3, 0.2, 0.1)
L2_VISIT = 0.35
L3_PATTERN_P = (0.3, 0.25, 0.18, 0.12, 0.09, 0.06)
L3_VISIT = 0.2             # a station's chance to be in an L3 pattern
L3_FIRST_SKIP = 1 / 16      # L3's first station: skipped apart from the pattern


@functools.lru_cache(maxsize=1)
def layout():
    """The fixed shape of the data: station of each column, route
    patterns, value distributions, label weights."""
    rs = np.random.RandomState(2016)
    station = np.empty(FEATURES, np.int32)
    start = 0
    for (s0, s1), cols in zip(LINE_STATIONS, LINE_COLUMNS):
        for s, part in zip(range(s0, s1),
                           np.array_split(np.arange(cols), s1 - s0)):
            station[start + part] = s
        start += cols
    # route patterns over the stations
    l0_pairs = np.arange(0, 24, 2)
    l0_patterns = np.zeros((len(L0_PATTERN_P), STATIONS), bool)
    for k in range(0, len(L0_PATTERN_P), 2):     # two complementary pairs
        first = rs.randint(0, 2, len(l0_pairs))
        l0_patterns[k, l0_pairs + first] = True
        l0_patterns[k + 1, l0_pairs + 1 - first] = True
    l3 = np.arange(*LINE_STATIONS[3])
    l3_patterns = rs.random_sample((len(L3_PATTERN_P), len(l3))) < L3_VISIT
    # a station no pattern visits joins the rarest pattern; L3's first
    # station is in every pattern (`_routes` skips it apart)
    l3_patterns[-1] |= ~l3_patterns.any(axis=0)
    l3_patterns[:, 0] = True
    l3_patterns = np.concatenate([
        np.zeros((len(L3_PATTERN_P), l3[0]), bool), l3_patterns], axis=1)
    # values: a quarter of the columns few-level, the rest rounded draws
    few = np.zeros(FEATURES, bool)
    few[rs.choice(FEATURES, int(FEATURES * FEW_LEVEL_SHARE),
                  replace=False)] = True
    loc = np.round(rs.uniform(-0.2, 0.2, FEATURES), 3)
    scale = rs.uniform(0.03, 0.12, FEATURES)
    few_idx = np.nonzero(few)[0]
    # a few-level column takes k equally spaced levels on the grid, the
    # lowest the likeliest: level floor(k * u^2) of a uniform u
    few_k = rs.randint(2, 16, len(few_idx)).astype(np.float32)
    few_step = rs.randint(1, 41, len(few_idx)).astype(np.float32)
    few_base = rs.randint(-300, 1, len(few_idx)).astype(np.float32)
    # label: the strongest column in L3's first station (skipped by a
    # sixteenth of the parts, whatever their route), the others in distinct stations that
    # between a fifth and four fifths of the parts visit
    visit = _visit_share(l0_patterns, l3_patterns)
    first = np.nonzero(~few & (station == l3[0]))[0]
    cont = np.nonzero(~few & (visit[station] >= 0.2)
                      & (visit[station] <= 0.8) & (station != l3[0]))[0]
    rs.shuffle(cont)
    informative, seen = [first[0]], set()
    for j in cont:
        if station[j] not in seen:
            informative.append(j)
            seen.add(station[j])
        if len(informative) == INFORMATIVE:
            break
    informative = np.array(informative)
    weight = rs.uniform(0.6, 1.2, INFORMATIVE) * rs.choice((-1, 1),
                                                           INFORMATIVE)
    # half: a skipped station carries more risk than any usual value,
    # half less; the first (the strongest) more, so that a split on it
    # sends its few NaN right with its high values: the root's best split
    # comes from the forward scan
    riskier = np.arange(INFORMATIVE) % 2 == 0
    missing_effect = np.where(riskier, 1.0, -1.0) * 1.2 * np.abs(weight)
    weight[0], missing_effect[0] = 3.0, 6.0
    return {"station": station, "l0_patterns": l0_patterns,
            "l3_patterns": l3_patterns, "few": few, "few_idx": few_idx,
            "few_k": few_k, "few_step": few_step, "few_base": few_base,
            "loc": loc.astype(np.float32),
            "scale": scale.astype(np.float32),
            "spread": (np.sqrt(6.0) * scale).astype(np.float32),
            "informative": informative,
            "weight": weight, "missing_effect": missing_effect}


def _visit_share(l0_patterns, l3_patterns):
    """[52]: the chance that a part's route visits each station."""
    share = np.zeros(STATIONS)
    share += FRONT_L0 * np.dot(L0_PATTERN_P, l0_patterns)
    share[np.arange(*LINE_STATIONS[1])] = (1 - FRONT_L0) / 2
    first = LINE_STATIONS[2][0]      # L2: `first` and `first + 1`, first of two
    share[first:first + 3] = L2_VISIT * np.array([0.5, 1.0, 0.5])
    share += np.dot(L3_PATTERN_P, l3_patterns)
    share[LINE_STATIONS[3][0]] *= 1 - L3_FIRST_SKIP
    return share


def station_visit_share():
    """[52]: the chance that a part's route visits each station."""
    lay = layout()
    return _visit_share(lay["l0_patterns"], lay["l3_patterns"])


def station_columns():
    """Columns a station holds: [52] counts."""
    return np.bincount(layout()["station"], minlength=STATIONS)


def expected_visited_share():
    """The share of cells whose station the route visits (exact, from
    the patterns)."""
    return float(np.dot(station_visit_share(), station_columns())
                 / FEATURES)


def visited_nan_rate():
    """The chance that a visited station's column is NaN, so that
    `NAN_SHARE` of all cells are."""
    return 1.0 - (1.0 - NAN_SHARE) / expected_visited_share()


def _routes(rng, n):
    """[n, 52] bool: the stations each of `n` parts visits."""
    lay = layout()
    out = np.zeros((n, STATIONS), bool)
    front_l0 = rng.random(n) < FRONT_L0
    pat = np.searchsorted(np.cumsum(L0_PATTERN_P)[:-1], rng.random(n),
                          side="right")
    out |= lay["l0_patterns"][pat] & front_l0[:, None]
    l1 = LINE_STATIONS[1][0] + rng.integers(0, 2, n)
    out[np.nonzero(~front_l0)[0], l1[~front_l0]] = True
    l2 = rng.random(n) < L2_VISIT
    first = LINE_STATIONS[2][0] + rng.integers(0, 2, n)
    rows = np.nonzero(l2)[0]
    out[rows, first[l2]] = True
    out[rows, first[l2] + 1] = True
    pat = np.searchsorted(np.cumsum(L3_PATTERN_P)[:-1], rng.random(n),
                          side="right")
    out |= lay["l3_patterns"][pat]
    out[:, LINE_STATIONS[3][0]] &= rng.random(n) >= L3_FIRST_SKIP
    return out


def _intercept(z):
    """b such that the mean of sigmoid(z + b) is `POSITIVE_RATE`
    (bisection on a monotone function)."""
    lo, hi = -30.0, 10.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(z + mid)))) < POSITIVE_RATE:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def probability(X):
    """Each row's chance to be positive, from the informative columns of
    `X` (in this generator's column order), the intercept solved on
    these rows."""
    lay = layout()
    inf = lay["informative"]
    x = X[:, inf].astype(np.float64)
    std = np.clip((x - lay["loc"][inf]) / lay["scale"][inf], -3, 3)
    z = np.where(np.isnan(x), lay["missing_effect"],
                 lay["weight"] * std).sum(axis=1)
    return 1.0 / (1.0 + np.exp(-(z + _intercept(z))))


def make(rows, features=FEATURES, seed=0):
    """(X [rows, 968] float32 with NaN, y [rows] float32 in {0, 1})."""
    if features != FEATURES:
        raise ValueError(f"bosch_like draws {FEATURES} columns, "
                         f"not {features}")
    lay = layout()
    rng = np.random.Generator(np.random.SFC64(seed))
    nan_rate = np.float32(visited_nan_rate())
    few_idx = lay["few_idx"]
    X = np.empty((rows, FEATURES), np.float32)
    # reused a block: first touches of fresh pages cost more than the draws
    draw = np.empty((min(rows, BLOCK_ROWS), FEATURES), np.float32)
    kept = np.empty((min(rows, BLOCK_ROWS), FEATURES), bool)
    few_draw = np.empty((min(rows, BLOCK_ROWS), len(few_idx)), np.float32)
    for r0 in range(0, rows, BLOCK_ROWS):
        blk = X[r0:r0 + BLOCK_ROWS]
        n = blk.shape[0]
        u, keep = draw[:n], kept[:n]
        # a triangular draw on (-1, 1) (two uniforms: a third of a
        # normal's time) at the column's scale, on the 0.001 grid
        rng.random(out=blk, dtype=np.float32)
        rng.random(out=u, dtype=np.float32)
        blk += u
        blk -= np.float32(1)
        blk *= lay["spread"]
        blk += lay["loc"]
        blk *= np.float32(1000)
        np.rint(blk, out=blk)
        blk /= np.float32(1000)
        few = few_draw[:n]
        rng.random(out=few, dtype=np.float32)
        few *= few
        few *= lay["few_k"]
        np.floor(few, out=few)
        few *= lay["few_step"]
        few += lay["few_base"]
        few /= np.float32(1000)
        blk[:, few_idx] = few
        np.take(_routes(rng, n), lay["station"], axis=1, out=keep)
        rng.random(out=u, dtype=np.float32)
        keep &= u >= nan_rate
        np.copyto(blk, np.float32(np.nan), where=~keep)
    y = (rng.random(rows) < probability(X)).astype(np.float32)
    return X, y
