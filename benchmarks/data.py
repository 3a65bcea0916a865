"""The data of a run and the quality it is judged by, found by name.

A configuration's file names its generator (`generators/<name>.py`,
`make(rows, features, seed)` -> (X, y)) and its held-out metric
(`quality/<metric>.py`, `score(y, scores)`), so a new data shape or a new
metric such as ndcg@10 is a new file.

What `--seed` decides is the order of the feature columns, and nothing
else.  The rows are the configuration's own (`data_seed`; the held-out
rows `data_seed + 1`): a training job's input is one published data set,
and on this system which rows it holds decides how many waves a tree
takes, so that runs on rows drawn from six seeds differed by 1.3% in
`iter_ms` where two runs of one seed differed by 0.002% (my chip run,
PR 26).  Each feature's histogram and gain are computed apart from the
others', so another column order gives the same trees under other
feature indices — the same work in another order — short of an exact
tie between two features' gains.
"""

import importlib

import numpy as np


def column_order(config, seed):
    return np.random.Generator(np.random.SFC64(seed)).permutation(
        config["features"])


def make(config, seed, heldout_rows=None):
    """(X, y): the configuration's training rows, or `heldout_rows` of
    its held-out rows, with the columns in the order `seed` draws."""
    gen = importlib.import_module("benchmarks.generators."
                                  + config["generator"])
    if heldout_rows is None:
        X, y = gen.make(config["rows"], config["features"],
                        config["data_seed"])
    else:
        X, y = gen.make(heldout_rows, config["features"],
                        config["data_seed"] + 1)
    return np.take(X, column_order(config, seed), axis=1), y


def quality(config, y, scores):
    metric = importlib.import_module("benchmarks.quality."
                                     + config["quality"]["metric"])
    return metric.score(y, scores)
