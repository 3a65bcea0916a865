"""Ranking objectives: LambdaRank-NDCG and RankXENDCG.

ref: src/objective/rank_objective.hpp (RankingObjective:28, LambdarankNDCG:131,
RankXENDCG:362) and the CUDA twin src/objective/cuda/cuda_rank_objective.cu.

Per-query lambda computation is vectorized over the full pairwise matrix of a
query (no scalar pair loops).  Gradients run on the device
(`make_device_grad_fn`: queries bucketed by padded length, one tensor
program a bucket, under the device scope `GBDT::gradients`); the per-query
host loop (`get_gradients_host`) is the reference the tests compare with
and the path of position-bias rank_xendcg alone.  The plan is built once a
booster on the host: spans `Rank::init` (max-DCGs) and `Rank::plan`
(buckets, fills, row windows), counters `rank_*`.
No per-document index map is in the device programs: a query's rows are
one contiguous run of the score vector, so a bucket reads and writes them
as whole 128-wide rows (`_read_block`, `_add_block`), and what has to be
permuted rides a `lax.sort` as a payload.
Deviations from the reference, both noted for parity review:
  * the exact sigmoid is used instead of the reference's 1024-bin lookup table
    (rank_objective.hpp GetSigmoid/ConstructSigmoidTable);
  * RankXENDCG's per-query RNG is a NumPy Generator seeded with seed+query_id
    rather than the reference's custom LCG (utils/random.h).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from .config import Config
from .metric import default_label_gain
from .objective import ObjectiveFunction
from .utils import log
from .utils.timer import global_timer

K_EPSILON = 1e-15


LANES = 128     # width of a row of the [n_rows, LANES] view of a row vector


def _discounts(n: int) -> np.ndarray:
    return 1.0 / np.log2(np.arange(n) + 2.0)


def _window_plan(starts: np.ndarray, m: int, n_rows: int):
    """Where a bucket's queries lie in the `[n_rows, LANES]` view of a
    row-ordered vector: a query of padded length `m` that starts at row
    `a` of the vector lies inside the `W = ceil(m / LANES) + 1` view rows
    from `a // LANES`, `a % LANES` lanes in.  Returns (`rows` [Qb, W]
    int32, `shift` [Qb] int32).  A window that runs past the last view
    row repeats that row: its documents all lie before it."""
    W = -(-m // LANES) + 1
    rows = np.minimum(starts[:, None] // LANES + np.arange(W)[None, :],
                      n_rows - 1)
    return rows.astype(np.int32), (starts % LANES).astype(np.int32)


def _as_rows(vec):
    """[n] -> its [ceil(n / LANES), LANES] view (zeros after the end)."""
    import jax.numpy as jnp
    return jnp.pad(vec, (0, -vec.shape[0] % LANES)).reshape(-1, LANES)


def _read_block(vec_rows, rows, shift, m: int):
    """A bucket's `[Qb, m]` block out of the `[n_rows, LANES]` view: the
    window's whole rows (one index a row, not one a document), then each
    query moved left by its `shift`, a power of two a step (bit `k` of
    the shift takes the copy `2^k` lanes on; the widths shrink with it).
    Slots past a query's end hold its neighbours: the caller masks."""
    import jax.numpy as jnp
    x = jnp.take(vec_rows, rows, axis=0).reshape(rows.shape[0], -1)
    for k in reversed(range(LANES.bit_length() - 1)):
        step = 1 << k
        x = jnp.where((shift >> k & 1).astype(bool)[:, None],
                      x[:, step:], x[:, :-step])
    return x[:, :m]


def _add_block(acc_rows, block, rows, shift):
    """`_read_block`'s mirror: `block` [Qb, C, m] (zero outside its
    documents) widened to the window, each query moved right by its
    `shift`, and the window's whole rows added into `acc_rows`
    [n_rows, C, LANES].  Neighbouring queries share a view row; every
    document gets one term that may be non-zero and zeros, so the sum is
    exact in any order."""
    import jax.numpy as jnp
    Qb, C, m = block.shape
    W = rows.shape[1]
    x = jnp.pad(block, ((0, 0), (0, 0), (0, W * LANES - (LANES - 1) - m)))
    for k in range(LANES.bit_length() - 1):
        x = jnp.where((shift >> k & 1).astype(bool)[:, None, None],
                      jnp.pad(x, ((0, 0), (0, 0), (1 << k, 0))),
                      jnp.pad(x, ((0, 0), (0, 0), (0, 1 << k))))
    x = x.reshape(Qb, C, W, LANES).transpose(0, 2, 1, 3)
    return acc_rows.at[rows.reshape(-1)].add(x.reshape(Qb * W, C, LANES))


def _over_buckets(sc, bucket_args, block_fn):
    """(g, h), each [n], of the row-ordered scores `sc` [n]: every
    bucket's block read out of the scores (device scope `Rank::gather`),
    handed to `block_fn(sc_b [Qb, m], bucket) -> (lambdas, hessians)`,
    and the two, zeroed outside the bucket's documents, added back into
    row order (`Rank::scatter`)."""
    import jax.numpy as jnp
    dscope = global_timer.device_scope
    n = sc.shape[0]
    with dscope("Rank::gather"):
        sc_rows = _as_rows(sc)
    with dscope("Rank::scatter"):
        gh = jnp.zeros((sc_rows.shape[0], 2, LANES), sc.dtype)
    for bk in bucket_args:
        with dscope("Rank::gather"):
            sc_b = _read_block(sc_rows, bk["rows"], bk["shift"],
                               bk["val"].shape[1])
        lam, hes = block_fn(sc_b, bk)
        with dscope("Rank::scatter"):
            gh = _add_block(
                gh, jnp.where(bk["val"][:, None, :],
                              jnp.stack([lam, hes], axis=1), 0.0),
                bk["rows"], bk["shift"])
    with dscope("Rank::scatter"):
        return gh[:, 0].reshape(-1)[:n], gh[:, 1].reshape(-1)[:n]


class RankingObjective(ObjectiveFunction):
    """Common per-query driver (ref: rank_objective.hpp:28)."""

    # not an elementwise `get_gradients(score, label, weight)`: gbdt.py asks
    # for the per-query program (`make_device_grad_fn`, on the device) and
    # falls back to `get_gradients_host` where there is none; metrics of
    # such an objective are evaluated on the host (ops/metrics.py)
    run_on_host = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.seed = config.objective_seed
        self.learning_rate = config.learning_rate
        self.position_bias_regularization = (
            config.lambdarank_position_bias_regularization)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Ranking tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries)
        self.num_queries = len(self.query_boundaries) - 1
        # position bias factors (ref: rank_objective.hpp:43-60,290):
        # per-position offsets added to scores before the pairwise
        # lambdas, updated by a Newton step every iteration
        self.positions = (None if metadata.position is None
                          else np.asarray(metadata.position, np.int64))
        if self.positions is not None:
            self.num_position_ids = int(self.positions.max()) + 1
            self.pos_biases = np.zeros(self.num_position_ids)

    @property
    def pos_biases(self):
        """Learned per-position offsets.  When the device gradient
        program is active the Newton state lives on device
        (_pos_biases_dev); reading here pulls it to host lazily."""
        dev = getattr(self, "_pos_biases_dev", None)
        if dev is not None:
            return np.asarray(dev, np.float64)
        return self._pos_biases_host

    @pos_biases.setter
    def pos_biases(self, v):
        # a host write takes over: drop the device snapshot so reads
        # and the host Newton loop stay coherent (re-init, host path)
        self._pos_biases_dev = None
        self._pos_biases_host = v

    def _plan_buckets(self, n_pad: int, label_gain=None):
        """The device program's plan: `metric.bucket_queries`' buckets
        (queries grouped by padded pow2 length `m`), each with its
        labels `lab` [Qb, m] in its doc positions, its gains `gain`
        [Qb, m] float32 (`label_gain[lab]`, where a table is given: the
        labels never change, so the lookup is made here, once) and its
        row windows `rows` [Qb, W], `shift` [Qb] (`_window_plan`).
        Counts what the plan holds where it is built: registry counters
        `rank_queries`, `rank_docs`, `rank_padded_docs` (sum of Qb * m),
        `rank_window_rows` (sum of Qb * W: the indices a pass over the
        buckets moves), `rank_buckets`."""
        from .metric import bucket_queries
        from .observability import global_registry
        buckets = bucket_queries(self.query_boundaries, n_pad)
        last = len(self.label) - 1
        n_rows = -(-n_pad // LANES)
        for b in buckets:
            # padding points at row n_pad - 1: any label will do, `val` masks it
            b["lab"] = np.where(
                b["val"], self.label[np.minimum(b["idx"], last)],
                0).astype(np.int32)
            if label_gain is not None:
                b["gain"] = np.asarray(label_gain, np.float32)[b["lab"]]
            b["rows"], b["shift"] = _window_plan(
                self.query_boundaries[b["qs"]], b["m"], n_rows)
        for name, value in (
                ("rank_queries", self.num_queries),
                ("rank_docs", int(self.query_boundaries[-1])),
                ("rank_padded_docs", sum(b["idx"].size for b in buckets)),
                ("rank_window_rows", sum(b["rows"].size for b in buckets)),
                ("rank_buckets", len(buckets))):
            global_registry.inc(name, value)
        return buckets

    def get_gradients_host(self, score: np.ndarray):
        """score [n] -> (grad, hess) on host (ref: RankingObjective::GetGradients)."""
        n = len(score)
        lambdas = np.zeros(n, dtype=np.float64)
        hessians = np.zeros(n, dtype=np.float64)
        if self.positions is not None:
            score = score + self.pos_biases[self.positions]  # hpp:68
        for q in range(self.num_queries):
            a, b = int(self.query_boundaries[q]), int(self.query_boundaries[q + 1])
            l, h = self._one_query(q, self.label[a:b], score[a:b])
            lambdas[a:b] = l
            hessians[a:b] = h
        if self.weight is not None:
            lambdas *= self.weight
            hessians *= self.weight
        if self.positions is not None:
            self._update_position_bias(lambdas, hessians)
        return lambdas.astype(np.float32), hessians.astype(np.float32)

    def _update_position_bias(self, lambdas, hessians):
        """Newton step on the per-position utility derivatives
        (ref: rank_objective.hpp:290 UpdatePositionBiasFactors)."""
        P = self.num_position_ids
        fd = -np.bincount(self.positions, weights=lambdas, minlength=P)
        sd = -np.bincount(self.positions, weights=hessians, minlength=P)
        cnt = np.bincount(self.positions, minlength=P)
        reg = self.position_bias_regularization
        fd -= self.pos_biases * reg * cnt
        sd -= reg * cnt
        self.pos_biases += (self.learning_rate * fd
                            / (np.abs(sd) + 0.001))

    def get_gradients(self, score, label, weight):  # pragma: no cover
        raise RuntimeError("ranking objectives compute gradients host-side; "
                           "use get_gradients_host")

    def _one_query(self, qid, label, score):
        raise NotImplementedError


class LambdarankNDCG(RankingObjective):
    """ref: rank_objective.hpp:131 LambdarankNDCG.

    Gradients run ON DEVICE by default (make_device_grad_fn): queries are
    bucketed by padded pow2 length, each bucket computes its pairwise
    lambdas as one masked [Qb, T, m] tensor program (the TPU analogue of
    the per-query CUDA kernels in cuda_rank_objective.cu:131
    GetGradientsKernel_LambdarankNDCG).  A bucket reads its scores as
    whole 128-wide rows of the score vector and adds its results back the
    same way (a query's documents are contiguous: `_read_block`,
    `_add_block`); scores, gains and labels go into score order as the
    payloads of one stable sort and the results come back by a second,
    keyed on the order the first gave.  Position-bias offsets and their
    Newton update run on device too, the bias vector threaded as
    explicit state."""
    name = "lambdarank"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        self.norm = config.lambdarank_norm
        self.truncation_level = config.lambdarank_truncation_level
        self.label_gain = np.asarray(list(config.label_gain) or
                                     default_label_gain())
        if self.sigmoid <= 0:
            log.fatal(f"Sigmoid param {self.sigmoid} should be greater than zero")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if (self.label >= len(self.label_gain)).any() or (self.label < 0).any():
            log.fatal("Label exceeds label_gain size in lambdarank")
        # inverse max DCG at truncation level per query (ref: hpp:160-170)
        with global_timer.scope("Rank::init"):
            self.inverse_max_dcgs = np.zeros(self.num_queries)
            disc = _discounts(self.truncation_level)
            for q in range(self.num_queries):
                a, b = int(self.query_boundaries[q]), int(self.query_boundaries[q + 1])
                g = np.sort(self.label_gain[self.label[a:b].astype(np.int64)])[::-1]
                k = min(self.truncation_level, b - a)
                max_dcg = float((g[:k] * disc[:k]).sum())
                self.inverse_max_dcgs[q] = 1.0 / max_dcg if max_dcg > 0 else 0.0

    # ------------------------------------------------------------------
    def make_device_grad_fn(self, n_pad: int):
        """Build the jitted device gradient program (always available
        for lambdarank; position-bias mode included).

        Bucket tensors (row windows, labels, gains, valid masks, 1/maxDCG)
        are passed as explicit jit arguments — closing over large device
        arrays embeds them as constants in the executable (see gbdt.py
        _grad_fn note).

        Position bias (ref: rank_objective.hpp:43-60,290) also runs on
        device: scores are offset by the per-position biases before the
        pairwise lambdas, and the Newton bias update is computed from
        the weighted lambdas/hessians via segment sums — the bias vector
        rides as explicit state threaded through each call."""
        import jax
        import jax.numpy as jnp

        sigmoid, norm, trunc = self.sigmoid, self.norm, self.truncation_level

        def pair_rows(m):
            """Rows i of a padded length-m block's [Tm, m] pair tensor."""
            return max(1, min(trunc, m - 1))

        with global_timer.scope("Rank::plan"):
            plan = self._plan_buckets(n_pad, self.label_gain)
            self._dev_buckets = [dict(
                rows=jnp.asarray(b["rows"]), shift=jnp.asarray(b["shift"]),
                lab=jnp.asarray(b["lab"]), gain=jnp.asarray(b["gain"]),
                val=jnp.asarray(b["val"]),
                imd=jnp.asarray(self.inverse_max_dcgs[b["qs"]]
                                .astype(np.float32)))
                for b in plan]
        # pairs the buckets' tensors evaluate an iteration, masked ones too
        from .observability import RecompileDetector, global_registry
        global_registry.inc("rank_pairs", sum(
            len(b["qs"]) * pair_rows(b["m"]) * b["m"] for b in plan))
        f32 = jnp.float32
        dscope = global_timer.device_scope

        def bucket_lambdas(sc_b, lab_b, gain_b, val_b, imd_b):
            """[Qb, m] padded query block -> (lambdas, hessians) in the
            block's doc positions (mirrors _one_query, vectorized).  Its
            parts carry `Rank::sort` (into score order and back) and
            `Rank::pairs` (the [Qb, Tm, m] pair tensor) inside the
            caller's `GBDT::gradients`."""
            m = sc_b.shape[1]
            Tm = pair_rows(m)
            with dscope("Rank::sort"):
                # one sort carries what the pairs read.  Its second key
                # is the lane iota, which makes it the stable sort by the
                # first and comes out as the order.  A block's documents
                # are its first `cnt` slots and padding sorts last,
                # behind them among equals: the sorted valid mask is a
                # prefix, and the sorted scores are the key's negation
                key = jnp.where(val_b, sc_b, -jnp.inf)
                neg, order, gains, sl = jax.lax.sort(
                    (-key, jax.lax.broadcasted_iota(jnp.int32, key.shape, 1),
                     gain_b, lab_b),
                    dimension=1, is_stable=False, num_keys=2)
                cnt = jnp.sum(val_b.astype(jnp.int32), axis=1)
                sv = jnp.arange(m)[None, :] < cnt[:, None]
                ssz = jnp.where(sv, -neg, 0.0)
                best = ssz[:, 0]
                # the last document of a descending order
                worst = jnp.min(jnp.where(val_b, sc_b, jnp.inf), axis=1)
            with dscope("Rank::pairs"):
                disc = (1.0 / jnp.log2(jnp.arange(m, dtype=f32) + 2.0))
                gi, gj = gains[:, :Tm, None], gains[:, None, :]
                si, sj = ssz[:, :Tm, None], ssz[:, None, :]
                di, dj = disc[None, :Tm, None], disc[None, None, :]
                li, lj = sl[:, :Tm, None], sl[:, None, :]
                pair_ok = ((jnp.arange(m)[None, None, :]
                            > jnp.arange(Tm)[None, :, None])
                           & (li != lj) & sv[:, :Tm, None] & sv[:, None, :])
                delta_ndcg = (jnp.abs(gi - gj) * jnp.abs(di - dj)
                              * imd_b[:, None, None])
                if norm:
                    dsa = jnp.abs(si - sj)
                    delta_ndcg = jnp.where(
                        (best != worst)[:, None, None],
                        delta_ndcg / (0.01 + dsa), delta_ndcg)
                i_is_high = li > lj
                d_s = jnp.where(i_is_high, si - sj, sj - si)
                p = 1.0 / (1.0 + jnp.exp(sigmoid * d_s))
                p_lambda = jnp.where(pair_ok, -sigmoid * delta_ndcg * p, 0.0)
                p_hess = jnp.where(pair_ok,
                                   p * (1.0 - p) * sigmoid * sigmoid
                                   * delta_ndcg, 0.0)
                sign_i = jnp.where(i_is_high, 1.0, -1.0)
                lam_s = jnp.zeros_like(sc_b).at[:, :Tm].add(
                    jnp.sum(p_lambda * sign_i, axis=2))
                lam_s = lam_s + jnp.sum(-p_lambda * sign_i, axis=1)
                hes_s = jnp.zeros_like(sc_b).at[:, :Tm].add(
                    jnp.sum(p_hess, axis=2))
                hes_s = hes_s + jnp.sum(p_hess, axis=1)
                if norm:
                    sum_lam = -2.0 * jnp.sum(p_lambda, axis=(1, 2))
                    nf = jnp.where(sum_lam > 0,
                                   jnp.log2(1.0 + sum_lam)
                                   / jnp.maximum(sum_lam, K_EPSILON), 1.0)
                    lam_s = lam_s * nf[:, None]
                    hes_s = hes_s * nf[:, None]
            with dscope("Rank::sort"):
                # back to doc order: the order's entries are unique
                _, lam, hes = jax.lax.sort((order, lam_s, hes_s), dimension=1,
                                           is_stable=False, num_keys=1)
            return lam, hes

        use_pos = self.positions is not None
        if use_pos:
            P = self.num_position_ids
            pos_dev = jnp.asarray(
                np.concatenate([self.positions.astype(np.int32),
                                np.zeros(n_pad - len(self.positions),
                                         np.int32)]))
            pos_mask = jnp.asarray(
                np.concatenate([np.ones(len(self.positions), np.float32),
                                np.zeros(n_pad - len(self.positions),
                                         np.float32)]))
            # per-position doc counts are static: precompute host-side
            # instead of a scatter-add every iteration
            pos_cnt = jnp.asarray(np.bincount(
                self.positions, minlength=P).astype(np.float32))
            self._pos_biases_dev = jnp.zeros(P, f32)
            lr = self.learning_rate
            reg = self.position_bias_regularization

        @dscope("GBDT::gradients")
        def grad_fn(scores, weight, bucket_args, biases, pos_dev,
                    pos_mask, pos_cnt):
            """The whole program under `GBDT::gradients`; a bucket's
            parts under `Rank::gather` (its scores' rows into its
            block), `Rank::sort`, `Rank::pairs` and `Rank::scatter` (its
            block's rows added back into row order)."""
            with dscope("Rank::gather"):
                sc = scores[0].astype(f32)
            if use_pos:
                sc = sc + jnp.take(biases, pos_dev)     # hpp:68
            g, h = _over_buckets(sc, bucket_args, lambda sc_b, bk: bucket_lambdas(
                sc_b, bk["lab"], bk["gain"], bk["val"], bk["imd"]))
            if weight is not None:
                g = g * weight
                h = h * weight
            if use_pos:
                # Newton step on the per-position utility derivatives
                # (ref: rank_objective.hpp:290 UpdatePositionBiasFactors),
                # from the WEIGHTED lambdas like the host path
                fd = -(jnp.zeros(P, f32).at[pos_dev].add(g * pos_mask))
                sd = -(jnp.zeros(P, f32).at[pos_dev].add(h * pos_mask))
                fd = fd - biases * reg * pos_cnt
                sd = sd - reg * pos_cnt
                biases = biases + lr * fd / (jnp.abs(sd) + 0.001)
            return g[None, :], h[None, :], biases

        # tpulint: disable-next=donate-argnums -- gradient maps read the live score buffer; the boosting loop keeps updating it
        jitted = RecompileDetector(jax.jit(grad_fn, static_argnames=()),
                                   "gradients")
        zero1 = jnp.zeros(1, f32)
        zeroi = jnp.zeros(1, jnp.int32)
        if not use_pos:
            def call(scores, weight):
                g, h, _ = jitted(scores, weight, self._dev_buckets,
                                 zero1, zeroi, zero1, zero1)
                return g, h
            return call

        def call(scores, weight):
            g, h, nb = jitted(scores, weight, self._dev_buckets,
                              self._pos_biases_dev, pos_dev, pos_mask,
                              pos_cnt)
            self._pos_biases_dev = nb
            return g, h
        return call

    def _one_query(self, qid, label, score):
        cnt = len(label)
        lambdas = np.zeros(cnt)
        hessians = np.zeros(cnt)
        if cnt <= 1 or self.inverse_max_dcgs[qid] == 0.0:
            return lambdas, hessians
        inv_max_dcg = self.inverse_max_dcgs[qid]
        order = np.argsort(-score, kind="stable")
        sl = label[order].astype(np.int64)
        ss = score[order].astype(np.float64)
        best_score, worst_score = ss[0], ss[-1]
        gains = self.label_gain[sl]
        disc = _discounts(cnt)
        T = min(self.truncation_level, cnt - 1)

        # pairwise over (i in [0,T), j in (i, cnt)) in sorted space
        gi, gj = gains[:T, None], gains[None, :]
        si, sj = ss[:T, None], ss[None, :]
        di, dj = disc[:T, None], disc[None, :]
        li, lj = sl[:T, None], sl[None, :]
        valid = (np.arange(cnt)[None, :] > np.arange(T)[:, None]) & (li != lj)

        delta_ndcg = np.abs(gi - gj) * np.abs(di - dj) * inv_max_dcg
        delta_score_abs = np.abs(si - sj)
        if self.norm and best_score != worst_score:
            delta_ndcg = delta_ndcg / (0.01 + delta_score_abs)
        # high = larger label; delta_score = s_high - s_low
        i_is_high = li > lj
        d_s = np.where(i_is_high, si - sj, sj - si)
        p = 1.0 / (1.0 + np.exp(self.sigmoid * d_s))
        p_lambda = -self.sigmoid * delta_ndcg * p          # negative
        p_hess = p * (1.0 - p) * self.sigmoid * self.sigmoid * delta_ndcg
        p_lambda = np.where(valid, p_lambda, 0.0)
        p_hess = np.where(valid, p_hess, 0.0)

        # accumulate into sorted positions, then unsort
        lam_sorted = np.zeros(cnt)
        hes_sorted = np.zeros(cnt)
        # high gets +p_lambda, low gets -p_lambda
        sign_i = np.where(i_is_high, 1.0, -1.0)
        lam_sorted[:T] += (p_lambda * sign_i).sum(axis=1)
        np.add.at(lam_sorted, np.broadcast_to(np.arange(cnt)[None, :],
                                              p_lambda.shape).ravel(),
                  (-p_lambda * sign_i).ravel())
        hes_sorted[:T] += p_hess.sum(axis=1)
        np.add.at(hes_sorted, np.broadcast_to(np.arange(cnt)[None, :],
                                              p_hess.shape).ravel(),
                  p_hess.ravel())
        sum_lambdas = -2.0 * p_lambda.sum()
        if self.norm and sum_lambdas > 0:
            nf = np.log2(1 + sum_lambdas) / sum_lambdas
            lam_sorted *= nf
            hes_sorted *= nf
        lambdas[order] = lam_sorted
        hessians[order] = hes_sorted
        return lambdas, hessians


class RankXENDCG(RankingObjective):
    """ref: rank_objective.hpp:362 RankXENDCG.

    Gradients run ON DEVICE by default (make_device_grad_fn), like
    lambdarank: queries are bucketed by padded pow2 length and each
    bucket computes its masked-softmax + three order-correction passes
    as one [Qb, m] tensor program — the TPU analogue of the per-query
    CUDA kernels (ref: cuda_rank_objective.cu:385,502,618
    GetGradientsKernel_RankXENDCG variants).  Per-query Gumbel draws use
    `jax.random.fold_in(iteration_key, query_id)` instead of the host's
    per-query numpy RandomState streams — same independence structure,
    different streams (the documented RNG deviation this file already
    makes for extra-trees seeds)."""
    name = "rank_xendcg"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.rands = [np.random.RandomState(self.seed + q)
                      for q in range(self.num_queries)]

    # ------------------------------------------------------------------
    def make_device_grad_fn(self, n_pad: int):
        """Bucketed device gradient program; None when position bias is
        active (the generic host Newton loop handles that rare mode)."""
        if self.positions is not None:
            return None
        import jax
        import jax.numpy as jnp

        with global_timer.scope("Rank::plan"):
            buckets = [dict(
                rows=jnp.asarray(b["rows"]), shift=jnp.asarray(b["shift"]),
                lab=jnp.asarray(b["lab"]), val=jnp.asarray(b["val"]),
                qid=jnp.asarray(np.asarray(b["qs"], np.int32)))
                for b in self._plan_buckets(n_pad)]
        f32 = jnp.float32
        seed = self.seed
        dscope = global_timer.device_scope

        def bucket_grads(key_it, sc_b, lab_b, val_b, qid_b):
            """Vectorized mirror of _one_query over a [Qb, m] block."""
            m = sc_b.shape[1]
            scm = jnp.where(val_b, sc_b, -jnp.inf)
            mx = jnp.max(scm, axis=1, keepdims=True)
            e = jnp.where(val_b, jnp.exp(sc_b - mx), 0.0)
            rho = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True),
                                  K_EPSILON)
            keys = jax.vmap(lambda q: jax.random.fold_in(key_it, q))(qid_b)
            u = jax.vmap(lambda k: jax.random.uniform(k, (m,)))(keys)
            params = jnp.where(val_b,
                               jnp.exp2(lab_b.astype(f32)) - u, 0.0)
            inv_den = 1.0 / jnp.maximum(
                jnp.sum(params, axis=1, keepdims=True), K_EPSILON)
            # guard 1/(1-rho): float32 rho can saturate to 1.0 on widely
            # separated scores (the float64 host loop cannot)
            inv_1m = 1.0 / jnp.maximum(1.0 - rho, K_EPSILON)
            l1 = jnp.where(val_b, -params * inv_den + rho, 0.0)
            lambdas = l1
            p1 = l1 * inv_1m
            sum_l1 = jnp.sum(jnp.where(val_b, p1, 0.0), 1, keepdims=True)
            l2 = rho * (sum_l1 - p1)
            lambdas = lambdas + jnp.where(val_b, l2, 0.0)
            p2 = l2 * inv_1m
            sum_l2 = jnp.sum(jnp.where(val_b, p2, 0.0), 1, keepdims=True)
            lambdas = lambdas + jnp.where(val_b, rho * (sum_l2 - p2), 0.0)
            hess = jnp.where(val_b, rho * (1.0 - rho), 0.0)
            keep = (jnp.sum(val_b, axis=1) > 1)[:, None]   # cnt<=1: zeros
            return (jnp.where(keep & val_b, lambdas, 0.0),
                    jnp.where(keep & val_b, hess, 0.0))

        @dscope("GBDT::gradients")
        def grad_fn(scores, weight, bucket_args, it):
            with dscope("Rank::gather"):
                sc = scores[0].astype(f32)
            key_it = jax.random.fold_in(jax.random.PRNGKey(seed), it)
            g, h = _over_buckets(sc, bucket_args, lambda sc_b, bk: bucket_grads(
                key_it, sc_b, bk["lab"], bk["val"], bk["qid"]))
            if weight is not None:
                g = g * weight
                h = h * weight
            return g[None, :], h[None, :]

        from .observability import RecompileDetector
        # tpulint: disable-next=donate-argnums -- gradient maps read the live score buffer; the boosting loop keeps updating it
        jitted = RecompileDetector(jax.jit(grad_fn), "gradients")
        self._xe_iter = 0

        def call(scores, weight):
            g, h = jitted(scores, weight, buckets,
                          jnp.asarray(self._xe_iter, jnp.int32))
            self._xe_iter += 1
            return g, h

        return call

    def _one_query(self, qid, label, score):
        cnt = len(label)
        if cnt <= 1:
            return np.zeros(cnt), np.zeros(cnt)
        sc = score.astype(np.float64)
        e = np.exp(sc - sc.max())
        rho = e / e.sum()
        params = np.power(2.0, label.astype(np.int64)) - \
            self.rands[qid].random_sample(cnt)
        inv_denominator = 1.0 / max(K_EPSILON, params.sum())
        # first-order
        l1 = -params * inv_denominator + rho
        lambdas = l1.copy()
        params = l1 / (1.0 - rho)
        sum_l1 = params.sum()
        # second-order
        l2 = rho * (sum_l1 - params)
        lambdas += l2
        params = l2 / (1.0 - rho)
        sum_l2 = params.sum()
        # third-order
        lambdas += rho * (sum_l2 - params)
        hessians = rho * (1.0 - rho)
        return lambdas, hessians


def create_ranking_objective(name: str, config: Config) -> RankingObjective:
    if name == "lambdarank":
        return LambdarankNDCG(config)
    if name == "rank_xendcg":
        return RankXENDCG(config)
    log.fatal(f"Unknown ranking objective: {name}")
