"""The ranking gradient programs as they stood before PR 35: one index a
document.  A reference for tests and `tools/kernel_checks.py`, never a
path of the program.

`lightgbm_tpu/ranking.py` moves a query's rows as the contiguous run they
are and lets payloads ride a sort; this file keeps the formulation it
replaced — a `[Qb, m]` index map into the row-ordered score vector
(`jnp.take`, `.at[].add`), `argsort` and `take_along_axis` into score
order and back, the gains by `jnp.take(label_gain, labels)` — with the
same arithmetic in the same order, so the two have to agree in every bit
(`array_equal`), on the CPU and on the chip.

`lambdarank(obj, n_pad)` and `xendcg(obj, n_pad)` take an initialised
objective, read its fields, and leave it alone: no counter, no span, no
`_dev_buckets`.  Each returns a pure function of `scores [1, n_pad]`,
`weight` (or None) and the state the program threads — the position
biases `[P]` (any array without positions), the iteration — jitted with
the bucket tensors as arguments, as the program's own is; the caller
keeps the state.
"""

import numpy as np

K_EPSILON = 1e-15


def _index_buckets(obj, n_pad):
    """`metric.bucket_queries`' buckets with the labels filled in."""
    from lightgbm_tpu.metric import bucket_queries
    buckets = bucket_queries(obj.query_boundaries, n_pad)
    last = len(obj.label) - 1
    for b in buckets:
        b["lab"] = np.where(b["val"], obj.label[np.minimum(b["idx"], last)],
                            0).astype(np.int32)
    return buckets


def lambdarank(obj, n_pad):
    import jax
    import jax.numpy as jnp

    sigmoid, norm, trunc = obj.sigmoid, obj.norm, obj.truncation_level
    buckets = [dict(idx=jnp.asarray(b["idx"]), lab=jnp.asarray(b["lab"]),
                    val=jnp.asarray(b["val"]),
                    imd=jnp.asarray(obj.inverse_max_dcgs[b["qs"]]
                                    .astype(np.float32)))
               for b in _index_buckets(obj, n_pad)]
    lg = jnp.asarray(obj.label_gain, jnp.float32)
    f32 = jnp.float32

    def bucket_lambdas(sc_b, lab_b, val_b, imd_b, m):
        Tm = max(1, min(trunc, m - 1))
        key = jnp.where(val_b, sc_b, -jnp.inf)
        order = jnp.argsort(-key, axis=1, stable=True)
        ss = jnp.take_along_axis(sc_b, order, 1)
        sl = jnp.take_along_axis(lab_b, order, 1)
        sv = jnp.take_along_axis(val_b, order, 1)
        ssz = jnp.where(sv, ss, 0.0)
        cnt = jnp.sum(sv.astype(jnp.int32), axis=1)
        gains = jnp.take(lg, jnp.clip(sl, 0, lg.shape[0] - 1))
        best = ssz[:, 0]
        worst = jnp.take_along_axis(
            ssz, jnp.maximum(cnt - 1, 0)[:, None], 1)[:, 0]
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
        inv_order = jnp.argsort(order, axis=1)
        lam = jnp.take_along_axis(lam_s, inv_order, 1)
        hes = jnp.take_along_axis(hes_s, inv_order, 1)
        return lam, hes

    use_pos = obj.positions is not None
    if use_pos:
        P = obj.num_position_ids
        tail = n_pad - len(obj.positions)
        pos_dev = jnp.asarray(np.concatenate(
            [obj.positions.astype(np.int32), np.zeros(tail, np.int32)]))
        pos_mask = jnp.asarray(np.concatenate(
            [np.ones(len(obj.positions), np.float32),
             np.zeros(tail, np.float32)]))
        pos_cnt = jnp.asarray(np.bincount(
            obj.positions, minlength=P).astype(np.float32))
        lr = obj.learning_rate
        reg = obj.position_bias_regularization

    def grad_fn(scores, weight, bucket_args, biases):
        sc = scores[0].astype(f32)
        if use_pos:
            sc = sc + jnp.take(biases, pos_dev)
        g = jnp.zeros(n_pad, f32)
        h = jnp.zeros(n_pad, f32)
        for bk in bucket_args:
            m = bk["idx"].shape[1]
            sc_b = jnp.take(sc, bk["idx"])
            lam, hes = bucket_lambdas(sc_b, bk["lab"], bk["val"],
                                      bk["imd"], m)
            lam = jnp.where(bk["val"], lam, 0.0)
            hes = jnp.where(bk["val"], hes, 0.0)
            g = g.at[bk["idx"].reshape(-1)].add(lam.reshape(-1))
            h = h.at[bk["idx"].reshape(-1)].add(hes.reshape(-1))
        if weight is not None:
            g = g * weight
            h = h * weight
        if use_pos:
            fd = -(jnp.zeros(P, f32).at[pos_dev].add(g * pos_mask))
            sd = -(jnp.zeros(P, f32).at[pos_dev].add(h * pos_mask))
            fd = fd - biases * reg * pos_cnt
            sd = sd - reg * pos_cnt
            biases = biases + lr * fd / (jnp.abs(sd) + 0.001)
        return g[None, :], h[None, :], biases

    def fn(scores, weight, biases):
        """-> (g [1, n_pad], h [1, n_pad], the biases after the step)."""
        return jitted(scores, weight, buckets, biases)
    jitted = jax.jit(grad_fn)
    return fn


def xendcg(obj, n_pad):
    import jax
    import jax.numpy as jnp

    buckets = [dict(idx=jnp.asarray(b["idx"]), lab=jnp.asarray(b["lab"]),
                    val=jnp.asarray(b["val"]),
                    qid=jnp.asarray(np.asarray(b["qs"], np.int32)))
               for b in _index_buckets(obj, n_pad)]
    f32 = jnp.float32
    seed = obj.seed

    def bucket_grads(key_it, sc_b, lab_b, val_b, qid_b):
        m = sc_b.shape[1]
        scm = jnp.where(val_b, sc_b, -jnp.inf)
        mx = jnp.max(scm, axis=1, keepdims=True)
        e = jnp.where(val_b, jnp.exp(sc_b - mx), 0.0)
        rho = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), K_EPSILON)
        keys = jax.vmap(lambda q: jax.random.fold_in(key_it, q))(qid_b)
        u = jax.vmap(lambda k: jax.random.uniform(k, (m,)))(keys)
        params = jnp.where(val_b, jnp.exp2(lab_b.astype(f32)) - u, 0.0)
        inv_den = 1.0 / jnp.maximum(
            jnp.sum(params, axis=1, keepdims=True), K_EPSILON)
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
        keep = (jnp.sum(val_b, axis=1) > 1)[:, None]
        return (jnp.where(keep & val_b, lambdas, 0.0),
                jnp.where(keep & val_b, hess, 0.0))

    def grad_fn(scores, weight, bucket_args, it):
        sc = scores[0].astype(f32)
        key_it = jax.random.fold_in(jax.random.PRNGKey(seed), it)
        g = jnp.zeros(n_pad, f32)
        h = jnp.zeros(n_pad, f32)
        for bk in bucket_args:
            sc_b = jnp.take(sc, bk["idx"])
            lam, hes = bucket_grads(key_it, sc_b, bk["lab"], bk["val"],
                                    bk["qid"])
            g = g.at[bk["idx"].reshape(-1)].add(lam.reshape(-1))
            h = h.at[bk["idx"].reshape(-1)].add(hes.reshape(-1))
        if weight is not None:
            g = g * weight
            h = h * weight
        return g[None, :], h[None, :]

    def fn(scores, weight, it):
        """-> (g [1, n_pad], h [1, n_pad]) of iteration `it`."""
        return jitted(scores, weight, buckets, it)
    jitted = jax.jit(grad_fn)
    return fn
