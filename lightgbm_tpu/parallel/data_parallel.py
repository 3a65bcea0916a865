"""Data-parallel GBDT: rows sharded over a 1-D mesh (ref: SURVEY.md §2.3 #3).

Mapping from the reference's DataParallelTreeLearner
(ref: src/treelearner/data_parallel_tree_learner.cpp):

  reference (socket collectives)              TPU (XLA collectives over mesh)
  ------------------------------------------- -------------------------------
  rows pre-partitioned per machine            binned [F, n] sharded on axis n
  local histograms then Network::ReduceScatter  histogram = reduction over the
    + HistogramSumReducer (:284)                sharded row axis -> GSPMD psum
  SyncUpGlobalBestSplit allreduce of           best-split argmax runs on the
    serialized SplitInfo (:441)                 replicated [F,B,2] histogram:
                                                no explicit sync needed
  root sums Allreduce in BeforeTrain (:167)    jnp.sum over sharded axis
  global_data_count_in_leaf_ tracking (:450)   actual counts psum'd the same way

Because `grow_tree` touches sharded data only through row-axis reductions
(histograms, sums, counts) and row-wise maps (recoloring), annotating the row
axis is sufficient: XLA partitions the program SPMD and the collectives ride
ICI — there is no separate "distributed learner" class, which is the point of
the redesign.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None,
              devices=None) -> Mesh:
    """1-D data-parallel mesh (multi-axis meshes come with feature-parallel)
    over the first `n_devices` devices of the default backend.  Asking for
    more than the backend has raises: a mesh quietly built on another
    backend's devices would train on the CPU while the caller believes it
    is on the chips."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise RuntimeError(
                    f"need {n_devices} {jax.default_backend()} devices, "
                    f"have {len(devices)}")
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (DATA_AXIS,))


def grow_params_for_mesh(params):
    """Adjust GrowParams for sharded rows: the partitioned-segment engine
    gathers rows by global index, which under GSPMD would all-gather the
    binned matrix per split — so sharded training uses the masked engine
    (compact_min=0), whose only row-axis ops are reductions and maps."""
    return params._replace(compact_min=0)


def make_sharded_wave_fn(mesh: Mesh, donate: bool = False):
    """Wave engine under explicit jax.shard_map over the data axis — the
    DEFAULT (Pallas) engine's distributed form.

    GSPMD cannot partition a pallas_call, so annotation-only sharding had
    to fall back to the leaf-wise/segment engine.  shard_map instead runs
    the per-shard Pallas histogram kernel on each device's local rows and
    the engine psums the computed-slot histograms (wave.py `_psum`) —
    exactly the reference's ReduceScatter of the same histograms its
    serial learner computes (ref: data_parallel_tree_learner.cpp:282-295
    HistogramSumReducer; :441 SyncUpGlobalBestSplit is a no-op here
    because the gain scan runs replicated on the reduced histograms).

    Returns a callable with the `_grow_fn` signature
    (binned, grad, hess, row_mask, col_mask, meta, params, **kw);
    jit-compiled once per (params, extra-kw-set) pair.
    """
    import functools

    @functools.lru_cache(maxsize=None)
    def _build(params, keys):
        from ..learner.wave import grow_tree_wave_impl
        sh_params = params._replace(data_axis=DATA_AXIS)

        def inner(binned, grad, hess, row_mask, col_mask, meta, *extras):
            return grow_tree_wave_impl(binned, grad, hess, row_mask,
                                       col_mask, meta, sh_params,
                                       **dict(zip(keys, extras)))

        ax = DATA_AXIS
        # tree arrays replicated (every shard computes identical
        # bookkeeping from the psum'd histograms); leaf_id stays sharded.
        # check_vma off: replication of the tree outputs is by
        # construction (all inputs to the bookkeeping are psum results),
        # which the static checker cannot see through the Pallas calls.
        # (the class-ordered copy of the bins is sharded by rows like
        # the bins; every other extra is replicated)
        extra_specs = tuple(P(None, ax) if k == "binned_classed" else P()
                            for k in keys)
        mapped = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, ax), P(ax), P(ax), P(ax), P(), P())
            + extra_specs,
            out_specs=(P(), P(ax)),
            check_vma=False)
        if not donate:
            return jax.jit(mapped)
        # donated buffers entering a shard_map'd entry must carry
        # EXPLICIT shardings: leaving XLA to infer the donated layout
        # from the arguments is the donation x SPMD interaction
        # implicated when a multi-device dry run wedged until the
        # wall-clock cap (tpulint spmd-axis-discipline enforces this
        # statically).  The sharded grad/hess slices die
        # at the grow call, like the single-device donated entry
        # (learner/wave.py).
        row = NamedSharding(mesh, P(ax))
        repl = NamedSharding(mesh, P())
        return jax.jit(
            mapped,
            in_shardings=(NamedSharding(mesh, P(None, ax)), row, row,
                          row, repl, repl)
            + tuple(NamedSharding(mesh, spec) for spec in extra_specs),
            donate_argnums=(1, 2))

    def call(binned, grad, hess, row_mask, col_mask, meta, params,
             cegb_used=None, extra_tag=None, quant_scales=None,
             binned_classed=None):
        opt = (("cegb_used", cegb_used), ("extra_tag", extra_tag),
               ("quant_scales", quant_scales),
               ("binned_classed", binned_classed))
        keys = tuple(k for k, v in opt if v is not None)
        extras = tuple(v for _, v in opt if v is not None)
        import jax.numpy as jnp
        extras = tuple(jnp.asarray(e) for e in extras)
        return _build(params, keys)(binned, grad, hess, row_mask,
                                    col_mask, meta, *extras)

    # expose the jitted builder so tests can .lower() the EXACT
    # production shard_map (specs included) for collective accounting
    call.build = _build
    return call


def data_parallel_shardings(mesh: Mesh) -> Tuple:
    """(binned, per-row vectors, replicated) shardings for grow_tree args."""
    row = NamedSharding(mesh, P(DATA_AXIS))
    feat_by_row = NamedSharding(mesh, P(None, DATA_AXIS))
    repl = NamedSharding(mesh, P())
    return feat_by_row, row, repl


def shard_for_data_parallel(mesh: Mesh, binned, grad, hess, row_mask):
    """Place the per-row tensors on the mesh; n must divide the mesh size."""
    feat_by_row, row, _ = data_parallel_shardings(mesh)
    return (jax.device_put(binned, feat_by_row),
            jax.device_put(grad, row),
            jax.device_put(hess, row),
            jax.device_put(row_mask, row))
