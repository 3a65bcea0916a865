"""Entrypoint manifest for the tpulint IR audit (docs/StaticAnalysis.md v4).

Every hot jitted entry the RecompileDetector fingerprints at runtime —
the grow/grow-wave engines (donated or not), the gradient program,
DeviceEval's packed eval tick, and the inference bucket ladder that the
serving dispatch compiles — is declared here with exemplar
`jax.ShapeDtypeStruct` signatures, the SAME (shape, dtype, static)
scheme the recompile watchdog and the cost model key on
(observability/watchdog.py call_signature).  `python -m tools.tpulint
--ir` abstractly traces each entry to its ClosedJaxpr (no device, no
data, no compile) and runs the IR rule passes over it: a silent
f32→f64 weak-type promotion, a pure_callback smuggled into device
code, a convert_element_type round trip, or a giant literal baked into
the program is a 10–20× TPU regression invisible in source — this file
is where it becomes lint-visible.  The reference enforces the same
discipline (histogram entry width, device/host boundaries) in its C++
type system; our typed artifact is the jaxpr.

Protocol (consumed by tools/tpulint/ir/trace.py, duck-typed so the
package never imports tools/):

* the module exposes `ENTRIES`, an iterable of objects with attributes
  `name` (detector-style entry name), `group` (RecompileDetector
  accounting group, `costmodel.group_of` of the runtime name), `build`
  (zero-argument callable returning `fn` or `(fn, args)` or
  `(fn, args, kwargs)` ready for abstract tracing), `declares`
  (frozenset of IR-shape declarations the scatter-audit rule honours)
  and `line` (anchor for findings/suppressions);
* exemplar sizes are deliberately small — the IR rules check dtypes,
  primitives and constants, none of which depend on the exemplar's row
  count staying production-sized;
* entries are traced under `jax.enable_x64` so weak-type
  float64 promotions (an np.float64 constant leaking into f32 device
  code) become VISIBLE instead of being silently squashed by the
  default x64-off config.

Declarations (`declares`) are entry-level, pattern-scoped suppressions
with the justification carried by the manifest itself:

* ``onehot-dot`` — the entry intentionally builds histograms through
  XLA's one-hot × MXU dot trick (the shape the ROADMAP's Pallas
  histogram kernel replaces); undeclared one-hot dots are findings so
  the pattern cannot silently spread to new entries.
* ``narrow-acc`` — the entry intentionally accumulates into sub-32-bit
  histogram entries (the LightGBM-style quantized-gradient path);
  undeclared narrow accumulation is an overflow hazard and a finding.
"""

from __future__ import annotations

from typing import NamedTuple

# exemplar dimensions — small on purpose (see module docstring)
_F = 8          # features
_N = 4096       # rows
_B = 255        # max_bin
_T = 6          # trees in the packed-inference exemplar
_NI = 31        # internal nodes per tree
_NL = 32        # leaves per tree
_W = 8          # categorical bitset words


class LintEntry(NamedTuple):
    name: str
    group: str
    build: object       # () -> fn | (fn, args) | (fn, args, kwargs)
    declares: frozenset
    line: int


ENTRIES = []


def lint_entry(name: str, declares=()):
    """Register `build` as the manifest entry `name`; the accounting
    group is the detector-name prefix (costmodel.group_of)."""
    def deco(build):
        ENTRIES.append(LintEntry(
            name=name, group=name.split("[", 1)[0], build=build,
            declares=frozenset(declares),
            line=build.__code__.co_firstlineno))
        return build
    return deco


# ----------------------------------------------------------------- helpers
def _sds(shape, dtype):
    import jax
    import numpy as np
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def _feature_meta():
    from .learner.grow import FeatureMeta
    return FeatureMeta(num_bin=_sds((_F,), "int32"),
                       missing_type=_sds((_F,), "int32"),
                       default_bin=_sds((_F,), "int32"),
                       penalty=_sds((_F,), "float32"))


def _grow_args():
    """(binned, grad, hess, row_mask, col_mask, meta) — the positional
    prefix of every grow entry (boosting/gbdt.py train_one_iter)."""
    return (_sds((_F, _N), "uint8"), _sds((_N,), "float32"),
            _sds((_N,), "float32"), _sds((_N,), "float32"),
            _sds((_F,), "bool"), _feature_meta())


def _config(**params):
    from .config import Config
    return Config(dict(params, verbosity=-1))


def _binary_objective():
    import numpy as np
    from .objective import BinaryLogloss
    obj = BinaryLogloss(_config(objective="binary"))
    # init() only derives class-balance scalars; a two-row exemplar
    # label gives the same traced program as any real dataset
    class _MD:
        label = np.asarray([0.0, 1.0], np.float32)
        weight = None
    obj.init(_MD(), 2)
    return obj


def _multiclass_objective(K: int = 3):
    import numpy as np
    from .objective import MulticlassSoftmax
    obj = MulticlassSoftmax(_config(objective="multiclass", num_class=K))
    class _MD:  # noqa: E306
        label = np.arange(K, dtype=np.float32)
        weight = None
    obj.init(_MD(), K)
    return obj


# ------------------------------------------------------- grow (tree growth)
# Runtime detector name: "grow_tree" (boosting/gbdt.py wraps whichever
# engine the strategy selected).  One manifest entry per engine variant
# so the audit sees every program the single runtime name can stand for.

@lint_entry("grow_tree[leafwise]")
def _build_grow_leafwise():
    from .learner.grow import GrowParams, grow_tree
    params = GrowParams(num_leaves=15, max_bin=_B, compact_min=0)
    return grow_tree, (*_grow_args(), params)


@lint_entry("grow_tree[leafwise-donated]")
def _build_grow_leafwise_donated():
    from .learner.grow import GrowParams, grow_tree_donated
    params = GrowParams(num_leaves=15, max_bin=_B, compact_min=0)
    return grow_tree_donated, (*_grow_args(), params)


@lint_entry("grow_tree[leafwise-hist-stack]")
def _build_grow_leafwise_hist_stack():
    # the per-leaf histogram stack + partitioned-segment engine — the
    # default single-device leaf-wise configuration
    from .learner.grow import GrowParams, grow_tree
    params = GrowParams(num_leaves=15, max_bin=_B, use_hist_stack=True,
                        compact_min=1024)
    return grow_tree, (*_grow_args(), params)


@lint_entry("grow_tree[wave]", declares=("onehot-dot",))
def _build_grow_wave():
    # declares onehot-dot: the wave engine's histogram IS the XLA
    # one-hot × MXU dot (PERF_NOTES round 3) — the declared shape the
    # ROADMAP's Pallas histogram kernel replaces
    from .learner.grow import GrowParams
    from .learner.wave import grow_tree_wave
    params = GrowParams(num_leaves=16, max_bin=_B)
    return grow_tree_wave, (*_grow_args(), params)


@lint_entry("grow_tree[wave-donated]", declares=("onehot-dot",))
def _build_grow_wave_donated():
    from .learner.grow import GrowParams
    from .learner.wave import grow_tree_wave_donated
    params = GrowParams(num_leaves=16, max_bin=_B)
    return grow_tree_wave_donated, (*_grow_args(), params)


@lint_entry("grow_tree[wave-quant]", declares=("onehot-dot", "narrow-acc"))
def _build_grow_wave_quant():
    # quantized training: int8-packed grad/hess through the MXU int8
    # histogram path — narrow accumulation is the point (declared), and
    # the audit guards the convert discipline around it
    from .learner.grow import GrowParams
    from .learner.wave import grow_tree_wave
    params = GrowParams(num_leaves=16, max_bin=_B, quant_bins=16)
    return grow_tree_wave, (*_grow_args(), params), {
        "quant_scales": _sds((2,), "float32")}


@lint_entry("grow_tree[wave-sharded]", declares=("onehot-dot",))
def _build_grow_wave_sharded():
    # the data-parallel engine: shard_map over a row mesh + histogram
    # psum (parallel/data_parallel.py).  Traced on however many local
    # devices exist — the PROGRAM (and thus the IR discipline) is the
    # same at any axis size; only the axis extent changes.
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from .learner.grow import GrowParams
    from .parallel.data_parallel import DATA_AXIS, make_sharded_wave_fn
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs, (DATA_AXIS,))
    fn = make_sharded_wave_fn(mesh)
    params = GrowParams(num_leaves=16, max_bin=_B, compact_min=0)
    # .build is the EXACT production jit entry (shard_map + specs);
    # the plain wrapper resolves params/kwargs host-side per call
    return fn.build(params, ()), _grow_args()


# ------------------------------------------------------------- gradients
# Runtime detector name: "gradients" (boosting/gbdt.py _grad_fn_raw).

@lint_entry("gradients[regression]")
def _build_gradients_regression():
    import jax
    from .objective import RegressionL2
    obj = RegressionL2(_config(objective="regression"))

    # the K == 1 wrapper mirrors gbdt.py _grad1: slice + expand in-jit
    def _grad1(sc, lab, w):
        g, h = obj.get_gradients(sc[0], lab, w)
        return g[None, :], h[None, :]
    return jax.jit(_grad1), (_sds((1, _N), "float32"),
                             _sds((_N,), "float32"), None)


@lint_entry("gradients[binary]")
def _build_gradients_binary():
    import jax
    obj = _binary_objective()

    def _grad1(sc, lab, w):
        g, h = obj.get_gradients(sc[0], lab, w)
        return g[None, :], h[None, :]
    return jax.jit(_grad1), (_sds((1, _N), "float32"),
                             _sds((_N,), "float32"), None)


@lint_entry("gradients[multiclass]")
def _build_gradients_multiclass():
    import jax
    obj = _multiclass_objective()
    fn = jax.jit(lambda sc, lab, w: obj.get_gradients(sc, lab, w))
    return fn, (_sds((3, _N), "float32"), _sds((_N,), "float32"),
                _sds((_N,), "float32"))


# ------------------------------------------------------------ device_eval
# Runtime detector name: "device_eval" (ops/metrics.py DeviceEval).

def _tick_args(K: int):
    # (scores, label, weight, pad_mask, grad_ok) — DeviceEval.run
    return (_sds((K, _N), "float32"), _sds((_N,), "float32"), None,
            _sds((_N,), "float32"), _sds((), "bool"))


@lint_entry("device_eval[binary-auc]")
def _build_device_eval_binary():
    import jax
    from .metric import create_metrics
    from .ops.metrics import build_plans, make_tick_fn
    obj = _binary_objective()
    cfg = _config(objective="binary", metric="auc,binary_logloss")
    plans = build_plans(create_metrics(cfg), cfg, obj, 1)
    return jax.jit(make_tick_fn(plans, obj, 1, 1)), _tick_args(1)


@lint_entry("device_eval[regression-rmse]")
def _build_device_eval_regression():
    import jax
    from .metric import create_metrics
    from .ops.metrics import build_plans, make_tick_fn
    from .objective import RegressionL2
    obj = RegressionL2(_config(objective="regression"))
    cfg = _config(objective="regression", metric="rmse,l1")
    plans = build_plans(create_metrics(cfg), cfg, obj, 1)
    return jax.jit(make_tick_fn(plans, obj, 1, 1)), _tick_args(1)


@lint_entry("device_eval[multiclass]")
def _build_device_eval_multiclass():
    import jax
    from .metric import create_metrics
    from .ops.metrics import build_plans, make_tick_fn
    obj = _multiclass_objective()
    cfg = _config(objective="multiclass", num_class=3,
                  metric="multi_logloss,multi_error")
    plans = build_plans(create_metrics(cfg), cfg, obj, 3)
    return jax.jit(make_tick_fn(plans, obj, 3, 1)), _tick_args(3)


# ---------------------------------------------- device_predict (inference)
# Runtime detector names: "device_predict[<mode>@<bucket>]" — one per
# (mode, bucket) rung of the ladder DevicePredictor._fn_for compiles and
# the serving registry warms.  The program is bucket-size-generic, so
# one exemplar bucket per MODE covers the whole ladder.

def _pack_args():
    """The 11 packed-ensemble arrays (inference/pack.py layout)."""
    return (_sds((_T, _NI), "int32"),    # split_feature
            _sds((_T, _NI), "float32"),  # threshold (f32-floored)
            _sds((_T, _NI), "int32"),    # missing_type
            _sds((_T, _NI), "bool"),     # default_left
            _sds((_T, _NI), "bool"),     # is_cat
            _sds((_T, _NI), "int32"),    # left
            _sds((_T, _NI), "int32"),    # right
            _sds((_T, _NL), "float32"),  # leaf_value
            _sds((_T, _NI), "int32"),    # cat_start
            _sds((_T, _NI), "int32"),    # cat_nwords
            _sds((_W,), "uint32"))       # cat_words


def _predict_entry(mode: str, num_class: int = 1, convert=None,
                   es_freq: int = 0, average: bool = False):
    import jax
    from .inference.predictor import build_program
    fn = jax.jit(build_program(6, num_class, average, convert, mode,
                               es_freq), donate_argnums=(0,))
    x = _sds((_N, _F), "float32")
    if es_freq > 0:
        return fn, (x, _sds((), "float32"), *_pack_args())
    return fn, (x, *_pack_args())


@lint_entry("device_predict[raw]")
def _build_predict_raw():
    return _predict_entry("raw")


@lint_entry("device_predict[leaf]")
def _build_predict_leaf():
    return _predict_entry("leaf")


@lint_entry("device_predict[convert]")
def _build_predict_convert():
    # the serving dispatch's default mode: objective conversion fused
    obj = _binary_objective()
    return _predict_entry("convert", convert=obj.convert_output)


@lint_entry("device_predict[convert-multiclass]")
def _build_predict_convert_multiclass():
    obj = _multiclass_objective()
    return _predict_entry("convert", num_class=3,
                          convert=obj.convert_output)


@lint_entry("device_predict[raw-es]")
def _build_predict_raw_es():
    # prediction early stopping: the masked lax.scan accumulation
    return _predict_entry("raw", es_freq=10)


@lint_entry("device_predict[raw-average]")
def _build_predict_raw_average():
    # RF output averaging (average_output models)
    return _predict_entry("raw", average=True)
