"""The main path's kernels and programs COMPILE for the real chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): a tile
that is not aligned, a kernel that wants more VMEM than the scoped limit,
a program that does not fit 16 GB of HBM or a pallas_call that cannot be
partitioned is refused here exactly as the chip's compiler would refuse
it, at no chip time.  Nothing runs, so this says nothing about results
or speed — `chip_smoke.py` on the chip is what executes these programs.

Shapes are the headline configuration's (`chip_smoke.py`, `bench.py`):
28 features, 255 bins, 2^20 rows, 255 leaves — and, in the `wide` cases,
the widest benchmark cell's (`epsilon-400k-b63.train`): 2,000 features,
63 bins, 400,384 padded rows, where the wave kernel runs in feature
groups and the decomposed kernel does not fit.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU library, the suite runs under
several xdist workers that each import every test file, and a file that
touched the library at import (a top-level call, a `skipif` condition, a
`parametrize` argument) would give the workers different tests to
collect.  Everything built from the topology is built in a fixture or a
test, in this one file, in this process.
"""

import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

F, B, N, LEAVES = 28, 255, 1 << 20, 255
WIDE_F, WIDE_B, WIDE_N = 2000, 63, 400_384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any refusal means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def _kernel_args(sh, F=F, N=N):
    """(binned_fm [F, N] u8, slot [N] i32, gh [3, N] f32) on `sh`."""
    return (_sds((F, N), "uint8", sh), _sds((N,), "int32", sh),
            _sds((3, N), "float32", sh))


def _grow_args(row, by_row, repl, F=F, N=N):
    """The positional arguments of a grow entry
    (boosting/gbdt.py train_one_iter), as shapes with their shardings."""
    from lightgbm_tpu.learner import FeatureMeta
    meta = FeatureMeta(num_bin=_sds((F,), "int32", repl),
                       missing_type=_sds((F,), "int32", repl),
                       default_bin=_sds((F,), "int32", repl),
                       penalty=_sds((F,), "float32", repl))
    return (_sds((F, N), "uint8", by_row), _sds((N,), "float32", row),
            _sds((N,), "float32", row), _sds((N,), "float32", row),
            _sds((F,), "bool", repl), meta)


def _grow_params(max_bin=B, split=None, **kw):
    from lightgbm_tpu.learner import GrowParams
    from lightgbm_tpu.ops.split import SplitParams
    return GrowParams(num_leaves=LEAVES, max_bin=max_bin,
                      hist_method="pallas",
                      split=split or SplitParams(min_data_in_leaf=20), **kw)


@pytest.mark.parametrize("num_slots", [8, 64, 255])
def test_wave_kernel_compiles(one_chip, num_slots):
    from lightgbm_tpu.ops.histogram import build_histogram_wave
    compiled = build_histogram_wave.lower(
        *_kernel_args(one_chip), max_bin=B, num_slots=num_slots).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("num_slots", [1, 8, 128])
def test_wave_kernel_compiles_in_feature_groups(one_chip, num_slots):
    """2,000 features: `F * unit` is 139-262 MB against the 16 MB gate,
    so the kernel runs in the groups `plan_wave_kernel` gives (80 / 80
    / 40 features here, under 6 MB each).  Mosaic takes each (the gates
    date from an older runtime and no shape wider than 28 had tried
    them); the chip runs them in `tools/kernel_checks.py --wide`."""
    from lightgbm_tpu.ops.histogram import build_histogram_wave
    compiled = build_histogram_wave.lower(
        *_kernel_args(one_chip, WIDE_F, WIDE_N), max_bin=WIDE_B,
        num_slots=num_slots).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("num_slots,fits", [(895, True), (1023, False)])
def test_smallest_group_gate_is_the_compilers(one_chip, num_slots, fits):
    """`plan_wave_kernel(...).fits` (the booster takes the leaf-wise
    engine where it is false) against the compiler at 255 bins: 8
    features of 895 slots count 16.0 MB and compile, of 1,023 slots 18.0
    MB and are refused for scoped VMEM."""
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            plan_wave_kernel)
    assert plan_wave_kernel(F, B, num_slots).fits is fits
    lowered = build_histogram_wave.lower(
        *_kernel_args(one_chip, F, 1 << 16), max_bin=B,
        num_slots=num_slots)
    if fits:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()


def test_wave_kernel_int8_compiles(one_chip):
    from lightgbm_tpu.ops.histogram import build_histogram_wave
    compiled = build_histogram_wave.lower(
        *_kernel_args(one_chip), max_bin=B, num_slots=255, quant_bins=16,
        quant_scales=_sds((2,), "float32", one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("num_slots", [1, 2, 4])
def test_wave_hl_kernel_compiles(one_chip, num_slots):
    from lightgbm_tpu.ops.histogram import build_histogram_wave_hl
    binned, slot, gh = _kernel_args(one_chip)
    compiled = build_histogram_wave_hl.lower(
        binned, _sds((N, F), "uint8", one_chip), slot, gh, max_bin=B,
        num_slots=num_slots, out_slots=8).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rows_kernel_compiles(one_chip):
    from lightgbm_tpu.ops.histogram import build_histogram_rows_pallas
    compiled = build_histogram_rows_pallas.lower(
        _sds((N, F), "uint8", one_chip), _sds((N, 2), "float32", one_chip),
        _sds((N,), "float32", one_chip), max_bin=B).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def grow_compiled(one_chip):
    """The whole-tree program `lgb.train` runs per iteration on a TPU,
    compiled once for the tests below."""
    from lightgbm_tpu.learner.wave import grow_tree_wave
    return grow_tree_wave.lower(
        *_grow_args(one_chip, one_chip, one_chip),
        params=_grow_params()).compile()


def test_grow_tree_wave_program_compiles_on_one_chip(grow_compiled):
    assert "tpu_custom_call" in grow_compiled.as_text()
    mem = grow_compiled.memory_analysis()
    # the program's temporaries: 201,573,888 B at these 2^20 rows (192 B a
    # row) plus 10%.  Before the recolour was a kernel it read 340,418,048
    # (the rows' records as f32 bytes and as int32 words: PERF.md, PR 40);
    # with gh [N, 3] and slot [N, 1] padded to 128 lanes 1,825,379,840
    # (1.74 KB a row): a rise to that is a per-row operand back in a
    # padded layout (PERF.md section 4)
    assert mem.temp_size_in_bytes < int(201_573_888 * 1.1), mem


def test_wide_grow_program_compiles_on_one_chip(one_chip):
    """The whole-tree program at the wide cell's shape: every wave
    through the full kernel (the decomposed one has no feature grouping
    and wants 40 MB of VMEM at one slot), no row-major copy of the bins,
    and temporaries of 820,994,560 B (the leaf cache [384, 256,000]
    f32 and its update's operands) plus 10%."""
    from lightgbm_tpu.learner.wave import grow_tree_wave
    compiled = grow_tree_wave.lower(
        *_grow_args(one_chip, one_chip, one_chip, WIDE_F, WIDE_N),
        params=_grow_params(max_bin=WIDE_B)).compile()
    text = compiled.as_text()
    calls = re.findall(r"^\s*%([\w.\-]+) = .*custom_call_target="
                       r'"tpu_custom_call"', text, re.M)
    assert len(calls) >= 18             # a histogram and a recolour a wave
    assert {re.sub(r"\.\d+$", "", c) for c in calls} == {
        "build_histogram_wave", "recolour_wave"}
    assert f"u8[{WIDE_N},{WIDE_F}]" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < int(820_994_560 * 1.1), mem


def test_bundled_grow_program_compiles_at_the_one_hot_shape(one_chip):
    """The whole-tree program at the one-hot cell's shape
    (`expo-onehot700-b63.train_sparse`): 11,000,832 padded rows in 12
    uint8 bundle columns of up to 255 codes, 504 used features at 63
    bins, 255 leaves.  The kernels run the columns' shape (both of
    them: 12 columns is one block for `_hl` too), the bundle decode
    carries its part, the recolour is the kernel with the bundle route
    inside it — NOT entered under `Efb::route`, which would report the
    whole recolour as the route; the part is the XLA form's (below) —
    and the temporaries are 1,757,799,424 B (160 B a row; 4,176,885,248,
    380 B a row, with the rows' records in memory) plus 10%; PERF.md
    section 4 has what the chip run reserved beside its buffers."""
    from lightgbm_tpu.learner import FeatureMeta
    from lightgbm_tpu.learner.wave import grow_tree_wave
    cols, used, rows, codes = 12, 504, 11_000_832, 255
    by_feature = {k: _sds((used,), "int32", one_chip)
                  for k in ("num_bin", "missing_type", "default_bin",
                            "group", "offset", "zero_bin")}
    meta = FeatureMeta(penalty=_sds((used,), "float32", one_chip),
                       in_bundle=_sds((used,), "bool", one_chip),
                       **by_feature)
    row = _sds((rows,), "float32", one_chip)
    compiled = grow_tree_wave.lower(
        _sds((cols, rows), "uint8", one_chip), row, row, row,
        _sds((used,), "bool", one_chip), meta,
        params=_grow_params(max_bin=63, has_bundles=True,
                            group_max_bin=codes)).compile()
    text = compiled.as_text()
    calls = re.findall(r"^\s*%([\w.\-]+) = .*custom_call_target="
                       r'"tpu_custom_call"', text, re.M)
    assert len(calls) >= 18             # a histogram and a recolour a wave
    assert {re.sub(r"\.\d+$", "", c) for c in calls} == {
        "build_histogram_wave", "build_histogram_wave_hl", "recolour_wave"}
    assert "/Tree.split_find/Efb.decode/" in text
    assert "Efb.route" not in text
    assert "vmap(Efb." not in text      # no reader of parts matches that
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < int(1_757_799_424 * 1.1), mem


def test_xla_recolour_carries_the_bundle_route_part(one_chip):
    """The recolour's XLA form (a backend without the Pallas kernels)
    under bundles: the column select and the code's decode are the part
    `Efb::route` of the caller's `Tree::partition`, which the benchmark's
    `efb_route_ms` reads where that form runs."""
    from lightgbm_tpu.ops.recolour import recolour_xla, table_layout
    from lightgbm_tpu.utils.timer import global_timer
    layout = table_layout(num_columns=12, max_bin=63, column_bins=255,
                          num_slots=64, sentinel=256, has_bundles=True)

    def partition(tab, leaf_id, binned):
        with global_timer.device_scope("Tree::partition"):
            return recolour_xla(tab, leaf_id, binned, layout=layout)

    rows = 1 << 16
    text = jax.jit(partition).lower(
        _sds((layout.rows, 64), jnp.bfloat16, one_chip),
        _sds((rows,), "int32", one_chip),
        _sds((12, rows), "uint8", one_chip)).compile().as_text()
    assert "/Tree.partition/Efb.route/" in text


# the ranking and one-hot cells' device columns by code count
# (`mslr-2270k-b63.train_rank`, `expo-onehot700-b63.train_sparse`) and
# their padded rows
from tools.kernel_checks import CAT_CODES, EXPO_CODES, MSLR_CODES  # noqa: E402
MSLR_N, EXPO_N = 2_271_232, 11_000_832


@pytest.mark.parametrize("codes,rows,slots", [
    (MSLR_CODES, MSLR_N, 8), (MSLR_CODES, MSLR_N, 128),
    (MSLR_CODES, MSLR_N, 255), (EXPO_CODES, EXPO_N, 8),
    (EXPO_CODES, EXPO_N, 128), (EXPO_CODES, EXPO_N, 255)])
def test_classed_wave_kernel_compiles_within_scoped_vmem(one_chip, codes,
                                                         rows, slots):
    """The call with each column's one-hot at its class's codes, at both
    cells' shapes: one block of 7,392 / 1,200 one-hot rows up to 128
    slots (15.1 MB counted at the ranking cell's 128, where the
    unclassed call ran three feature groups), two calls at the chain
    tail's 256 there — each within the compiler's scoped VMEM."""
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            hist_classes_of,
                                            plan_wave_kernel)
    classes, _ = hist_classes_of(codes)
    plan = plan_wave_kernel(len(codes), max(codes), slots,
                            hist_classes=classes)
    assert plan.vmem_bytes <= 16 << 20 and plan.groups == (
        2 if (len(codes), slots) == (137, 255) else 1)
    compiled = build_histogram_wave.lower(
        *_kernel_args(one_chip, len(codes), rows), max_bin=max(codes),
        num_slots=slots, hist_classes=classes).compile()
    assert compiled.as_text().count("tpu_custom_call") >= plan.groups


_HLO_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+ = (?:\(.*?\)|\S+) ([\w\-]+)\(")


def _ops_under(text, part):
    """{opcode: count} of the compiled module's instructions, fused ones
    included, whose op_name holds `part`."""
    kinds = collections.Counter()
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        op = _HLO_OP.match(line)
        if name and op and part in name.group(1) + "/":
            kinds[op.group(1)] += 1
    return kinds


# the categorical cell (`expo-cat8-b63.train_cat`): 8 columns, 6 of them
# categorical with up to 189 bins (the 99%-coverage rule), at Expo's rows
CAT_COLUMNS = 6


@pytest.mark.parametrize("codes,rows,max_bin,cat,onehot_rows,full,hl,temp", [
    (MSLR_CODES, MSLR_N, 63, 0, 7392, 10, 0, None),
    (CAT_CODES, EXPO_N, 189, CAT_COLUMNS, 592, 10, 0, 335_155_200)])
def test_grow_program_is_one_for_two_column_orders(one_chip, codes, rows,
                                                   max_bin, cat, onehot_rows,
                                                   full, hl, temp):
    """What refused PR 37: the grow program at the ranking cell's shape,
    lowered from the code counts of two column orders (the 45 few-code
    columns at other indices, as `--seed` moves them), is the same
    module text — classes are static as a sorted multiset, the order is
    the data in `FeatureMeta.hist_order` / `hist_inverse` and in the
    class-ordered copy of the bins — so the second order finds the
    first's executable in the compile cache.  It compiles, every wave
    through the full kernel classed (`f32[7392, .]` results; ten calls
    for nine waves, the chain-tail wave's at 64 and at 128 slots): the
    decomposed one would ask 0.76 of its FLOP at one slot, and no
    row-major copy of the bins is made.

    The categorical cell's shape too (PR 41): `uint8[8, 11,000,832]`,
    255 leaves, the six categorical columns' indices data
    (`FeatureMeta.cat_index`) and only their count static, 6 bitset
    words; the scan carries its part `Cat.scan` under
    `Tree.split_find`, the recolour is the kernel with the bitset route
    inside it, and the temporaries are `temp` B plus 10% (1,773,102,080
    while the 1-slot waves took `_hl`: its row-major copy of the bins,
    `u8[11,000,832, 8]`, is 128 lanes wide in the chip's tiling)."""
    from lightgbm_tpu.learner import FeatureMeta
    from lightgbm_tpu.learner.wave import grow_tree_wave
    from lightgbm_tpu.ops.histogram import hist_classes_of
    from lightgbm_tpu.ops.split import SplitParams, cat_bitset_words
    F = len(codes)
    by_col = {k: _sds((F,), "int32", one_chip)
              for k in ("num_bin", "missing_type", "default_bin",
                        "hist_order", "hist_inverse")}
    if cat:
        by_col.update(is_cat=_sds((F,), "bool", one_chip),
                      cat_index=_sds((cat,), "int32", one_chip))
    meta = FeatureMeta(penalty=_sds((F,), "float32", one_chip), **by_col)
    row = _sds((rows,), "float32", one_chip)
    bins = _sds((F, rows), "uint8", one_chip)
    args = (bins, row, row, row, _sds((F,), "bool", one_chip), meta)
    split = SplitParams(min_data_in_leaf=20, has_categorical=bool(cat))
    texts, orders = [], []
    for seed in (1, 2):
        order_codes = np.random.RandomState(seed).permutation(codes)
        classes, order = hist_classes_of(order_codes)
        orders.append(order)
        lowered = grow_tree_wave.lower(
            *args, params=_grow_params(max_bin=max_bin, hist_classes=classes,
                                       split=split),
            binned_classed=bins)    # as a booster hands it over
        texts.append(lowered.as_text())
    assert not np.array_equal(*orders)
    assert texts[0] == texts[1]
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = re.findall(r"^\s*%([\w.\-]+) = \((f32\[[\d,]+\]).*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    kernels = [shape for name, shape in calls
               if name.startswith("build_histogram_wave.")]
    assert len(kernels) == full and all(
        s.startswith(f"f32[{onehot_rows},") for s in kernels)
    assert sum(name.startswith("build_histogram_wave_hl.")
               for name, _ in calls) == hl
    assert f"u8[{rows},{F}]" not in text
    if cat:
        assert cat_bitset_words(max_bin) == 6
        assert "/Tree.split_find/" in text and "/Cat.scan/" in text
        assert "vmap(Cat." not in text      # no reader of parts matches that
        assert re.search(r"/Tree\.split_find/[^\"]*/Cat\.scan/", text)
        # the scan is dense work: one sort, and under `Cat.scan` no
        # gather and no loop at the default max_cat_threshold
        kinds = _ops_under(text, "/Cat.scan/")
        assert kinds["sort"] and not kinds["gather"] + kinds["while"], kinds
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < int(temp * 1.1), mem


def _bucketize_memory(one_chip, n_pad, features, bounds):
    """The bucketize program's memory analysis at [n_pad, features]
    float32 with `bounds` bin bounds a column, compiled for the chip."""
    from lightgbm_tpu.io.device_bin import _bucketize_program
    return _bucketize_program().lower(
        _sds((n_pad, features), "float32", one_chip),
        _sds((features, bounds), "float32", one_chip),
        _sds((features,), "bool", one_chip),
        _sds((features,), "int32", one_chip), 1 << 16).compile(
        ).memory_analysis()


def test_bucketize_program_compiles_at_the_wide_shape(one_chip):
    """`io/device_bin.py` at 458,752 padded rows x 2,000 features: the
    float matrix (3.67 GB), its transposed copy and the bins fit the
    chip together, and the [65,536, 2,000, 62] compare is fused into its
    count (unfused it is 8.1 GB of its own: the temporaries read
    7,428,338,176 B with it fused)."""
    mem = _bucketize_memory(one_chip, 458_752, WIDE_F, WIDE_B - 1)
    assert mem.temp_size_in_bytes < int(7_428_338_176 * 1.05), mem


# the missing-value cell (`bosch-1000k-b63.train_missing`): 968 columns,
# every one with a NaN bin, as the generator's columns bin at 63 bins
# (the driver's `hist_classes` fact): 225 of up to 16 codes, 17 of up to
# 32, 726 of 63; 1,000,000 rows
MISSING_CLASSES = ((16, 225), (32, 17), (64, 726))
MISSING_F, MISSING_N = 968, 1_000_448


def test_bucketize_program_compiles_at_the_missing_cell_shape(one_chip):
    """Device binning at 1,048,576 padded rows x 968 NaN-typed columns
    (which columns hold a NaN bin, `nan_bin`, and read NaN as 0,
    `nan_zero`, is data): the float matrix (4.06 GB, donated), the bins
    (1.02 GB) and the temporaries, 6,090,416,640 B, fit the chip: the
    cell's set-up peak, about 11.2 GB of 16.9."""
    mem = _bucketize_memory(one_chip, 1 << 20, MISSING_F, 62)
    assert mem.temp_size_in_bytes < int(6_090_416_640 * 1.05), mem
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 12 << 30


def test_grow_program_compiles_at_the_missing_cell_shape(one_chip):
    """The whole-tree program at the missing-value cell's shape:
    `uint8[968, 1,000,448]` in three histogram classes, every column
    NaN-typed (`has_missing`), 255 leaves.  Two column orders lower to
    one module (the classes are a sorted multiset, the order data).
    Every wave takes the full kernel in class groups, each a call whose
    one-hot rows AND bins block fit the scoped VMEM (a 305-column group
    at 128 slots was refused for its bins block: PERF.md section 6); the
    gain scan's forward pass carries its part `Split.missing_right`
    under `Tree.split_find`; the temporaries are 961,283,072 B plus 10%."""
    from lightgbm_tpu.learner import FeatureMeta
    from lightgbm_tpu.learner.wave import grow_tree_wave
    from lightgbm_tpu.ops.histogram import (hist_classes_of,
                                            plan_wave_kernel)
    from lightgbm_tpu.ops.split import SplitParams
    F, rows = MISSING_F, MISSING_N
    codes = np.repeat([c for c, _ in MISSING_CLASSES],
                      [k for _, k in MISSING_CLASSES])
    for slots in (8, 64, 128, 256):
        plan = plan_wave_kernel(F, 63, slots, hist_classes=MISSING_CLASSES)
        assert plan.groups > 1 and plan.vmem_bytes <= 16 << 20
    by_col = {k: _sds((F,), "int32", one_chip)
              for k in ("num_bin", "missing_type", "default_bin",
                        "hist_order", "hist_inverse")}
    meta = FeatureMeta(penalty=_sds((F,), "float32", one_chip), **by_col)
    row = _sds((rows,), "float32", one_chip)
    bins = _sds((F, rows), "uint8", one_chip)
    args = (bins, row, row, row, _sds((F,), "bool", one_chip), meta)
    split = SplitParams(min_data_in_leaf=20, has_missing=True)
    texts = []
    for seed in (1, 2):
        classes, _ = hist_classes_of(
            np.random.RandomState(seed).permutation(codes))
        assert classes == MISSING_CLASSES
        lowered = grow_tree_wave.lower(
            *args, params=_grow_params(max_bin=63, hist_classes=classes,
                                       split=split),
            binned_classed=bins)
        texts.append(lowered.as_text())
    assert texts[0] == texts[1]
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = re.findall(r"^\s*%([\w.\-]+) = .*custom_call_target="
                       r'"tpu_custom_call"', text, re.M)
    assert {re.sub(r"\.\d+$", "", c) for c in calls} == {
        "build_histogram_wave", "recolour_wave"}
    assert re.search(r"/Tree\.split_find/[^\"]*/Split\.missing_right/", text)
    assert "vmap(Split." not in text    # no reader of parts matches that
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < int(961_283_072 * 1.1), mem


def _top_level_instructions(text):
    """(name, result type with layout, opcode) of every instruction that
    is not inside a fusion's own computation."""
    fused, out = False, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            fused = head.group(1).startswith("fused_computation")
            continue
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if m and not fused:
            out.append(m.groups())
    return out


def test_grow_program_hands_the_kernels_unpadded_row_operands(grow_compiled):
    """The layout contract between the wave engine and its kernels: gh
    [3, N] and slot [1, N], rows on lanes, built once a tree.  With gh
    [N, 3] and slot [N, 1] this program held 8 + 8 copies into
    `{1,0:T(8,128)}` (one a wave, 512 B a row each for 12 and 4 B of
    content): 35 ms of a 214 ms iteration on the chip (PERF.md, PR 29)."""
    text = grow_compiled.as_text()
    instrs = _top_level_instructions(text)
    assert len(instrs) > 1000                     # the parser still reads
    padded = re.compile(rf"^(f32\[{N},3\]|s32\[{N},1\])\{{1,0:T\(8,128\)")
    copies = [i for i in instrs if i[2] == "copy" and padded.match(i[1])]
    assert not copies, copies
    # no 32-bit kernel operand is a per-row array with fewer than 128
    # minor elements, row-major.  (u8[N, 28], the decomposed kernel's
    # row-major bins, is not this contract's and stays out.)
    types = {name: typ for name, typ, _ in instrs}
    calls = re.findall(r"= .*? custom-call\(([^)]*)\), "
                       r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) >= 9
    narrow = re.compile(rf"^[fs]32\[{N},(\d+)\]\{{1,0")
    for operands in calls:
        for name in re.findall(r"%([\w.\-]+)", operands):
            m = narrow.match(types[name])
            assert not (m and int(m.group(1)) < 128), (name, types[name])
    # gh is materialised once, before the first wave: everything else of
    # its shape hands it on (the conds' and the while's tuples; the
    # compiler's own same-layout move out of the memory space `S(1)` it
    # places the fusion's result in — an async `copy-start`/`copy-done`,
    # or the same move in slices, `slice-start`/`slice-done` joined by a
    # `ConcatBitcast` custom call — where a relayout is a `copy`)
    hands_on = {"get-tuple-element", "parameter", "bitcast", "copy-start",
                "copy-done"}
    joined = set(re.findall(r"%([\w.\-]+) = \S+ custom-call\([^)]*\), "
                            r'custom_call_target="ConcatBitcast"', text))
    made = [i for i in instrs if i[1].startswith(f"f32[3,{N}]")
            and i[2] not in hands_on and i[0] not in joined]
    assert len(made) <= 1, made


def test_grow_program_names_its_kernels_and_scopes(grow_compiled):
    """What the benchmark's trace readers hold on to: each Pallas
    custom-call is named after its kernel (`pallas_call(name=...)`; the
    profiler's event name starts with the instruction's), a histogram
    and a recolour a wave, 9 waves a tree (the chain-tail wave's
    histogram at two widths, 64 and 128 slots: one call a branch of its
    switch), and the ops around them carry the program's scopes in
    `op_name`.  `recolour_wave` is not a
    `^%build_histogram`: the benchmark reads it by its scope, under
    `Tree.partition`, which the custom call's own `op_name` holds."""
    calls = _kernel_calls(grow_compiled)
    names = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", c).group(1)
             for c in calls]
    heads = sorted({re.sub(r"\.\d+$", "", c) for c in names})
    assert heads == ["build_histogram_wave", "build_histogram_wave_hl",
                     "recolour_wave"]
    assert sum(n.startswith("recolour_wave") for n in names) == 9
    assert len(calls) == 19
    for name, call in zip(names, calls):
        if name.startswith("recolour_wave"):
            assert re.search(r'op_name="[^"]*/Tree\.partition/[^"]*"', call)
    text = grow_compiled.as_text()
    for scope in ("Tree.hist_operands", "Tree.histogram", "Tree.cache",
                  "Tree.split_find", "Tree.partition"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("F,rows,leaves,kw", [
    (F, N, 8, {}), (F, N, 256, {}),
    (WIDE_F, WIDE_N, 256, dict(max_bin=WIDE_B, column_bins=WIDE_B)),
    (12, 11_000_832, 256, dict(max_bin=63, has_bundles=True)),
    (F, N, 256, dict(cat_words=8))])
def test_recolour_kernel_compiles(one_chip, F, rows, leaves, kw):
    """`ops/recolour.py recolour_wave` at (28, 2^20) with 8 and 256
    leaves, at the wide cell's 2,000 columns (eight column blocks of 256
    on a second grid axis), at the one-hot cell's 12 bundle columns (nine one-byte fields; 32,768 rows a
    step, the last block hanging over the 11,000,832) and with the
    categorical bitset's 32 byte rows."""
    from lightgbm_tpu.ops.recolour import (plan_recolour, recolour_wave,
                                           table_layout)
    layout = table_layout(**{**dict(num_columns=F, max_bin=B, column_bins=B,
                                    num_slots=leaves, sentinel=256), **kw})
    compiled = recolour_wave.lower(
        _sds((layout.rows, leaves), jnp.bfloat16, one_chip),
        _sds((rows,), "int32", one_chip),
        _sds((F, rows), "uint8", one_chip), layout=layout).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert plan_recolour(F, rows) == {
        F: (28, 32768), WIDE_F: (256, 8192), 12: (12, 32768)}[F]


def test_grow_program_keeps_no_row_records_in_memory(grow_compiled):
    """What the recolour cost before it was a kernel (PERF.md, PR 40):
    every row's record written out as `f32[N, 30]` bytes and again as
    `s32[N, 10]` words, and the words read back six times — 1.8 GB a
    wave for 0.1 GB of rows.  Under `Tree.partition` the compiled
    program now holds no 32-bit array with a row axis and another axis
    over 1, in either order: the records live in the kernel's VMEM."""
    instrs = re.split(r"\n(?=\s*(?:ROOT )?%[\w.\-]+ = )",
                      grow_compiled.as_text())
    assert len(instrs) > 1000                     # the split still reads
    wide = re.compile(rf"= [fs]32\[(?:{N},(\d+)|(\d+),{N})\]")
    kept = []
    for instr in instrs:
        m = wide.search(instr.split("\n")[0])
        if (m and int(m.group(1) or m.group(2)) > 1
                and re.search(r'op_name="[^"]*/Tree\.partition/', instr)):
            kept.append(instr.strip()[:200])
    assert not kept, kept


@pytest.fixture(scope="module")
def sharded_compiled(topo):
    """`tree_learner=data`: the production shard_map (its own builder,
    its own specs) around the wave engine, compiled for the four chips
    of the described host."""
    from lightgbm_tpu.parallel import (grow_params_for_mesh,
                                       make_sharded_wave_fn)
    mesh = Mesh(np.array(topo.devices), ("data",))
    assert mesh.devices.size == 4
    row = NamedSharding(mesh, P("data"))
    by_row = NamedSharding(mesh, P(None, "data"))
    repl = NamedSharding(mesh, P())
    jitted = make_sharded_wave_fn(mesh).build(
        grow_params_for_mesh(_grow_params()), ())
    return jitted.lower(*_grow_args(row, by_row, repl)).compile()


def test_sharded_wave_program_compiles_on_four_chips(sharded_compiled):
    """The Pallas kernels inside the shard_map — the histogram's and the
    recolour's, each on its chip's rows — histograms psum'd."""
    hlo = sharded_compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
    assert len(re.findall(r"^\s*%recolour_wave[\w.\-]* = .*"
                          r'custom_call_target="tpu_custom_call"', hlo,
                          re.M)) == 9


def _kernel_calls(compiled):
    """[(instruction text)] of the program's Pallas custom-calls.  (An
    instruction may run over several lines of the text: a frontend
    attribute's JSON.)"""
    return [instr for instr in re.split(
        r"\n(?=\s*(?:ROOT )?%[\w.\-]+ = )", compiled.as_text())
        if 'custom_call_target="tpu_custom_call"' in instr]


def _hist_kernel_calls(compiled):
    """`_kernel_calls` less the recolour's: the histogram kernels, which
    the benchmark reads by the head `build_histogram`."""
    return [instr for instr in _kernel_calls(compiled)
            if re.match(r"\s*(?:ROOT )?%build_histogram", instr)]


def _kernel_labels(compiled):
    """[(instruction, (n, f, e) or None)] of the program's histogram
    custom-calls: the `Hist.mxu_n<n>_f<f>_e<e>` part in each one's
    `op_name` (`ops/histogram.py mxu_call_scope`), inside
    `Tree.histogram` — past the `cond/branch_<i>_fun` of the switch that
    picks a wave's width, where it has one."""
    out = []
    for instr in _hist_kernel_calls(compiled):
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", instr).group(1)
        part = re.search(r'op_name="[^"]*/Tree\.histogram/'
                         r'(?:cond/branch_\d+_fun/)?'
                         r'Hist\.mxu_n(\d+)_f(\d+)_e(\d+)/[^"]*"', instr)
        out.append((name, part and tuple(map(int, part.groups()))))
    return out


@pytest.mark.parametrize("program,rows_local", [
    ("grow_compiled", N), ("sharded_compiled", N // 4)])
def test_every_kernel_call_carries_what_it_asks_of_the_mxu(
        request, program, rows_local):
    """The benchmark's `hist_mxu_roofline` / `hist_mxu_padding` read each
    kernel event's label; an event without one is work they do not see.
    On one chip and under `shard_map` on four, every histogram
    custom-call of the grow program holds the part inside
    `Tree.histogram`, and the
    ladder's labels are the 255-leaf tree's: 1, 1, 2, 4 true slots
    through `_hl`, 8 to 64 through the full kernel at 1,837,056 FLOP a
    row (which `_hl` would ask at 8 too), and the `while_loop`'s wave,
    which names no true slots, at its two widths: 64 slots (one N tile,
    where its live slots fit) and its bound of 128."""
    labels = _kernel_labels(request.getfixturevalue(program))
    assert len(labels) >= 9
    assert all(part is not None for _, part in labels), labels
    by_kernel = {}
    for name, part in labels:
        by_kernel.setdefault(re.sub(r"\.\d+$", "", name), []).append(part)
    assert sorted(by_kernel["build_histogram_wave_hl"]) == [
        (2, 493568, 1), (2, 493568, 1), (4, 690176, 1), (8, 919552, 1)]
    assert sorted(by_kernel["build_histogram_wave"]) == [
        (16, 1837056, 1), (32, 1837056, 1), (64, 1837056, 1),
        (128, 1837056, 1), (128, 1837056, 1), (256, 3672064, 1)]


def test_each_kernel_call_holds_its_count_where_the_cache_key_sees_it(
        grow_compiled):
    """The compile cache's key ignores op metadata, so labels alone
    would be hidden by another program's cached executable: each
    `pallas_call` also states `mxu_flop_per_row` in its custom-call's
    `kernel_metadata`, a frontend attribute, which the key holds.  It is
    the `f` of the call's label."""
    stated = [re.search(r'kernel_metadata=\{\s*"mxu_flop_per_row":"(\d+)"',
                        instr) for instr in _hist_kernel_calls(grow_compiled)]
    assert len(stated) == 10 and all(stated)
    assert [int(m.group(1)) for m in stated] == [
        f for _, (_, f, _) in _kernel_labels(grow_compiled)]


def test_cost_analysis_still_counts_no_kernel(grow_compiled):
    """Whether XLA's cost analysis counts the kernels: it does not.
    With `cost_estimate=` on the three `pallas_call`s it did (the
    program's FLOP read 14.32e12, the labels' 14.28e12 and little else),
    but the compiler's scheduler reads the estimate too, and the Higgs
    cells' iteration came out 0.1% slower on the chip (PERF.md section 6,
    PR 39), so it is left out: `observability/costmodel.py`'s `roofline`
    events go on reporting an MFU without the kernels (ROADMAP D2)."""
    kernels = sum(f * N for _, (_, f, _) in _kernel_labels(grow_compiled))
    assert kernels == 15_454_208 * N
    assert grow_compiled.cost_analysis()["flops"] < 0.01 * kernels


HIGGS_N = 2_625_536     # the Higgs cells' padded rows a chip


def _score_update_onehot(scores, class_id, leaf_vals, rate, leaf_id,
                         pad_mask):
    """`boosting/gbdt.py _score_update_shrink` with the form a TPU takes
    (the booster's own closure picks by `jax.default_backend()`, which
    is the CPU here)."""
    from lightgbm_tpu.boosting.leaf_lookup import lookup_onehot
    delta = lookup_onehot(leaf_vals * rate, leaf_id)
    return scores.at[class_id].add(delta * pad_mask)


def _score_update_compiled(scores_sh, row, repl, n):
    return jax.jit(_score_update_onehot, donate_argnums=(0,)).lower(
        _sds((1, n), "float32", scores_sh), 0,
        _sds((LEAVES,), "float32", repl), 0.1, _sds((n,), "int32", row),
        _sds((n,), "float32", row)).compile()


def test_score_update_onehot_fuses_its_compare_on_one_chip(one_chip):
    """An `[n, 255]` one-hot in memory would be 2.7 GB of int32 at the
    Higgs cells' rows: the compiler folds the compare and the select
    into the reduction, so the program's temporaries stay under a
    megabyte (322,560 B when this was written; the gather's program
    129,024 B), and no gather is left in it."""
    compiled = _score_update_compiled(one_chip, one_chip, one_chip, HIGGS_N)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not re.search(r"\bgather\(", compiled.as_text())


def test_score_update_onehot_needs_no_collective_on_four_chips(topo):
    """`tree_learner=data`: scores, leaf ids and the pad mask sharded by
    rows, the leaf values replicated — every chip looks its own rows up
    and nothing crosses chips."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    compiled = _score_update_compiled(
        NamedSharding(mesh, P(None, "data")), NamedSharding(mesh, P("data")),
        NamedSharding(mesh, P()), 4 * HIGGS_N)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not re.search(r"\b(gather|all-reduce|all-gather|all-to-all|"
                         r"collective-permute|reduce-scatter)[-a-z]*\(",
                         compiled.as_text())
