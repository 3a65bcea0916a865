"""The recolour kernel EXECUTED on the CPU, in interpret mode.

`ops/recolour.py` has the wave engine's recolour in two forms of one
rule: `recolour_wave`, a row-tiled Pallas call, and `recolour_xla`, the
same functions over all rows at once.  Here both run against the rule
written out in NumPy from the per-leaf records themselves
(`tools/kernel_checks.py _recolour_host`: what `learner/wave.py` computed
before the rule moved), `leaf_id` and `kslot` bit for bit.
`tests/test_chip_compile.py` shows that the kernel compiles for the chip;
`tools/kernel_checks.py` check 10 and `--recolour` compare the bits there.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from lightgbm_tpu.observability.registry import global_registry
from lightgbm_tpu.ops.recolour import (pack_table, plan_recolour,
                                       recolour_wave, recolour_xla,
                                       table_layout)
from tools.kernel_checks import (_recolour_case, _recolour_forms,
                                 _recolour_host)

SENTINEL = 256


@pytest.fixture()
def interpret_pallas(monkeypatch):
    """Every `pl.pallas_call` traced inside the test interprets its
    kernel (shapes in this file are used by no other test)."""
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


def _one_leaf(**record):
    """Per-leaf vectors of a wave of 8 (the engine's smallest) whose
    leaf 0 holds `record`."""
    return {k: np.array([v] + [0] * 7, bool if isinstance(v, bool)
                        else np.int32) for k, v in record.items()}


def _assert_forms_equal_host(fields, leaf_id, binned, **layout_kw):
    want = _recolour_host(fields, leaf_id, binned, SENTINEL)
    for form, fn in _recolour_forms(fields, leaf_id, binned, **layout_kw):
        for name, got, w in zip(("leaf_id", "kslot"), fn(), want):
            got = np.asarray(got)
            assert got.dtype == np.int32 and got.shape == w.shape
            assert np.array_equal(got, w), (form, name,
                                            np.flatnonzero(got != w)[:8])


@pytest.mark.parametrize("F", [5, 28, 137])
@pytest.mark.parametrize("leaves", [8, 16, 64, 256])
def test_kernel_and_xla_form_equal_the_rule(interpret_pallas, leaves, F):
    """Plain columns: a third of the leaves do not split (their rows
    stay and take the sentinel slot), the leaves' features have no
    missing type, zero-as-missing and NaN-as-missing in turn with
    `default_left` both ways, thresholds and default bins anywhere in
    the feature's bins."""
    fields, leaf_id, binned = _recolour_case(F, 1536, leaves, seed=leaves + F)
    assert 0 < fields["split_sel"].sum() < leaves
    _assert_forms_equal_host(fields, leaf_id, binned)


@pytest.mark.parametrize("missing_type,default_left", [
    (0, False), (1, False), (1, True), (2, False), (2, True)])
def test_missing_values_take_the_default_side(interpret_pallas,
                                              missing_type, default_left):
    """One leaf, one column, every bin as a row: with zero-as-missing
    the default bin, with NaN-as-missing the last bin goes the split's
    default way whatever the threshold says; every other bin goes by
    the threshold, and with no missing type every bin does."""
    bins, thr, default_bin = 40, 17, 25
    fields = _one_leaf(
        split_sel=True, column=0, threshold=thr, default_left=default_left,
        new_leaf=3, rank=0, small_left=True, missing_type=missing_type,
        default_bin=default_bin, num_bin=bins)
    n = 512
    binned = (np.arange(n) % bins).astype(np.uint8)[None, :]
    leaf_id = np.zeros(n, np.int32)
    missing = {0: -1, 1: default_bin, 2: bins - 1}[missing_type]
    go_left = np.where(binned[0] == missing, default_left, binned[0] <= thr)
    for form, fn in _recolour_forms(fields, leaf_id, binned):
        new_leaf, kslot = map(np.asarray, fn())
        assert np.array_equal(new_leaf, np.where(go_left, 0, 3)), form
        assert np.array_equal(kslot, np.where(go_left, 0, SENTINEL)), form


@pytest.mark.parametrize("leaves", [8, 64])
def test_bundle_codes_decode_to_the_members_bin(interpret_pallas, leaves):
    """Bundle columns (`has_bundles`): a code inside the split member's
    range is its local bin, a code outside it — another member's, or
    0 — reads the member's zero bin; both then route as plain bins do.
    The rows hold codes below, inside, at both ends of and above their
    leaf's member's range."""
    fields, leaf_id, binned = _recolour_case(12, 4096, leaves, bundles=True,
                                             max_bin=63, seed=leaves)
    code = binned[fields["column"][leaf_id],
                  np.arange(leaf_id.size)].astype(np.int32)
    local = code - fields["offset"][leaf_id]
    nb = fields["num_bin"][leaf_id]
    for where in (local < 0, local == 0, (local > 0) & (local < nb - 1),
                  local == nb - 1, local >= nb):
        assert where.any()
    _assert_forms_equal_host(fields, leaf_id, binned, max_bin=63)


def test_zero_bin_routes_rows_outside_the_member(interpret_pallas):
    """The `zero_bin` case alone: one bundled leaf whose member sits at
    codes 100..109, zero bin 4, threshold 3 — rows with other members'
    codes read bin 4 and go right; with threshold 4 they go left."""
    n = 512
    code = np.concatenate([np.arange(95, 115), np.arange(0, 20)])
    binned = np.resize(code, n).astype(np.uint8)[None, :]
    for thr in (3, 4):
        fields = _one_leaf(
            split_sel=True, column=0, threshold=thr, default_left=False,
            new_leaf=9, rank=5, small_left=False, missing_type=0,
            default_bin=0, num_bin=10, offset=100, zero_bin=4)
        local = binned[0].astype(np.int32) - 100
        fbin = np.where((local >= 0) & (local < 10), local, 4)
        for form, fn in _recolour_forms(fields, np.zeros(n, np.int32),
                                        binned, max_bin=63):
            new_leaf, kslot = map(np.asarray, fn())
            assert np.array_equal(new_leaf, np.where(fbin <= thr, 0, 9)), form
            assert np.array_equal(kslot, np.where(fbin <= thr, SENTINEL, 5))


def test_categorical_bitsets_route_by_the_bins_bit(interpret_pallas):
    """Categorical splits: half of the leaves route by bit `fbin` of
    their 256-bit set (32 byte rows of the table), the others by
    threshold, in one wave."""
    fields, leaf_id, binned = _recolour_case(7, 2048, 16, cat_words=8)
    assert fields["is_cat"].any() and not fields["is_cat"].all()
    _assert_forms_equal_host(fields, leaf_id, binned)


def test_a_wave_with_no_split_moves_no_row(interpret_pallas):
    fields, leaf_id, binned = _recolour_case(28, 1024, 64, seed=3)
    fields["split_sel"][:] = False
    for form, fn in _recolour_forms(fields, leaf_id, binned):
        new_leaf, kslot = map(np.asarray, fn())
        assert np.array_equal(new_leaf, leaf_id), form
        assert (kslot == SENTINEL).all(), form


@pytest.mark.parametrize("F,n", [(28, 70_144), (300, 17_920)])
def test_rows_of_several_tiles_and_a_hanging_last_block(interpret_pallas,
                                                        F, n):
    """`n` over several row blocks, the last one hanging over the end
    (`plan_recolour`: 32,768 rows a block at 28 columns; 300 columns go
    in two column blocks of 256, 8,192 rows each): every row is routed
    once, none past the end is written."""
    Fg, Rt = plan_recolour(F, n)
    assert n > 2 * Rt and n % Rt and (Fg < F) == (F == 300)
    fields, leaf_id, binned = _recolour_case(F, n, 16, seed=F)
    _assert_forms_equal_host(fields, leaf_id, binned)


def test_wide_fields_take_more_bytes(interpret_pallas):
    """Bounds past a byte: 2,000 columns, 1,024 leaves and bins in
    int32 codes — the layout gives `col`, `new`, `rank` two byte rows
    each and the words come back whole."""
    leaves, F, n = 1024, 2000, 512
    layout = table_layout(num_columns=F, max_bin=255, column_bins=255,
                          num_slots=leaves, sentinel=leaves)
    assert {f: b for f, _, b in layout.fields} == dict(
        flags=1, col=2, thr=1, new=2, rank=2, miss=1)
    fields, leaf_id, binned = _recolour_case(F, n, leaves, sentinel=leaves)
    want = _recolour_host(fields, leaf_id, binned, leaves)
    tab = pack_table(layout, **{k: jnp.asarray(v) for k, v in fields.items()})
    for form in (recolour_wave, jax.jit(recolour_xla,
                                        static_argnames=("layout",))):
        got = form(tab, jnp.asarray(leaf_id),
                   jnp.asarray(binned.astype(np.int32)), layout=layout)
        assert all(np.array_equal(np.asarray(g), w)
                   for g, w in zip(got, want))


def test_the_counters_read_the_form_that_ran(interpret_pallas):
    """`recolour_kernel_traces` / `recolour_xla_traces` are added where
    a form is TRACED: one a distinct signature, nothing on a cached
    call."""
    def counters():
        snap = global_registry.snapshot()["counters"]
        return (snap.get("recolour_kernel_traces", 0),
                snap.get("recolour_xla_traces", 0))

    fields, leaf_id, binned = _recolour_case(9, 1024, 8)   # a shape of its own
    (_, kernel), (_, xla) = _recolour_forms(fields, leaf_id, binned)
    k0, x0 = counters()
    kernel()
    assert counters() == (k0 + 1, x0)
    xla()
    assert counters() == (k0 + 1, x0 + 1)
    kernel(), xla()
    assert counters() == (k0 + 1, x0 + 1)


def test_wave_engine_takes_the_form_its_histograms_take(interpret_pallas):
    """`learner/wave.py` step 4: with the Pallas histogram kernels
    (`hist_method="pallas"`) the recolour is the kernel, with an XLA
    lowering the XLA form — one static flag, nothing to configure — and
    the two grow the same tree over the same rows."""
    from lightgbm_tpu.learner import FeatureMeta, GrowParams
    from lightgbm_tpu.learner.wave import grow_tree_wave
    from lightgbm_tpu.ops.split import MISSING_NONE, SplitParams
    rng = np.random.RandomState(40)
    F, n, B = 6, 3072, 32
    binned = jnp.asarray(rng.randint(0, B, (F, n)).astype(np.uint8))
    # gradients on the bf16 grid, so both histogram lowerings sum the
    # same numbers
    grad = jnp.asarray(rng.randint(-16, 17, n) / 8.0, jnp.float32)
    hess = jnp.ones(n, jnp.float32)
    meta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                       missing_type=jnp.full(F, MISSING_NONE, jnp.int32),
                       default_bin=jnp.zeros(F, jnp.int32),
                       penalty=jnp.ones(F, jnp.float32))
    args = (binned, grad, hess, jnp.ones(n, jnp.float32),
            jnp.ones(F, bool), meta)
    snap = lambda: {k: v for k, v in
                    global_registry.snapshot()["counters"].items()
                    if k.startswith("recolour_")}
    grown = {}
    for method in ("pallas", "onehot"):
        before = snap()
        grown[method] = grow_tree_wave(*args, params=GrowParams(
            num_leaves=15, max_bin=B, hist_method=method,
            split=SplitParams(min_data_in_leaf=5)))
        moved = {k for k, v in snap().items() if v != before.get(k, 0)}
        assert moved == {"recolour_kernel_traces" if method == "pallas"
                         else "recolour_xla_traces"}
    (tree_p, leaf_p), (tree_x, leaf_x) = grown["pallas"], grown["onehot"]
    assert int(tree_p.num_leaves) == 15
    assert np.array_equal(np.asarray(leaf_p), np.asarray(leaf_x))
    for a, b in zip(tree_p, tree_x):
        assert np.array_equal(np.asarray(a), np.asarray(b))
