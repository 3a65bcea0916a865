"""Native C parser vs Python fallback parity (ref: src/io/parser.cpp —
the reference's parsers are native too; io/parser.py keeps detection and
label resolution, native/parser.c does the token hot loops)."""

import numpy as np
import pytest

from lightgbm_tpu.io.parser import parse_file
from lightgbm_tpu.native import (parse_dense_native, parse_libsvm_native,
                                 parser_lib)

pytestmark = pytest.mark.skipif(parser_lib() is None,
                                reason="no C compiler available")


def test_dense_native_matches_python(tmp_path):
    txt = ("1\t0.5\t\t3.25\n"
           "0\tna\t2e-3\t-1\n"
           "\n"
           "1\tNaN\t7\tnull\n")
    mat = parse_dense_native(txt.encode(), "\t", 4, 4)
    assert mat.shape == (3, 4)
    np.testing.assert_allclose(mat[0], [1, 0.5, np.nan, 3.25])
    np.testing.assert_allclose(mat[1], [0, np.nan, 2e-3, -1])
    np.testing.assert_allclose(mat[2], [1, np.nan, 7, np.nan])


def test_dense_ragged_row_raises():
    with pytest.raises(ValueError, match="line 2"):
        parse_dense_native(b"1,2,3\n4,5\n", ",", 2, 3)


def test_libsvm_native_matches_python():
    txt = b"1 0:0.5 3:2.5\n0 1:-1\n1\n"
    feats, labels = parse_libsvm_native(txt)
    np.testing.assert_allclose(labels, [1, 0, 1])
    np.testing.assert_allclose(
        feats, [[0.5, 0, 0, 2.5], [0, -1, 0, 0], [0, 0, 0, 0]])


def test_parse_file_on_reference_examples(tmp_path):
    """End-to-end parse of a file shaped like the reference's binary.train
    (seeded: /root/reference is not mounted here) goes through the native
    path and matches numpy's own parse."""
    rng = np.random.RandomState(9)
    path = str(tmp_path / "binary.train")
    np.savetxt(path, np.column_stack([rng.randint(0, 2, 2000),
                                      rng.randn(2000, 28)]),
               delimiter="\t", fmt="%.6g")
    feats, labels, names = parse_file(path)
    ref = np.loadtxt(path)
    np.testing.assert_allclose(labels, ref[:, 0])
    np.testing.assert_allclose(feats, ref[:, 1:])


def test_parse_file_libsvm_rank(tmp_path):
    # shaped like the reference's lambdarank/rank.train (graded labels,
    # sparse `index:value` pairs), seeded
    rng = np.random.RandomState(13)
    path = str(tmp_path / "rank.train")
    with open(path, "w") as f:
        for _ in range(300):
            idx = np.sort(rng.choice(np.arange(1, 301), 40, replace=False))
            f.write(f"{rng.randint(0, 5)} " + " ".join(
                f"{k}:{rng.rand():.4f}" for k in idx) + "\n")
    feats, labels, _ = parse_file(path)
    assert feats.shape[0] == len(labels) > 0
    assert np.isfinite(labels).all()
    # spot-check the first line against a manual parse
    with open(path) as f:
        first = f.readline().split()
    assert labels[0] == float(first[0])
    for pair in first[1:]:
        k, v = pair.split(":")
        np.testing.assert_allclose(feats[0, int(k)], float(v))


def test_dense_bad_token_raises_like_python():
    """Native strictness matches the Python fallback: garbage tokens are
    rejected, not silently NaN'd (environment-independent behavior)."""
    with pytest.raises(ValueError, match="line 2"):
        parse_dense_native(b"1,2\n3,abc\n", ",", 2, 2)
    with pytest.raises(ValueError, match="line 1"):
        parse_dense_native(b"1.5x,2\n", ",", 1, 2)
    # but inf and nan still parse
    m = parse_dense_native(b"inf,nan\n", ",", 1, 2)
    assert np.isinf(m[0, 0]) and np.isnan(m[0, 1])


def test_libsvm_bad_pair_raises():
    with pytest.raises(ValueError, match="line 1"):
        parse_libsvm_native(b"1 0x10:1\n")
    with pytest.raises(ValueError, match="line 2"):
        parse_libsvm_native(b"1 0:1\n0 1:2q\n")


def test_libsvm_negative_index_rejected_both_paths(tmp_path):
    """Native and Python-fallback LibSVM parsers must reject a negative
    feature index identically (the fallback used to train silently via
    Python negative indexing)."""
    import pytest

    import lightgbm_tpu.io.parser as P
    import lightgbm_tpu.native as N

    f = tmp_path / "bad.svm"
    f.write_text("1 0:1.5 -2:3.0\n0 1:2.0\n")
    # native path (when a compiler exists) and forced Python fallback must
    # both raise ValueError with the native parser's message shape
    if N.parser_lib() is not None:
        with pytest.raises(ValueError, match="malformed libsvm pair"):
            P.parse_file(str(f))
    orig = N.parse_libsvm_native
    N.parse_libsvm_native = lambda *a, **k: None
    try:
        with pytest.raises(ValueError, match="malformed libsvm pair"):
            P.parse_file(str(f))
    finally:
        N.parse_libsvm_native = orig
