"""CLI application (ref: src/main.cpp; application.cpp:31;
examples/*/train.conf are parsed directly)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.cli import main, parse_args

EXAMPLES = "/root/reference/examples"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference's example directories (conf files, data, machine lists)
# are not mounted in every sandbox; what needs THEM skips, what only
# needs a binary-classification file trains on a seeded stand-in
needs_reference = pytest.mark.skipif(
    not os.path.isdir(EXAMPLES),
    reason="/root/reference (the reference's example files) is not mounted")


@pytest.fixture(scope="module")
def binary(tmp_path_factory):
    """Directory holding `binary.train` (7000 x 28) and `binary.test`
    (500 x 28), label in column 0, tab-separated: the reference's own
    files when they are mounted, else the same shapes from a seed."""
    ref = f"{EXAMPLES}/binary_classification"
    if os.path.isdir(ref):
        return ref
    d = tmp_path_factory.mktemp("binary_classification")
    rng = np.random.RandomState(42)
    for name, n in (("binary.train", 7000), ("binary.test", 500)):
        X = rng.randn(n, 28)
        logit = 2.0 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2] * X[:, 3]
        y = (rng.rand(n) < 1 / (1 + np.exp(-logit))).astype(np.float64)
        np.savetxt(d / name, np.column_stack([y, X]), delimiter="\t",
                   fmt="%.6f")
    return str(d)


def test_parse_args_precedence(tmp_path):
    conf = tmp_path / "c.conf"
    conf.write_text("num_leaves = 31\nlearning_rate = 0.05\n# comment\n")
    params = parse_args([f"config={conf}", "num_leaves=7", "data=x.txt"])
    assert params["num_leaves"] == "7"       # CLI wins over config file
    assert params["learning_rate"] == "0.05"
    assert params["data"] == "x.txt"


def test_train_and_predict_roundtrip(tmp_path, binary):
    model = tmp_path / "model.txt"
    out = tmp_path / "preds.txt"
    rc = main([f"data={binary}/binary.train", "objective=binary",
               "num_iterations=15", "num_leaves=31", "verbosity=-1",
               f"output_model={model}"])
    assert rc == 0 and model.exists()
    rc = main(["task=predict", f"data={binary}/binary.test",
               f"input_model={model}", f"output_result={out}",
               "verbosity=-1"])
    assert rc == 0
    preds = np.loadtxt(out)
    y = np.loadtxt(f"{binary}/binary.test")[:, 0]
    assert preds.shape == y.shape
    assert 0 <= preds.min() and preds.max() <= 1
    acc = np.mean((preds > 0.5) == (y > 0.5))
    assert acc > 0.7, acc


@needs_reference
def test_train_with_reference_example_conf(tmp_path, binary):
    """The reference's own train.conf files must parse and run."""
    model = tmp_path / "model.txt"
    rc = main([f"config={binary}/train.conf",
               f"data={binary}/binary.train",
               f"valid={binary}/binary.test",
               "num_iterations=3", f"output_model={model}",
               "verbosity=-1"])
    assert rc == 0 and model.exists()
    text = model.read_text()
    assert text.startswith("tree\n")


def test_cli_refit(tmp_path, binary):
    model = tmp_path / "model.txt"
    refitted = tmp_path / "model2.txt"
    main([f"data={binary}/binary.train", "objective=binary",
          "num_iterations=3", "num_leaves=15", "verbosity=-1",
          f"output_model={model}"])
    rc = main(["task=refit", f"data={binary}/binary.train",
               f"input_model={model}", f"output_model={refitted}",
               "verbosity=-1"])
    assert rc == 0 and refitted.exists()
    assert refitted.read_text() != model.read_text()


def test_cli_convert_model(tmp_path, binary):
    model = tmp_path / "model.txt"
    cpp = tmp_path / "pred.cpp"
    main([f"data={binary}/binary.train", "objective=binary",
          "num_iterations=2", "num_leaves=7", "verbosity=-1",
          f"output_model={model}"])
    rc = main(["task=convert_model", f"input_model={model}",
               f"convert_model={cpp}", "verbosity=-1"])
    assert rc == 0
    src = cpp.read_text()
    assert "double Predict(const double* row)" in src
    assert "PredictTree0" in src


def test_python_dash_m_entrypoint(tmp_path, binary):
    model = tmp_path / "model.txt"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu",
         f"data={binary}/binary.train", "objective=binary",
         "num_iterations=2", "num_leaves=7", "verbosity=-1",
         f"output_model={model}"],
        capture_output=True, text=True, timeout=300,
        cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert model.exists()


def test_snapshot_freq(tmp_path, binary):
    """snapshot_freq writes model.snapshot_iter_N checkpoints
    (ref: gbdt.cpp:244-248) that resume via input_model."""
    model = tmp_path / "model.txt"
    rc = main([f"data={binary}/binary.train", "objective=binary",
               "num_iterations=6", "num_leaves=7", "verbosity=-1",
               "snapshot_freq=2", f"output_model={model}"])
    assert rc == 0
    snaps = sorted(tmp_path.glob("model.txt.snapshot_iter_*"))
    assert len(snaps) == 3, snaps
    import lightgbm_tpu as lgb
    b = lgb.Booster(model_file=str(snaps[0]))
    assert b._gbdt.current_iteration() == 2


@needs_reference
def test_parallel_learning_example_conf(tmp_path, monkeypatch):
    """The reference's parallel_learning config (tree_learner=feature +
    machine list params).  machines is no longer a silent no-op: a host
    that is not in the machine list fails LOUDLY (the reference's
    Network::Init would likewise fail to bind its listed port), while
    the single-machine form of the same config trains with the feature
    axis sharded over the local mesh (SURVEY §2.3 #2)."""
    import pytest as _pytest

    from lightgbm_tpu.utils.log import LightGBMError
    ex = f"{EXAMPLES}/parallel_learning"
    monkeypatch.chdir(ex)      # relative data paths resolve like the ref CLI
    model = tmp_path / "model.txt"
    # this host is not one of mlist.txt's machines -> loud failure
    with _pytest.raises(LightGBMError, match="machine list"):
        main(["config=train.conf", "num_iterations=2",
              f"output_model={model}", "verbosity=-1"])
    # the same config minus the cluster params trains locally
    rc = main(["config=train.conf", "num_iterations=2", "num_machines=1",
               f"output_model={model}", "verbosity=-1"])
    assert rc == 0 and model.exists()


@needs_reference
@pytest.mark.parametrize("example", [
    "regression", "binary_classification", "multiclass_classification",
    "lambdarank", "xendcg"])
def test_cli_runs_every_reference_example(example, tmp_path, monkeypatch):
    """Every reference example's own train.conf must train AND its
    predict.conf must predict through our CLI, unmodified except the
    output paths (the switch-over contract: a reference user's configs
    keep working).  Mirrors tests/python_package_test/test_consistency.py
    driving examples/*/train.conf."""
    ex = f"{EXAMPLES}/{example}"
    model = tmp_path / "model.txt"
    monkeypatch.chdir(ex)  # configs use relative data paths
    rc = main([f"config={ex}/train.conf", "num_trees=5",
               f"output_model={model}", "verbosity=-1"])
    assert rc == 0 and model.exists()
    pred_out = tmp_path / "pred.txt"
    rc = main([f"config={ex}/predict.conf", f"input_model={model}",
               f"output_result={pred_out}"])
    assert rc == 0
    preds = np.loadtxt(pred_out)
    assert np.isfinite(preds).all() and len(preds) > 0


def test_cli_predict_streams_chunks(tmp_path, monkeypatch, binary):
    """File prediction must run in bounded row chunks (ref:
    predictor.hpp:30 PipelineReader) and produce byte-identical output
    to a single-chunk run."""
    import lightgbm_tpu.cli as cli
    model = tmp_path / "m.txt"
    rc = main(["task=train", "objective=binary",
               f"data={binary}/binary.train", f"output_model={model}",
               "num_trees=5", "verbosity=-1"])
    assert rc == 0
    out_full = tmp_path / "pred_full.txt"
    rc = main(["task=predict", f"data={binary}/binary.test",
               f"input_model={model}", f"output_result={out_full}"])
    assert rc == 0
    # force many small chunks and compare byte-for-byte
    monkeypatch.setattr(cli, "_PREDICT_CHUNK_BUDGET", 8 * 28 * 100)
    out_chunked = tmp_path / "pred_chunked.txt"
    rc = main(["task=predict", f"data={binary}/binary.test",
               f"input_model={model}", f"output_result={out_chunked}"])
    assert rc == 0
    assert out_full.read_text() == out_chunked.read_text()
    assert len(out_full.read_text().splitlines()) == 500


def test_parse_file_stream_matches_parse_file(tmp_path, binary):
    """The streamed parser must produce the same rows as the one-shot
    parser for dense and libsvm inputs, across chunk boundaries."""
    import numpy as np
    from lightgbm_tpu.io.parser import parse_file, parse_file_stream
    dense = f"{binary}/binary.train"
    f_full, l_full, _ = parse_file(dense)
    chunks = list(parse_file_stream(dense, chunk_rows=777))
    f_s = np.concatenate([c[0] for c in chunks])
    l_s = np.concatenate([c[1] for c in chunks])
    np.testing.assert_array_equal(f_full, f_s)
    np.testing.assert_array_equal(l_full, l_s)
    assert len(chunks) > 1
    # libsvm with a width hint covering indices missing from late chunks
    svm = tmp_path / "t.svm"
    rng = np.random.RandomState(0)
    lines = []
    for i in range(500):
        k = rng.randint(0, 9)
        lines.append(f"{i % 2} {k}:{rng.rand():.6f}" +
                     (" 9:1.5" if i < 100 else ""))
    svm.write_text("\n".join(lines) + "\n")
    f_full, l_full, _ = parse_file(str(svm))
    chunks = list(parse_file_stream(str(svm), chunk_rows=150,
                                    num_features=f_full.shape[1]))
    f_s = np.concatenate([c[0] for c in chunks])
    np.testing.assert_array_equal(f_full, f_s)
