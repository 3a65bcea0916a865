"""Measure the REAL reference LightGBM CLI on the exact bench dataset.

Trains the oracle binary (tools/build_reference_oracle.sh) on the same
Higgs-like synthetic that bench.py uses (same generator, same seed, same
params: 255 leaves, max_bin 255, lr 0.1, min_data_in_leaf 20), times
sec/iter as (t(ITERS_HI) - t(ITERS_LO)) / (ITERS_HI - ITERS_LO) so data
loading/binning is excluded, computes held-out AUC with the same
tie-averaged AUC as bench.py, and writes docs/oracle_bench.json (its own
record of ref_auc / ref_sec_per_iter; bench.py does not read it).

Run manually once per host class: the result records host facts
(cpu count, model) so the judged numbers carry their context.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench import FEATURES, NUM_LEAVES, ROWS
from tools.higgs_like import auc as _auc, make_higgs_like

ORACLE = "/tmp/lgb_ref_src/lightgbm"
ITERS_LO = 13
ITERS_HI = 63


def main():
    if not os.path.exists(ORACLE):
        print("oracle binary missing; run tools/build_reference_oracle.sh",
              file=sys.stderr)
        return 1
    work = tempfile.mkdtemp(prefix="lgb_oracle_bench")
    try:
        return _run(work)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(work):
    X, y = make_higgs_like(ROWS, FEATURES)
    Xte, yte = make_higgs_like(100_000, FEATURES, seed=1)
    train_csv = os.path.join(work, "train.csv")
    test_csv = os.path.join(work, "test.csv")
    np.savetxt(train_csv, np.column_stack([y, X]), fmt="%.9g", delimiter="\t")
    np.savetxt(test_csv, np.column_stack([yte, Xte]), fmt="%.9g",
               delimiter="\t")

    def train(iters, model_out):
        conf = os.path.join(work, f"train_{iters}.conf")
        with open(conf, "w") as f:
            f.write(f"""task = train
objective = binary
data = {train_csv}
num_trees = {iters}
num_leaves = {NUM_LEAVES}
max_bin = 255
learning_rate = 0.1
min_data_in_leaf = 20
metric = none
verbosity = -1
output_model = {model_out}
""")
        t0 = time.time()
        subprocess.run([ORACLE, f"config={conf}"], check=True,
                       stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        return time.time() - t0

    model_lo = os.path.join(work, "m_lo.txt")
    t_lo = train(ITERS_LO, model_lo)
    model_hi = os.path.join(work, "m_hi.txt")
    t_hi = train(ITERS_HI, model_hi)
    sec_per_iter = (t_hi - t_lo) / (ITERS_HI - ITERS_LO)

    # held-out AUC at ITERS_LO iterations = the same trained-iteration
    # count as bench.py's quality gate (3 warmup + 10 timed)
    pred_out = os.path.join(work, "pred.txt")
    pconf = os.path.join(work, "pred.conf")
    with open(pconf, "w") as f:
        f.write(f"""task = predict
data = {test_csv}
input_model = {model_lo}
output_result = {pred_out}
""")
    subprocess.run([ORACLE, f"config={pconf}"], check=True,
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    auc = _auc(yte, np.loadtxt(pred_out))

    cpu_model = ""
    try:
        for line in open("/proc/cpuinfo"):
            if line.startswith("model name"):
                cpu_model = line.split(":", 1)[1].strip()
                break
    except OSError:
        pass
    out = {
        "rows": ROWS,
        "num_leaves": NUM_LEAVES,
        "iters_lo": ITERS_LO,
        "iters_timed": ITERS_HI - ITERS_LO,
        "ref_sec_per_iter": round(sec_per_iter, 4),
        "ref_auc_at_iters_lo": round(auc, 5),
        "wall_lo": round(t_lo, 2),
        "wall_hi": round(t_hi, 2),
        "host_cpus": os.cpu_count(),
        "host_cpu_model": cpu_model,
        "note": ("reference CLI measured on THIS host (single benchmark "
                 "process, OpenMP over all host cores); compare with the "
                 "docs-scaled 28-core 2015 anchor in BASELINE.md"),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "oracle_bench.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
