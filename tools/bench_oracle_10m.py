"""Reference oracle CLI on the 10M BASELINE workload (single host core):
same data/params as tools/bench_10m.py, timing excludes load/binning by
differencing two runs (13 vs 63 trees), AUC at 103 trees matches the TPU
run's 3 warmup + 100 timed.  Writes docs/oracle_bench_10m.json."""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench import FEATURES
from tools.higgs_like import auc as _auc, make_higgs_like
from tools.bench_10m import ROWS, TEST_ROWS

ORACLE = "/tmp/lgb_ref_src/lightgbm"
ITERS_LO = 13
ITERS_HI = 63
ITERS_AUC = 103


def write_tsv(path, y, X):
    # np.savetxt is ~10x too slow at 10M rows on one core; format in
    # chunks with a preallocated %.7g vectorized formatter
    with open(path, "w") as f:
        step = 200_000
        for i in range(0, len(y), step):
            block = np.column_stack([y[i:i + step], X[i:i + step]])
            lines = "\n".join(
                "\t".join(f"{v:.7g}" for v in row) for row in block)
            f.write(lines + "\n")


def main():
    if not os.path.exists(ORACLE):
        print("oracle binary missing; run tools/build_reference_oracle.sh",
              file=sys.stderr)
        return 1
    work = tempfile.mkdtemp(prefix="lgb_oracle_10m")
    try:
        X, y = make_higgs_like(ROWS, FEATURES)
        Xte, yte = make_higgs_like(TEST_ROWS, FEATURES, seed=1)
        train_tsv = os.path.join(work, "train.tsv")
        test_tsv = os.path.join(work, "test.tsv")
        t0 = time.time()
        write_tsv(train_tsv, y, X)
        write_tsv(test_tsv, yte, Xte)
        print(f"tsv written in {time.time()-t0:.0f}s", flush=True)

        def train(iters, model_out):
            conf = os.path.join(work, f"train_{iters}.conf")
            with open(conf, "w") as f:
                f.write(f"""task = train
objective = binary
data = {train_tsv}
output_model = {model_out}
num_trees = {iters}
num_leaves = 255
max_bin = 255
learning_rate = 0.1
min_data_in_leaf = 20
num_threads = 1
verbosity = -1
label_column = 0
""")
            t0 = time.time()
            subprocess.run([ORACLE, f"config={conf}"], check=True,
                           stdout=subprocess.DEVNULL)
            return time.time() - t0

        t_lo = train(ITERS_LO, os.path.join(work, "m_lo.txt"))
        print(f"{ITERS_LO} trees: {t_lo:.0f}s", flush=True)
        t_hi = train(ITERS_HI, os.path.join(work, "m_hi.txt"))
        print(f"{ITERS_HI} trees: {t_hi:.0f}s", flush=True)
        t_auc = train(ITERS_AUC, os.path.join(work, "m_auc.txt"))
        print(f"{ITERS_AUC} trees: {t_auc:.0f}s", flush=True)
        pred = os.path.join(work, "pred.txt")
        conf = os.path.join(work, "pred.conf")
        with open(conf, "w") as f:
            f.write(f"""task = predict
data = {test_tsv}
input_model = {os.path.join(work, 'm_auc.txt')}
output_result = {pred}
label_column = 0
""")
        subprocess.run([ORACLE, f"config={conf}"], check=True,
                       stdout=subprocess.DEVNULL)
        scores = np.loadtxt(pred)
        auc = _auc(yte, scores)
        out = {"rows": ROWS, "num_leaves": 255,
               "ref_sec_per_iter": round((t_hi - t_lo)
                                         / (ITERS_HI - ITERS_LO), 4),
               "iters_auc": ITERS_AUC,
               "ref_auc_at_iters": round(float(auc), 5),
               "host_cpus": os.cpu_count(),
               "measured_at": time.strftime("%Y-%m-%d")}
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "oracle_bench_10m.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
