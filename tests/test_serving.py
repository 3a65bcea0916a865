"""Serving daemon suite (docs/Serving.md): registry hot swap, request
coalescing, byte-exactness vs Booster.predict, drain semantics.

The byte-identity oracle is `Booster.predict` with the device path
forced (device_predict=true): the daemon packs the same trees through
the same jitted traversal, so responses must match BIT-FOR-BIT — any
relative-tolerance pass here would hide a cross-wired coalescer split
or a torn hot swap, the two bug classes this suite exists to catch.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.serving import (ServingClient, ServingDaemon,
                                  serve_counters_reset, start_frontend)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_xy(n, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    X[rng.rand(n) < 0.1, 0] = np.nan
    y = ((np.nan_to_num(X[:, 0]) + X[:, 1] > 0)).astype(np.float32)
    return X, y


_PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
           "metric": "none", "min_data_in_leaf": 5,
           "device_predict": "true", "device_predict_min_bucket": 32}


def _train(rounds=8, seed=0, **extra):
    X, y = _mk_xy(600, seed=seed)
    p = dict(_PARAMS)
    p.update(extra)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=rounds)
    bst._gbdt._sync_model()
    return bst, X


def _daemon(**overrides):
    p = dict(_PARAMS, serve_max_batch_rows=256,
             serve_max_coalesce_wait_ms=1.0)
    p.update(overrides)
    serve_counters_reset()
    return ServingDaemon(Config(p)).start()


@pytest.fixture(scope="module")
def served():
    """One daemon + model + oracle booster shared by the read-only
    parity tests (hot-swap / drain tests build their own)."""
    bst, X = _train()
    d = _daemon()
    d.registry.register("m", booster=bst, block=True)
    yield d, bst, X
    d.stop(drain=True, timeout=10)


# ---------------------------------------------------------------- parity
def test_responses_byte_identical_to_booster_predict(served):
    d, bst, X = served
    c = ServingClient(d)
    for n in (1, 7, 32, 100):
        got = c.predict("m", X[:n])
        exp = bst.predict(X[:n])
        assert np.array_equal(got, exp)      # byte-identical, no tolerance
        raw = c.predict("m", X[:n], mode="raw")
        assert np.array_equal(raw, bst.predict(X[:n], raw_score=True))
        leaf = c.predict("m", X[:n], mode="leaf")
        assert np.array_equal(leaf, bst.predict(X[:n], pred_leaf=True))


def test_float64_lossless_served_lossy_rejected(served):
    d, bst, X = served
    X64 = np.asarray(X[:16], np.float64)          # lossless round trip
    assert np.array_equal(d.predict("m", X64), bst.predict(X[:16]))
    bad = X64 + 1e-12                              # not f32-representable
    bad[np.isnan(bad)] = 0.0
    with pytest.raises(ValueError, match="losslessly"):
        d.predict("m", bad)


def test_multiclass_and_dtype_matrix():
    X, _ = _mk_xy(500, seed=3)
    y = np.random.RandomState(5).randint(0, 3, 500).astype(np.float32)
    p = dict(_PARAMS, objective="multiclass", num_class=3, num_leaves=8)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    d = _daemon()
    try:
        d.registry.register("mc", booster=bst, block=True)
        got = d.predict("mc", X[:40])
        assert got.shape == (40, 3)
        assert np.array_equal(got, bst.predict(X[:40]))
        # integer rows are exactly representable -> served
        Xi = np.arange(12, dtype=np.int64).reshape(2, 6)
        assert np.array_equal(d.predict("mc", Xi),
                              bst.predict(Xi.astype(np.float32)))
    finally:
        d.stop()


def test_zero_new_traces_after_warmup(served):
    d, _, X = served
    base = d.registry.serve_recompiles()
    for n in (1, 3, 17, 33, 64, 128, 200, 256):
        d.predict("m", X[:n])
        d.predict("m", X[:n], mode="raw")
    assert d.registry.serve_recompiles() == base == 0


# ------------------------------------------------------------- coalescing
def test_coalescer_merges_concurrent_requests():
    bst, X = _train()
    d = _daemon(serve_max_coalesce_wait_ms=150.0)
    try:
        d.registry.register("m", booster=bst, block=True)
        before = d.stats()
        futs = []
        starts = [5 * i for i in range(8)]
        for s in starts:
            futs.append((s, d.submit("m", X[s:s + 3])))
        outs = [(s, f.result(timeout=30)) for s, f in futs]
        after = d.stats()
        # merged: 8 requests, ONE coalesced dispatch window
        assert after["serve_requests"] - before["serve_requests"] == 8
        assert after["serve_batches"] - before["serve_batches"] == 1
        # split back per request, no cross-wiring
        exp = bst.predict(X)
        for s, out in outs:
            assert np.array_equal(out, exp[s:s + 3])
    finally:
        d.stop()


def test_coalescer_wait_zero_dispatches_immediately():
    bst, X = _train()
    d = _daemon(serve_max_coalesce_wait_ms=0.0)
    try:
        d.registry.register("m", booster=bst, block=True)
        before = d.stats()["serve_batches"]
        for _ in range(4):
            d.predict("m", X[:2])      # sequential: nothing to merge
        assert d.stats()["serve_batches"] - before == 4
    finally:
        d.stop()


def test_coalescer_wait_bounds_latency():
    """A lone request must not wait out a large coalesce window many
    times over: the wait is ONE bounded window after the first pop."""
    bst, X = _train()
    d = _daemon(serve_max_coalesce_wait_ms=100.0)
    try:
        d.registry.register("m", booster=bst, block=True)
        d.predict("m", X[:2])          # warm the dispatch path
        t0 = time.monotonic()
        d.predict("m", X[:2], timeout=30)
        elapsed_ms = (time.monotonic() - t0) * 1000
        assert elapsed_ms < 1000.0, elapsed_ms
    finally:
        d.stop()


# ---------------------------------------------------------------- hot swap
def test_hot_swap_under_concurrent_load_never_tears():
    b1, X = _train(rounds=6, seed=1)
    b2, _ = _train(rounds=14, seed=1)
    pool = X[:256]
    exp = {1: b1.predict(pool), 2: b2.predict(pool)}
    assert not np.allclose(exp[1], exp[2])
    d = _daemon()
    try:
        h1 = d.registry.register("m", booster=b1, block=True)
        errors, mismatches, done = [], [], [0]
        lock = threading.Lock()

        def client(tid):
            r = np.random.RandomState(tid)
            for _ in range(40):
                s, n = int(r.randint(0, 250)), int(r.randint(1, 6))
                try:
                    fut = d.submit("m", pool[s:s + n])
                    out = fut.result(timeout=30)
                    # response matches EXACTLY the version that served
                    # it — old or new, never a mix, never garbage
                    if not np.array_equal(out, exp[fut.version][s:s + n]):
                        with lock:
                            mismatches.append((fut.version, s, n))
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))
                with lock:
                    done[0] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.02)
        h2 = d.registry.register("m", booster=b2, block=False)  # mid-load
        for t in threads:
            t.join(timeout=120)
        h2.wait(timeout=60)
        assert done[0] == 240 and not errors and not mismatches
        assert h2.entry.version == 2
        # new traffic serves v2; retired v1 freed once idle
        fut = d.submit("m", pool[:4])
        assert fut.result(timeout=30) is not None and fut.version == 2
        deadline = time.monotonic() + 10
        while not h1.entry.released and time.monotonic() < deadline:
            time.sleep(0.02)
        assert h1.entry.released and h1.entry.in_flight == 0
        assert d.registry.serve_recompiles() == 0
    finally:
        d.stop()


def test_failed_load_keeps_old_version_serving():
    bst, X = _train()
    d = _daemon()
    try:
        d.registry.register("m", booster=bst, block=True)
        h = d.registry.register("m", model_file="/nonexistent/model.txt")
        with pytest.raises(RuntimeError, match="failed to load"):
            h.wait(timeout=30)
        assert h.error is not None
        # old version unaffected
        assert np.array_equal(d.predict("m", X[:8]), bst.predict(X[:8]))
        assert d.registry.stats()["models"]["m"]["version"] == 1
    finally:
        d.stop()


def test_register_rejects_linear_trees():
    rng = np.random.RandomState(2)
    X = rng.rand(400, 4)
    y = (X @ rng.rand(4)).astype(np.float64)
    bst = lgb.train({"objective": "regression", "linear_tree": True,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    d = _daemon()
    try:
        h = d.registry.register("lin", booster=bst)
        with pytest.raises(RuntimeError, match="device-servable"):
            h.wait(timeout=30)
    finally:
        d.stop()


# ---------------------------------------------------------------- rejects
def test_unknown_model_and_feature_mismatch_rejected(served):
    d, _, X = served
    with pytest.raises(KeyError, match="No model"):
        d.predict("nope", X[:2])
    with pytest.raises(ValueError, match="features"):
        d.predict("m", X[:2, :4])     # width mismatch would re-trace
    with pytest.raises(ValueError, match="mode"):
        d.predict("m", X[:2], mode="bogus")
    assert d.registry.serve_recompiles() == 0


# ------------------------------------------------------------- early stop
def test_early_stop_serving_matches_booster():
    bst, X = _train(rounds=20)
    d = _daemon(pred_early_stop=True, pred_early_stop_freq=3,
                pred_early_stop_margin=0.5)
    try:
        d.registry.register("m", booster=bst, block=True)
        got = d.predict("m", X[:64], mode="raw")
        exp = bst.predict(X[:64], raw_score=True, pred_early_stop=True,
                          pred_early_stop_freq=3,
                          pred_early_stop_margin=0.5)
        assert np.array_equal(got, exp)
        # early stopping actually engaged (differs from the full sum)
        assert not np.allclose(got, bst.predict(X[:64], raw_score=True))
        assert d.registry.serve_recompiles() == 0
    finally:
        d.stop()


# ------------------------------------------------------------------- DART
def test_dart_mid_training_model_serves_current_drop_state():
    X, y = _mk_xy(600, seed=4)
    p = dict(_PARAMS, boosting="dart", drop_rate=0.9, skip_drop=0.0,
             learning_rate=0.3)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=6)
    g = bst._gbdt
    g._sync_model()
    d = _daemon()
    try:
        d.registry.register("dart", booster=bst, block=True)
        assert np.array_equal(d.predict("dart", X[:32]),
                              bst.predict(X[:32]))
        # mutate drop state in place (what the next train iteration
        # does): a re-register must repack the CURRENT weights
        g.pre_gradient_hook()
        assert g.drop_index_, "drop did not trigger; bump drop_rate"
        d.registry.register("dart", booster=bst, block=True)
        got = d.predict("dart", X[:32])
        assert np.array_equal(got, bst.predict(X[:32]))
    finally:
        d.stop()


# ------------------------------------------------------------------ stats
def test_stats_and_latency_window(served):
    d, _, X = served
    d.predict("m", X[:8])
    s = d.stats()
    assert s["serve_requests"] >= 1 and s["serve_errors"] == 0
    assert s["serve_p50_ms"] is not None and s["serve_p99_ms"] is not None
    assert s["serve_p50_ms"] <= s["serve_p99_ms"] or np.isclose(
        s["serve_p50_ms"], s["serve_p99_ms"])
    assert "m" in s["models"] and s["models"]["m"]["in_flight"] == 0


def test_roofline_stats_and_sampled_traces(served):
    """ISSUE 11: serving stats carry a measured dispatch-site roofline
    (warmup excluded) and sampled requests leave stage-waterfall traces
    in the flight recorder."""
    from lightgbm_tpu.observability.flightrec import flight_recorder
    d, _, X = served
    before = len(flight_recorder.trace_tail(256))
    # serve_trace_sample defaults to 64: push enough requests through
    # that at least one gets traced
    for i in range(70):
        d.predict("m", X[i % 16:(i % 16) + 4])
    rl = d.stats().get("roofline")
    assert rl is not None and rl["dispatches"] >= 70
    # a utilization is reported only against a TPU's peaks
    assert rl["measured_mfu"] is None
    assert rl["bound"] in ("compute", "hbm")
    assert rl["flops"] > 0 and rl["dispatch_s"] > 0
    traces = flight_recorder.trace_tail(256)
    assert len(traces) > before
    t = traces[-1]
    assert t["model"] == "m" and t["version"] >= 1
    # stage waterfall is monotone: enqueue(0) <= coalesce <= dispatch
    # <= settle <= respond
    stages = [t["coalesce_ms"], t["dispatch_ms"],
              t["device_settle_ms"], t["respond_ms"]]
    assert all(s is not None for s in stages)
    assert stages == sorted(stages) and stages[0] >= 0
    # the coalesce-batch histogram counted these dispatches
    assert sum(flight_recorder.contents()
               ["coalesce_batch_requests_hist"]) > 0


def test_metrics_port_http_and_op_metrics(served):
    """The daemon's two scrape surfaces: GET /metrics (fleet-facing)
    and op=metrics on the TCP wire — same Prometheus text."""
    import urllib.request

    from lightgbm_tpu.observability import start_metrics_http
    d, _, X = served
    d.predict("m", X[:4])
    srv = start_metrics_http(port=0, daemon=d)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30
        ).read().decode()
    finally:
        srv.shutdown()
    assert "# TYPE lgbm_serve_requests counter" in body
    assert 'lgbm_serve_latency_ms{quantile="0.99"}' in body
    assert 'lgbm_serve_model_version{model="m"} 1' in body
    assert "lgbm_serve_queue_pending" in body
    assert 'lgbm_serve_requests_by_model{model="m"}' in body
    fe = start_frontend(d, port=0)
    try:
        port = fe.server_address[1]
        with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
            f = s.makefile("rwb")
            f.write(b'{"op": "metrics"}\n')
            f.flush()
            resp = json.loads(f.readline())
    finally:
        fe.shutdown()
    assert resp["ok"]
    assert "# TYPE lgbm_serve_requests counter" in resp["metrics"]


# --------------------------------------------------------------- frontend
def test_tcp_frontend_round_trip(served):
    d, bst, X = served
    srv = start_frontend(d, port=0)
    try:
        port = srv.server_address[1]
        with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
            f = s.makefile("rwb")
            f.write((json.dumps(
                {"model": "m", "rows": X[:3].tolist()}) + "\n").encode())
            f.flush()
            resp = json.loads(f.readline())
            assert resp["ok"] and resp["version"] == 1
            np.testing.assert_allclose(resp["preds"], bst.predict(X[:3]),
                                       rtol=0, atol=0)
            f.write(b'{"op": "stats"}\n')
            f.flush()
            stats = json.loads(f.readline())
            assert stats["ok"] and "serve_requests" in stats["stats"]
            f.write(b'not json\n')
            f.flush()
            err = json.loads(f.readline())
            assert not err["ok"]
            f.write((json.dumps(
                {"model": "ghost", "rows": [[0.0] * 6]}) + "\n").encode())
            f.flush()
            assert not json.loads(f.readline())["ok"]
    finally:
        srv.shutdown()


# ----------------------------------------------------------------- SIGTERM
_SIGTERM_CHILD = r"""
import os, sys, threading, time
sys.path.insert(0, os.environ["SERVE_REPO"])
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.observability import set_event_logger
from lightgbm_tpu.observability.events import EventLogger
from lightgbm_tpu.serving import ServingDaemon

rng = np.random.RandomState(0)
X = rng.randn(400, 6).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                 "min_data_in_leaf": 5, "device_predict": "true",
                 "device_predict_min_bucket": 32},
                lgb.Dataset(X, label=y), num_boost_round=4)
set_event_logger(EventLogger(os.environ["SERVE_METRICS"]))
cfg = Config({"device_predict": "true", "device_predict_min_bucket": 32,
              "serve_max_batch_rows": 128, "verbosity": -1,
              # big window: queued requests SIT until drain proves them
              "serve_max_coalesce_wait_ms": 5000.0,
              "serve_drain_timeout_s": 30.0})
daemon = ServingDaemon(cfg).start()
daemon.registry.register("m", booster=bst, block=True)
daemon.install_signal_handlers()
futs = [daemon.submit("m", X[i:i+2]) for i in range(24)]
print("SUBMITTED", len(futs), flush=True)
def watch():
    for f in futs:
        f.result(timeout=60)
    print("ALL_COMPLETED", flush=True)
threading.Thread(target=watch, daemon=True).start()
time.sleep(60)
"""


def test_sigterm_drains_queue_and_exits_143(tmp_path):
    """SIGTERM mid-backlog: every queued request completes (drain), a
    `serve_drain` event lands, and the exit status stays `killed by
    SIGTERM` so supervisors classify *preempt* — the serving analogue
    of training's checkpoint-on-demand."""
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    script = tmp_path / "child.py"
    script.write_text(_SIGTERM_CHILD)
    env = dict(os.environ, SERVE_REPO=REPO, SERVE_METRICS=str(metrics),
               JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, str(script)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    try:
        # wait for the backlog to be queued, then preempt
        t0 = time.monotonic()
        while time.monotonic() - t0 < 120:
            line = proc.stdout.readline()
            if "SUBMITTED" in line:
                break
        else:
            pytest.fail("child never submitted its backlog")
        time.sleep(0.3)
        proc.send_signal(signal.SIGTERM)
        out_rest, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode in (-signal.SIGTERM, 143), (proc.returncode,
                                                       out_rest)
    assert "ALL_COMPLETED" in out_rest
    events = []
    for pth in metrics.glob("events-rank*.jsonl"):
        for ln in pth.read_text().splitlines():
            events.append(json.loads(ln))
    kinds = [e.get("event") for e in events]
    assert "serve_drain" in kinds
    drain = [e for e in events if e.get("event") == "serve_drain"][-1]
    assert drain["drained"] is True and drain["requests"] >= 24


_CLI_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["SERVE_REPO"])
from lightgbm_tpu.cli import main
sys.exit(main(sys.argv[1:]))
"""


def test_model_load_does_not_clobber_verbosity(tmp_path):
    """Loading a model builds a quiet predictor-mode Config; that must
    not silence the PROCESS log level — the daemon loads models
    mid-flight and its swap/drain logs have to keep flowing (this bug
    ate the CLI serve banner until fixed)."""
    from lightgbm_tpu.utils import log as _log
    bst, _ = _train(rounds=2)
    f = tmp_path / "m.txt"
    bst.save_model(str(f))
    prev = _log.get_verbosity()
    try:
        _log.set_verbosity(1)
        lgb.Booster(model_file=str(f))
        assert _log.get_verbosity() == 1
    finally:
        _log.set_verbosity(prev)


def test_cli_serve_end_to_end(tmp_path):
    """`python -m lightgbm_tpu serve`: loads + warms the model file,
    answers over the TCP front end, and SIGTERM drains + exits 143.
    (The child is on the CPU through JAX_PLATFORMS=cpu in its
    environment alone.)"""
    bst, X = _train(rounds=4)
    model = tmp_path / "model.txt"
    bst.save_model(str(model))
    script = tmp_path / "cli_child.py"
    script.write_text(_CLI_CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1",
               SERVE_REPO=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-u", str(script), "serve",
         f"serve_models=m={model}", "serve_port=0", "verbosity=1",
         "device_predict=true", "device_predict_min_bucket=32",
         "serve_max_batch_rows=64"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    try:
        port = None
        t0 = time.monotonic()
        while time.monotonic() - t0 < 180:
            line = proc.stdout.readline()
            if "front end listening on" in line:
                port = int(line.rsplit(":", 1)[1])
                break
            if proc.poll() is not None:
                pytest.fail(f"CLI serve exited early: {line}")
        assert port is not None, "front end never came up"
        with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
            f = s.makefile("rwb")
            f.write((json.dumps(
                {"model": "m", "rows": X[:2].tolist()}) + "\n").encode())
            f.flush()
            resp = json.loads(f.readline())
        assert resp["ok"]
        np.testing.assert_allclose(resp["preds"], bst.predict(X[:2]),
                                   rtol=1e-6, atol=1e-6)
        proc.send_signal(signal.SIGTERM)
        proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode in (-signal.SIGTERM, 143)


# -------------------------------------------------- adaptive coalescing
def test_adaptive_coalesce_wait_decision():
    """The EWMA decision function (docs/Serving.md): static mode keeps
    the configured window unconditionally; adaptive mode keeps it under
    burst (EWMA gap <= window) and shrinks to 0 when arrivals are
    sparse (gap beyond the window) or unknown (no history)."""
    from lightgbm_tpu.serving import Coalescer
    static = Coalescer(max_wait_ms=40.0)
    assert static.effective_wait_s() == pytest.approx(0.040)
    c = Coalescer(max_wait_ms=40.0, adaptive=True)
    assert c.effective_wait_s() == 0.0          # no history yet
    with c._lock:
        c._ewma_gap_s = 0.001                   # burst: 1 ms gaps
    assert c.effective_wait_s() == pytest.approx(0.040)
    with c._lock:
        c._ewma_gap_s = 0.300                   # sparse: 300 ms gaps
    assert c.effective_wait_s() == 0.0


def test_adaptive_coalesce_sparse_p50_drops_vs_static():
    """Sparse sequential load: the static window makes EVERY request
    wait out serve_max_coalesce_wait_ms for batch-mates that never
    come; the adaptive window learns the arrival gap exceeds the
    window and dispatches immediately — p50 drops by at least the
    window."""
    bst, X = _train()

    def run(adaptive):
        d = _daemon(serve_max_coalesce_wait_ms=60.0,
                    serve_adaptive_coalesce="auto" if adaptive else "off")
        d.registry.register("m", booster=bst, block=True)
        try:
            lats = []
            for i in range(8):
                fut = d.submit("m", X[i:i + 1])
                fut.result(timeout=60)
                lats.append(fut.latency_ms)
                time.sleep(0.09)     # arrival gap 90 ms > 60 ms window
            return float(np.median(lats))
        finally:
            d.stop(drain=True, timeout=10)

    static_p50 = run(adaptive=False)
    adaptive_p50 = run(adaptive=True)
    assert static_p50 >= 55.0         # every request waits the window
    assert adaptive_p50 < static_p50 - 40.0


def test_adaptive_coalesce_burst_batches_unchanged():
    """Burst load: once the EWMA has seen burst-rate gaps, adaptive
    mode keeps the FULL static window, so bursts coalesce into the
    same fused dispatches as the static config (the batching
    efficiency the window exists to buy)."""
    bst, X = _train()

    def run(adaptive):
        from lightgbm_tpu.observability.registry import global_registry
        serve_counters_reset()
        d = _daemon(serve_max_coalesce_wait_ms=40.0,
                    serve_adaptive_coalesce="auto" if adaptive else "off")
        d.registry.register("m", booster=bst, block=True)
        try:
            for _round in range(3):   # round 0 warms the EWMA
                futs = [d.submit("m", X[i:i + 1]) for i in range(12)]
                for fut in futs:
                    fut.result(timeout=60)
            reqs = global_registry.counter("serve_requests")
            disp = global_registry.counter("serve_dispatches")
            return reqs / max(disp, 1)
        finally:
            d.stop(drain=True, timeout=10)

    static_ratio = run(adaptive=False)
    adaptive_ratio = run(adaptive=True)
    # both must coalesce bursts into fused dispatches (>= 2 requests
    # per dispatch on average), adaptive no worse than ~half static
    assert static_ratio >= 2.0
    assert adaptive_ratio >= 2.0
    assert adaptive_ratio >= 0.5 * static_ratio


# ------------------------------------------------------- UDS front end
def test_uds_frontend_round_trip_and_drain(tmp_path):
    """The Unix-socket front end speaks the SAME wire as TCP: predict
    (byte-identical to Booster.predict), health, metrics and publish
    all answer; after a drain-stop the daemon rejects instead of
    wedging the socket."""
    from lightgbm_tpu.serving import start_uds_frontend
    bst, X = _train()
    d = _daemon()
    d.registry.register("m", booster=bst, block=True)
    sock = str(tmp_path / "serve.sock")
    srv = start_uds_frontend(d, sock, request_timeout_s=60.0)
    try:
        c = ServingClient.connect_uds(sock)
        got = c.predict("m", X[:5])
        assert np.array_equal(got, bst.predict(X[:5]))  # byte-identical
        h = c.health()
        assert h["ready"] and h["models"] == {"m": 1}
        assert "m" in c.models()
        assert c.stats()["serve_requests"] >= 1
        # op=publish over the same socket: the rollout hook works on
        # UDS exactly like TCP (same handler)
        model2 = tmp_path / "m2.txt"
        bst2, _ = _train(rounds=4, seed=3)
        bst2.save_model(str(model2))
        from lightgbm_tpu.serving import LineClient
        lc = LineClient(uds_path=sock)
        reply = lc.request({"op": "publish", "model": "m",
                            "path": str(model2)}, timeout_s=120)
        assert reply["ok"] and reply["version"] == 2
        got2 = c.predict("m", X[:5])
        assert np.array_equal(got2, bst2.predict(X[:5]))
        reply = lc.request({"op": "metrics"}, timeout_s=30)
        assert reply["ok"] and "lgbm_serve_requests" in reply["metrics"]
        lc.close()
        # drain: stop the daemon, the socket answers a structured error
        d.stop(drain=True, timeout=10)
        reply = LineClient(uds_path=sock).request(
            {"model": "m", "rows": X[:1].tolist()}, timeout_s=30)
        assert not reply["ok"] and "error" in reply
        c.close()
    finally:
        srv.shutdown()
        d.stop(drain=False)


def test_uds_stale_socket_is_replaced(tmp_path):
    from lightgbm_tpu.serving import start_uds_frontend
    bst, X = _train()
    sock = str(tmp_path / "serve.sock")
    open(sock, "w").close()           # stale file where the socket goes
    d = _daemon()
    d.registry.register("m", booster=bst, block=True)
    srv = start_uds_frontend(d, sock)
    try:
        got = ServingClient.connect_uds(sock).predict("m", X[:2])
        assert np.array_equal(got, bst.predict(X[:2]))
    finally:
        srv.shutdown()
        d.stop(drain=True, timeout=10)
