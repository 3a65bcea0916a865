"""Benchmark: sec/iteration on a Higgs-like binary workload (driver contract).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

Every mode runs on the backend JAX finds; nothing here probes for a
device in a child or re-executes on the CPU.  The default mode (the
training bench) measures the chip and exits non-zero when JAX's backend
is not a TPU, when the on-chip kernel checks are not "ok", or when the
IR audit raises.  `--serve`, `--serve-fleet`, `--online` and
`--multichip` are correctness drills that also run under
JAX_PLATFORMS=cpu (tools/verify.sh); where one of them pins its
children to the CPU on purpose, the comment there says "CPU drill" and
why.

`python bench.py --diff A.json B.json` instead compares two saved bench
lines' per-phase `timer_top_ms` breakdowns (perf-PR review mode,
ROADMAP PR-2 follow-up): per-scope ms/calls for both runs, delta and
ratio, plus the headline sec/iter movement.

Baseline anchor (BASELINE.md): reference CPU LightGBM trains Higgs (10.5M rows,
28 features, num_leaves=255, 500 iters) in 130.094 s => 0.260 s/iter
(docs/Experiments.rst:110-123).  This bench runs the same config shape on a
synthetic Higgs-like dataset at BENCH_ROWS rows (default 1M; the real Higgs
file is not downloadable in this environment) and scales the baseline
linearly in rows for vs_baseline — the reference's histogram cost is linear in
num_data, so sec_per_iter_baseline ~ 0.260 * rows / 10.5e6.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tools.higgs_like import auc as _auc, make_higgs_like  # noqa: E402

ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
FEATURES = 28
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
ITERS = int(os.environ.get("BENCH_ITERS", 10))
WARMUP = 3
BASELINE_SEC_PER_ITER_10M = 130.094 / 500  # ref docs/Experiments.rst
HIGGS_ROWS = 10_500_000


def _require_tpu():
    """The training bench measures the chip: on any other backend it
    stops before generating data.  No probe child, no CPU re-exec — the
    process that asks JAX for its devices is the one that trains."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: no TPU found (JAX's default backend is "
                 f"{dev.platform!r}); the training bench runs on the chip "
                 "only and does not fall back")
    return dev


def diff_main(path_a, path_b):
    """Compare two bench JSON lines' timer_top_ms breakdowns per phase.

    The timer_top_ms field is [[scope, total_ms, calls], ...] over the 3
    instrumented post-loop iterations (docs/Observability.md).  Scopes
    present in only one run are listed with the other side blank — a
    new/removed phase is exactly what a perf-PR review needs to see."""
    runs = []
    for p in (path_a, path_b):
        with open(p) as f:
            runs.append(json.load(f))
    a, b = runs
    ta = {name: (ms, cnt) for name, ms, cnt in a.get("timer_top_ms", [])}
    tb = {name: (ms, cnt) for name, ms, cnt in b.get("timer_top_ms", [])}
    # keep A's ordering (slowest first), then B-only scopes
    names = [n for n, _, _ in a.get("timer_top_ms", [])]
    names += [n for n, _, _ in b.get("timer_top_ms", []) if n not in ta]
    wn = max([len(n) for n in names] + [5])
    print(f"{'phase':<{wn}} {'A ms':>10} {'B ms':>10} {'delta':>10} "
          f"{'ratio':>7}  calls A->B")
    for n in names:
        ma, ca = ta.get(n, (None, None))
        mb, cb = tb.get(n, (None, None))
        sa = f"{ma:.1f}" if ma is not None else "-"
        sb = f"{mb:.1f}" if mb is not None else "-"
        if ma is not None and mb is not None:
            delta = f"{mb - ma:+.1f}"
            ratio = f"{mb / ma:.2f}x" if ma > 0 else "-"
        else:
            delta, ratio = "-", "-"
        calls = f"{ca if ca is not None else '-'}" \
                f"->{cb if cb is not None else '-'}"
        print(f"{n:<{wn}} {sa:>10} {sb:>10} {delta:>10} {ratio:>7}  {calls}")
    va, vb = a.get("value"), b.get("value")
    if va and vb:
        print(f"headline: {va} -> {vb} {a.get('unit', 's/iter')} "
              f"({vb / va:.3f}x; {'faster' if vb < va else 'slower'} B)")
    for key in ("auc", "quality_mode_sec_per_iter", "quality_mode_auc",
                "peak_device_bytes", "backend", "host_block_ms_per_iter",
                "setup_construct_s", "setup_compile_s"):
        if a.get(key) is not None or b.get(key) is not None:
            print(f"{key}: {a.get(key)} -> {b.get(key)}")
    return 0


def _predict_throughput(booster, X):
    """Serving-side rows/s for the three predict paths (ISSUE 4): the
    jitted device traversal, the native (single-core C) batch predictor,
    and the pure-Python per-tree loop.  Device/python row counts shrink
    off-TPU so the phase stays inside the bench budget; the reported
    number is a RATE either way."""
    import jax
    g = booster._gbdt
    g._sync_model()
    on_tpu = jax.default_backend() == "tpu"
    out = {}

    def timed(fn, rows, warmup=True):
        if warmup:
            fn()
        t0 = time.time()
        fn()
        dt = time.time() - t0
        return round(rows / max(dt, 1e-9), 1)

    # device path: forced on (auto would skip off-TPU); float32 input
    n_dev = X.shape[0] if on_tpu else min(X.shape[0], 200_000)
    Xd = np.ascontiguousarray(X[:n_dev], np.float32)
    prev_mode = g.config.device_predict
    try:
        g.config.device_predict = "true"
        hit = g._device_predictor(Xd, 0, -1)
        if hit is not None:
            dp, Xd = hit
            out["device"] = timed(lambda: dp.predict_raw(Xd), n_dev)
            out["device_rows"] = n_dev
    finally:
        g.config.device_predict = prev_mode

    # native path (PackedPredictor, OpenMP where available)
    K = g.num_tree_per_iteration
    total_iters = len(g.models_) // max(K, 1)
    packed = g._packed_for(0, total_iters, K)
    X64 = np.ascontiguousarray(X, np.float64)
    if packed is not None:
        out["native"] = timed(
            lambda: packed.predict(X64, K, g.average_output_), X.shape[0])
        out["native_rows"] = X.shape[0]

    # pure-Python per-tree loop (the fallback path), subsampled: at 1M
    # rows x hundreds of leaves it would take minutes on this host
    n_py = min(X.shape[0], 50_000)
    Xp = X64[:n_py]

    def py_path():
        acc = np.zeros(n_py)
        for t in g.models_:
            acc += t.predict(Xp)
        return acc

    out["python"] = timed(py_path, n_py, warmup=False)
    out["python_rows"] = n_py
    return out


def serve_main(smoke: bool = False) -> int:
    """Closed-loop serving bench (ISSUE 10): `python bench.py --serve`.

    Drives the serving daemon with S concurrent closed-loop streams
    (one outstanding request per stream, resubmitted on completion),
    hot-swaps a second model mid-run, and prints ONE JSON line with
    `serve_p50_ms` / `serve_p99_ms` / `serve_rows_per_s` /
    `serve_recompiles`.  Every response is checked BYTE-IDENTICAL
    against `Booster.predict` of the model version that served it —
    a swap may answer with either version, never a mix, never a drop.

    Streams are multiplexed over a small thread pool (S streams / T
    threads, each thread submits its streams' requests then waits them
    all — one outstanding request per stream, closed-loop): the CPU
    container has a single core, so S OS threads would bench the GIL,
    not the daemon.  `--smoke` shrinks everything for the verify gate.
    """
    import jax

    import threading

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.serving import ServingDaemon

    streams = int(os.environ.get("BENCH_SERVE_STREAMS",
                                 64 if smoke else 1024))
    rounds = int(os.environ.get("BENCH_SERVE_ROUNDS", 3 if smoke else 10))
    req_rows = int(os.environ.get("BENCH_SERVE_REQ_ROWS", 4))
    n_threads = max(1, min(16, streams))
    per_thread = max(1, streams // n_threads)
    streams = n_threads * per_thread

    # model pair: v2 continues v1 so the swap changes every score
    Xtr, ytr = make_higgs_like(20_000, FEATURES, seed=7)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 20, "device_predict": "true",
              "device_predict_min_bucket": 128}
    b1 = lgb.train(params, lgb.Dataset(Xtr, label=ytr), num_boost_round=20)
    b2 = lgb.train(params, lgb.Dataset(Xtr, label=ytr), num_boost_round=40)

    pool, _ = make_higgs_like(4096, FEATURES, seed=8)
    pool = np.ascontiguousarray(pool, np.float32)
    # expected scores per version VIA Booster.predict (the acceptance
    # oracle); responses must match the serving version byte-for-byte
    expected = {1: b1.predict(pool), 2: b2.predict(pool)}

    cfg = Config({**params,
                  "serve_max_batch_rows": 4096,
                  "serve_queue_depth": max(streams * 2, 64),
                  "metrics_port": 0,  # ephemeral /metrics; scraped below
                  "serve_max_coalesce_wait_ms": float(
                      os.environ.get("BENCH_SERVE_WAIT_MS", 2.0))})
    daemon = ServingDaemon(cfg).start()
    v1_handle = daemon.registry.register("higgs", booster=b1, block=True)
    warmup_recompiles = daemon.registry.serve_recompiles()

    latencies: list = []
    failures: list = []
    lat_lock = threading.Lock()
    rows_served = [0]
    versions_seen: set = set()
    swap_gate = threading.Event()
    start_gate = threading.Barrier(n_threads + 1)

    def slice_for(stream: int, rnd: int):
        start = ((stream * 2654435761 + rnd * 97) % (len(pool) - req_rows))
        return start, pool[start:start + req_rows]

    def client(tid: int) -> None:
        start_gate.wait()
        my_streams = range(tid * per_thread, (tid + 1) * per_thread)
        for rnd in range(rounds):
            futs = []
            for s in my_streams:
                start, rows = slice_for(s, rnd)
                try:
                    futs.append((start, daemon.submit("higgs", rows)))
                except Exception as e:  # noqa: BLE001
                    with lat_lock:
                        failures.append(f"submit:{e}")
            for start, fut in futs:
                try:
                    out = fut.result(timeout=120)
                except Exception as e:  # noqa: BLE001
                    with lat_lock:
                        failures.append(f"result:{e}")
                    continue
                exp = expected[fut.version][start:start + req_rows]
                ok = np.array_equal(out, exp)
                with lat_lock:
                    latencies.append(fut.latency_ms)
                    rows_served[0] += req_rows
                    versions_seen.add(fut.version)
                    if not ok:
                        failures.append(
                            f"mismatch v{fut.version}@{start}")
            if tid == 0 and rnd == max(rounds // 2 - 1, 0):
                swap_gate.set()  # main hot-swaps while rounds continue

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_threads)]
    for t in threads:
        t.start()
    t0 = time.time()
    start_gate.wait()
    swap_gate.wait(timeout=300)
    # hot swap MID-LOAD: the v2 load+warmup runs on a background thread
    # while v1 keeps serving; in-flight requests finish on v1
    swap_handle = daemon.registry.register("higgs", booster=b2, block=False)
    for t in threads:
        t.join(timeout=600)
    wall = time.time() - t0
    swap_handle.wait(timeout=120)

    # post-swap phase: the background v2 warmup typically outlasts the
    # closed-loop rounds, so prove the swap END state explicitly — v2
    # serves byte-identically and the retired v1 entry released its
    # device buffers once its last in-flight request finished
    for i in range(16):
        start, rows = slice_for(i, rounds)
        fut = daemon.submit("higgs", rows)
        out = fut.result(timeout=120)
        versions_seen.add(fut.version)
        if fut.version != 2 or not np.array_equal(
                out, expected[2][start:start + req_rows]):
            failures.append(f"post-swap mismatch v{fut.version}@{start}")
    if not v1_handle.entry.released:
        failures.append("retired v1 entry still holds device buffers")

    recompiles = daemon.registry.serve_recompiles() - warmup_recompiles
    stats = daemon.stats()

    # Prometheus scrape gate (docs/Observability.md): the fleet/router
    # layer consumes GET /metrics, so the bench asserts a parseable page
    # with the serve counters and tail-latency quantile gauges present
    metrics_scrape_ok = False
    scrape_error = None
    try:
        import urllib.request
        port = daemon.metrics_server.port
        page = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
        required = ("lgbm_serve_requests", "lgbm_serve_rows",
                    'lgbm_serve_latency_ms{quantile="0.5"}',
                    'lgbm_serve_latency_ms{quantile="0.99"}',
                    "lgbm_serve_queue_pending",
                    'lgbm_serve_requests_by_model{model="higgs"}')
        missing = [r for r in required if r not in page]
        # every exposition line must be a comment or name[{labels}] value
        malformed = [ln for ln in page.splitlines()
                     if ln and not ln.startswith("#")
                     and len(ln.rsplit(" ", 1)) != 2]
        if missing:
            scrape_error = f"missing series: {missing}"
        elif malformed:
            scrape_error = f"malformed lines: {malformed[:3]}"
        else:
            metrics_scrape_ok = True
    except Exception as e:  # noqa: BLE001 - reported in the JSON line
        scrape_error = str(e)

    serve_roofline = stats.get("roofline")
    daemon.stop(drain=True, timeout=30)

    lat = np.asarray(latencies, np.float64)
    n_req = streams * rounds
    hot_swap_ok = (not failures and len(lat) == n_req
                   and swap_handle.entry is not None
                   and swap_handle.entry.version == 2
                   and versions_seen == {1, 2})
    out = {
        "metric": "serve_closed_loop",
        "value": round(float(np.percentile(lat, 99)), 3) if len(lat) else None,
        "unit": "p99_ms",
        "serve_p50_ms": round(float(np.percentile(lat, 50)), 3)
        if len(lat) else None,
        "serve_p99_ms": round(float(np.percentile(lat, 99)), 3)
        if len(lat) else None,
        "serve_rows_per_s": round(rows_served[0] / max(wall, 1e-9), 1),
        "serve_requests_per_s": round(len(lat) / max(wall, 1e-9), 1),
        "serve_recompiles": int(recompiles),
        "streams": streams,
        "rounds": rounds,
        "request_rows": req_rows,
        "requests": int(len(lat)),
        "rows": int(rows_served[0]),
        "hot_swap_ok": bool(hot_swap_ok),
        "versions_seen": sorted(versions_seen),
        "coalesced_batches": int(stats["serve_batches"]),
        "coalesce_wait_ms": cfg.serve_max_coalesce_wait_ms,
        "metrics_scrape_ok": bool(metrics_scrape_ok),
        "metrics_scrape_error": scrape_error,
        "serve_measured_mfu": (round(serve_roofline["measured_mfu"], 7)
                               if serve_roofline
                               and serve_roofline.get("measured_mfu")
                               is not None else None),
        "serve_roofline_bound": (serve_roofline or {}).get("bound"),
        "errors": failures[:5],
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
    }
    print(json.dumps(out))
    ok = hot_swap_ok and recompiles == 0 and metrics_scrape_ok
    return 0 if ok else 1


def _parse_fleet_faults(smoke: bool) -> dict:
    """BENCH_FLEET_FAULT=replica_crash@N,serve_slow@N,serve_shed@N,
    canary_diverge@N — the fleet bench's chaos spec.  replica_crash /
    serve_slow / serve_shed become LGBM_TPU_FAULT specs injected into a
    replica's environment (@N = that replica's N-th accepted request);
    canary_diverge@N is a bench-level drill: once N client requests
    have succeeded, publish a deliberately-divergent model as a canary
    and demand the auto-rollback.  The default (smoke included) drills
    one crash, one shed, one slow dispatcher (the SLO-burn bait: the
    armed sleep stalls the dispatch loop, so every queued request
    behind it breaches the latency SLO at once), and one divergent
    canary."""
    raw = os.environ.get("BENCH_FLEET_FAULT")
    if raw is None:
        raw = ("replica_crash@25,serve_shed@10,serve_slow@60,"
               "canary_diverge@120")
    out = {"replica_crash": None, "serve_slow": None,
           "serve_shed": None, "canary_diverge": None}
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        kind, _, n = tok.partition("@")
        if kind in out and n.lstrip("-").isdigit():
            out[kind] = int(n)
        else:
            print(f"[bench] WARNING: ignoring malformed "
                  f"BENCH_FLEET_FAULT spec {tok!r}", file=sys.stderr)
    return out


def serve_fleet_main(smoke: bool = False) -> int:
    """Fleet serving bench (ISSUE 13): `python bench.py --serve-fleet`.

    Spawns K replica daemons + the retry/shed/canary router, drives
    closed-loop client threads THROUGH the router, and chaos-drills the
    fault domain mid-load (BENCH_FLEET_FAULT): one replica crashes and
    is relaunched, one replica sheds, a rolling publish swaps every
    replica to v2, and a deliberately-divergent canary must AUTO-ROLL
    BACK.  Gates (rc != 0 on violation): ZERO failed client requests
    through all of it, every response byte-identical to
    `Booster.predict` of the version that served it, the
    `serve_rollback`/`serve_shed` counters present on the router's
    /metrics page, and every replica draining to rc 143 on SIGTERM.
    ISSUE 14 adds the observability-plane gates: the router's merged
    `lgbm_fleet_*` scrape must equal the sum of the per-replica scrapes
    with BOTH replicas contributing, at least one sampled request must
    assemble into a full cross-process trace (router route/attempt +
    replica serve/queue/dispatch/respond spans, >= 2 pids, monotone
    stamps), and the serve_slow dispatcher stall must fire >= 1
    `slo_burn` (75 ms p99 SLO, shrunk burn windows)."""
    import jax

    import tempfile
    import threading
    import urllib.request

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.observability.registry import global_registry
    from lightgbm_tpu.serving import OverloadedError, ReplicaFleet, Router
    from lightgbm_tpu.serving.daemon import serve_counters_reset

    faults = _parse_fleet_faults(smoke)
    replicas = int(os.environ.get("BENCH_FLEET_REPLICAS",
                                  2 if smoke else 3))
    n_threads = int(os.environ.get("BENCH_FLEET_THREADS",
                                   6 if smoke else 12))
    req_rows = int(os.environ.get("BENCH_FLEET_REQ_ROWS", 4))
    target_requests = int(os.environ.get(
        "BENCH_FLEET_REQUESTS", 400 if smoke else 4000))

    # model trio: v2 continues the workload (the GOOD publish); the
    # canary candidate is trained with a pathological class weight so
    # its score distribution visibly diverges — the auto-rollback bait
    Xtr, ytr = make_higgs_like(20_000, FEATURES, seed=7)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 20, "device_predict": "true",
              "device_predict_min_bucket": 64}
    b1 = lgb.train(params, lgb.Dataset(Xtr, label=ytr), num_boost_round=20)
    b2 = lgb.train(params, lgb.Dataset(Xtr, label=ytr), num_boost_round=40)
    b_bad = lgb.train({**params, "scale_pos_weight": 100.0},
                      lgb.Dataset(Xtr, label=ytr), num_boost_round=10)

    workdir = tempfile.mkdtemp(prefix="lgbm-fleet-bench-")
    paths = {}
    for tag, bst in (("v1", b1), ("v2", b2), ("bad", b_bad)):
        paths[tag] = os.path.join(workdir, f"model_{tag}.txt")
        bst.save_model(paths[tag])

    pool, _ = make_higgs_like(2048, FEATURES, seed=8)
    pool = np.ascontiguousarray(pool, np.float32)
    # byte-identity oracle: every routed response must equal ONE
    # version's Booster.predict rows exactly (versions are per-replica
    # registry counters, so the SCORES identify the model, and a row
    # mix of two versions inside one response can never match any)
    expected = {tag: b.predict(pool)
                for tag, b in (("v1", b1), ("v2", b2), ("bad", b_bad))}

    serve_counters_reset()
    for key in ("slo_burn_total", "router_requests", "router_rows"):
        global_registry.inc(key, -global_registry.counter(key))
    victim = 1 % replicas
    fault_envs = {}
    specs = []
    if faults["replica_crash"] is not None:
        specs.append((victim, f"serve_crash@{faults['replica_crash']}"))
    if faults["serve_shed"] is not None:
        specs.append((0, f"serve_shed@{faults['serve_shed']}"))
    if faults["serve_slow"] is not None:
        specs.append((0, f"serve_slow@{faults['serve_slow']}"))
    for idx, spec in specs:
        env = fault_envs.setdefault(idx, {})
        env["LGBM_TPU_FAULT"] = ",".join(
            filter(None, [env.get("LGBM_TPU_FAULT"), spec]))

    serve_params = {"device_predict": "true",
                    "device_predict_min_bucket": 64,
                    "serve_max_batch_rows": 256,
                    "serve_max_coalesce_wait_ms": 2.0,
                    "serve_queue_depth": 256,
                    "verbosity": -1}
    cfg = Config({**serve_params,
                  "serve_replicas": replicas,
                  "serve_retry_max": 4,
                  "serve_retry_backoff_ms": 25.0,
                  "serve_request_timeout_s": 60.0,
                  "serve_canary_pct": 50.0,
                  "serve_canary_min_samples": 24,
                  "serve_canary_max_divergence": 2.0,
                  "serve_canary_max_error_rate": 0.2,
                  # cross-process tracing (ISSUE 14): sample every 16th
                  # routed request so the smoke run assembles a few
                  # dozen full client->router->replica->device traces
                  "serve_trace_sample": 16,
                  # SLO burn gate: normal container latency (p99 tens
                  # of ms) stays inside budget; the serve_slow fault's
                  # armed 2 s dispatcher stall pushes every queued
                  # request over 75 ms at once and must burn BOTH
                  # windows (shrunk so a smoke run spans several)
                  "serve_slo_p99_ms": 75.0,
                  "serve_slo_error_pct": 1.0,
                  "serve_slo_fast_window_s": 2.0,
                  "serve_slo_slow_window_s": 20.0})
    fleet = ReplicaFleet(
        num_replicas=replicas, model_entries=[("higgs", paths["v1"])],
        workdir=workdir, params=serve_params,
        max_restarts=3, health_interval_s=0.25,
        # CPU drill: this parent has trained the models above and so
        # holds the chip on a TPU host, and K replica processes cannot
        # share it anyway (serving/fleet.py) — the replicas answer from
        # the CPU, and this mode drills the fault domain, not the device
        force_cpu=True,
        fault_envs=fault_envs).start()
    router = Router(fleet, cfg)
    router.register_incumbent("higgs", paths["v1"])
    failures: list = []
    latencies: list = []
    lat_lock = threading.Lock()
    ok_count = [0]
    overload_rejections = [0]
    rows_served = [0]
    versions_matched: set = set()
    stop_flag = threading.Event()
    try:
        if not fleet.wait_ready(timeout=420.0):
            print(json.dumps({"metric": "serve_fleet", "value": None,
                              "error": "fleet never became ready",
                              "replicas": fleet.describe()}))
            return 1

        def match_version(out_rows, start):
            for tag, exp in expected.items():
                if np.array_equal(out_rows, exp[start:start + req_rows]):
                    return tag
            return None

        def client(tid: int) -> None:
            rnd = 0
            while not stop_flag.is_set():
                rnd += 1
                start = ((tid * 2654435761 + rnd * 97)
                         % (len(pool) - req_rows))
                try:
                    r = router.predict("higgs",
                                       pool[start:start + req_rows],
                                       deadline_ms=45_000.0)
                except OverloadedError:
                    # an explicit admission rejection is the correct
                    # answer from a saturated fleet, not a lost request
                    # — the client backs off; the gate bounds the RATE
                    with lat_lock:
                        overload_rejections[0] += 1
                    time.sleep(0.1)
                    continue
                except Exception as e:  # noqa: BLE001
                    with lat_lock:
                        failures.append(f"t{tid}r{rnd}: {e!r}")
                    time.sleep(0.05)  # no failure-storm spinning
                    continue
                tag = match_version(np.asarray(r.preds), start)
                with lat_lock:
                    latencies.append(r.latency_ms)
                    rows_served[0] += req_rows
                    ok_count[0] += 1
                    if tag is None:
                        failures.append(
                            f"t{tid}r{rnd}: response matches NO "
                            f"version byte-for-byte (v{r.version} "
                            f"replica {r.replica})")
                    else:
                        versions_matched.add(tag)

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_threads)]
        t0 = time.time()
        for t in threads:
            t.start()

        def done_fraction() -> float:
            with lat_lock:
                return ok_count[0] / max(target_requests, 1)

        def wait_until(frac: float, timeout: float = 420.0) -> None:
            deadline = time.time() + timeout
            while done_fraction() < frac and time.time() < deadline:
                time.sleep(0.05)

        # phase A: plain load; the crash + shed faults fire in here
        wait_until(0.35)
        # phase B: rolling publish v2 (no canary) under live load —
        # after the crashed replica rejoined, so the roll covers the
        # whole fleet (a replica skipped mid-restart would relaunch
        # onto the new version anyway via fleet.set_model_path)
        fleet.wait_ready(timeout=180.0)
        publish_info = router.publish("higgs", paths["v2"], canary_pct=0)
        # phase C: wait for the canary threshold, then drop the bait
        canary_at = faults["canary_diverge"]
        rollback_ok = None
        if canary_at is not None:
            while done_fraction() * target_requests < canary_at and \
                    time.time() - t0 < 420.0:
                time.sleep(0.05)
            fleet.wait_ready(timeout=120.0, min_replicas=2)
            router.publish("higgs", paths["bad"])  # serve_canary_pct=50
            verdict = router.canary_wait("higgs", timeout=240.0)
            rollback_ok = verdict == "rolled_back"
        wait_until(1.0)
        stop_flag.set()
        for t in threads:
            t.join(timeout=120.0)
        wall = time.time() - t0

        # --- fleet-aggregation gate (ISSUE 14): one forced synchronous
        # scrape of every replica, then the router's MERGED counter must
        # equal the sum of the per-replica scrapes exactly (traffic has
        # stopped, so the counters are static) and BOTH replicas must
        # have contributed a non-zero share
        fleet.wait_ready(timeout=60.0)
        fleet.scrape_all()
        agg_snapshot = fleet.aggregator.snapshot()
        per_replica_requests = {
            idx: s["counters"].get("lgbm_serve_requests", 0.0)
            for idx, s in sorted(agg_snapshot.items())}
        merged_requests = fleet.aggregator.merged_counters().get(
            "lgbm_serve_requests", 0.0)
        fleet_metrics_ok = (
            len(per_replica_requests) >= min(replicas, 2)
            and all(v > 0 for v in per_replica_requests.values())
            and abs(merged_requests
                    - sum(per_replica_requests.values())) < 1e-9)

        # --- assembled-trace gate (ISSUE 14): at least one sampled
        # request produced a full cross-process waterfall — router
        # routing (route/attempt), replica coalesce/dispatch
        # (serve/queue/dispatch) and device settle (dispatch span end +
        # respond span) — from >= 2 processes with monotone stamps
        trace_ok = False
        trace_seen = router.assembler.traces()
        for tr in trace_seen:
            if tr.get("outcome") != "ok":
                continue
            names = {s["name"] for s in tr["spans"]}
            if not {"route", "attempt", "serve", "queue", "dispatch",
                    "respond"} <= names:
                continue
            if len(tr.get("processes", ())) < 2:
                continue
            rels = [s["rel_ms"] for s in tr["spans"]]
            if any(b < a for a, b in zip(rels, rels[1:])) \
                    or any(r < 0 for r in rels):
                continue
            trace_ok = True
            break

        # --- SLO burn gate (ISSUE 14): the serve_slow fault's 2 s
        # dispatcher stall breached the 75 ms latency SLO for every
        # queued request at once; the router's multi-window burn-rate
        # tracker must have fired at least one slo_burn
        slo_burns = int(global_registry.counter("slo_burn_total"))
        slo_wanted = faults["serve_slow"] is not None
        slo_ok = (slo_burns >= 1) if slo_wanted else None

        # /metrics gate: the router's scrape page must carry the fleet
        # counters the acceptance names (serve_rollback, serve_shed)
        # plus the merged fleet families and per-replica gauges
        router.start_frontend(port=0, metrics_port=0)
        metrics_scrape_ok = False
        scrape_error = None
        try:
            page = urllib.request.urlopen(
                f"http://127.0.0.1:{router.metrics_server.port}/metrics",
                timeout=30).read().decode()
            required = ["lgbm_router_requests", "lgbm_router_rows",
                        "lgbm_serve_shed", "lgbm_router_p99_ms",
                        "lgbm_fleet_replicas_routable",
                        "lgbm_fleet_serve_requests",
                        'lgbm_fleet_replica_up{replica="0"}',
                        'lgbm_fleet_replica_up{replica="1"}',
                        "lgbm_fleet_latency_ms"]
            if rollback_ok is not None:
                required.append("lgbm_serve_rollback")
            if slo_wanted:
                required.append("lgbm_fleet_slo_burning")
            missing = [r for r in required if r not in page]
            malformed = [ln for ln in page.splitlines()
                         if ln and not ln.startswith("#")
                         and len(ln.rsplit(" ", 1)) != 2]
            page_fleet_requests = None
            for ln in page.splitlines():
                if ln.startswith("lgbm_fleet_serve_requests "):
                    page_fleet_requests = float(ln.rsplit(" ", 1)[1])
            if missing:
                scrape_error = f"missing series: {missing}"
            elif malformed:
                scrape_error = f"malformed lines: {malformed[:3]}"
            elif page_fleet_requests is not None and abs(
                    page_fleet_requests
                    - sum(per_replica_requests.values())) > 1e-9:
                scrape_error = (
                    f"merged lgbm_fleet_serve_requests "
                    f"{page_fleet_requests} != per-replica sum "
                    f"{sum(per_replica_requests.values())}")
            else:
                metrics_scrape_ok = True
        except Exception as e:  # noqa: BLE001 - reported in the JSON line
            scrape_error = str(e)

        # one TCP round trip through the router wire (clients above ran
        # in-process; the wire is what a real fleet client sees)
        wire_ok = False
        try:
            import socket
            port = router.frontend.server_address[1]
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=30) as s:
                f = s.makefile("rwb")
                f.write((json.dumps({"model": "higgs",
                                     "rows": pool[:req_rows].tolist()})
                         + "\n").encode())
                f.flush()
                resp = json.loads(f.readline())
            wire_ok = bool(resp.get("ok")) and match_version(
                np.asarray(resp["preds"]), 0) is not None
        except Exception as e:  # noqa: BLE001
            failures.append(f"wire: {e!r}")

        stats = router.stats()
        crashes = int(global_registry.counter("serve_replica_down"))
        restarts = int(global_registry.counter("serve_replica_restarts"))
    finally:
        stop_flag.set()
        rcs = fleet.stop(drain=True, timeout=60.0)
        router.stop()
    drain_ok = all(rc in (143, -15) for rc in rcs.values())

    lat = np.asarray(latencies, np.float64)
    crash_wanted = faults["replica_crash"] is not None
    out = {
        "metric": "serve_fleet",
        "value": round(float(np.percentile(lat, 99)), 3)
        if len(lat) else None,
        "unit": "p99_ms",
        "fleet_p50_ms": round(float(np.percentile(lat, 50)), 3)
        if len(lat) else None,
        "fleet_p99_ms": round(float(np.percentile(lat, 99)), 3)
        if len(lat) else None,
        "fleet_rows_per_s": round(rows_served[0] / max(wall, 1e-9), 1),
        "fleet_requests_per_s": round(len(lat) / max(wall, 1e-9), 1),
        "replicas": replicas,
        "requests_ok": int(ok_count[0]),
        "requests_failed": len(failures),
        "overload_rejections": int(overload_rejections[0]),
        "replica_crashes": crashes,
        "replica_restarts": restarts,
        "router_retries": int(stats["router_retries"]),
        "serve_shed": int(stats["serve_shed"]),
        "serve_overloaded": int(stats["serve_overloaded"]),
        "publishes": int(stats["serve_publish"]),
        "rollback_ok": rollback_ok,
        "serve_rollback": int(stats["serve_rollback"]),
        "versions_matched": sorted(versions_matched),
        "publish_rolled_replicas": sorted(
            publish_info.get("replicas", {})) if publish_info else None,
        "metrics_scrape_ok": bool(metrics_scrape_ok),
        "metrics_scrape_error": scrape_error,
        "fleet_metrics_ok": bool(fleet_metrics_ok),
        "fleet_requests_per_replica": {
            str(k): int(v) for k, v in per_replica_requests.items()},
        "fleet_requests_merged": int(merged_requests),
        "traces_assembled": len(trace_seen),
        "trace_ok": bool(trace_ok),
        "slo_burns": slo_burns,
        "slo_ok": slo_ok,
        "wire_ok": bool(wire_ok),
        "drain_returncodes": {str(k): v for k, v in sorted(rcs.items())},
        "drain_ok": bool(drain_ok),
        "errors": failures[:5],
        "fault_spec": {k: v for k, v in faults.items() if v is not None},
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
    }
    print(json.dumps(out))
    ok = (not failures
          and ok_count[0] >= target_requests
          and overload_rejections[0] <= 0.05 * max(ok_count[0], 1)
          and (not crash_wanted or (crashes >= 1 and restarts >= 1))
          and int(stats["serve_publish"]) >= 1
          and {"v1", "v2"} <= versions_matched
          and (rollback_ok is None or rollback_ok)
          and fleet_metrics_ok and trace_ok
          and (slo_ok is None or slo_ok)
          and metrics_scrape_ok and wire_ok and drain_ok)
    return 0 if ok else 1


def online_main(smoke: bool = False) -> int:
    """Online continual-learning bench (docs/Online.md):
    `python bench.py --online [--smoke]`.

    Phase 1 (in-process, sustained load): an OnlineTrainer thread
    consumes MemoryChunkSource generations — boosting new trees per
    chunk, checkpointing each generation, hot-publishing into a local
    ServingDaemon — while closed-loop client threads keep querying.
    The chaos spec (`LGBM_TPU_FAULT=online_publish_fail@…,
    online_chunk_corrupt@…`) drills the failure semantics mid-run: a
    failed publish must retry and land (old generation serving
    throughout), a corrupt chunk must be SKIPPED with the previous
    generation serving.  Gates: ZERO lost client requests across all
    publishes, every response byte-identical to `Booster.predict` of
    the exact generation that served it, >= 3 generations published,
    reported freshness lag finite and under `online_max_lag_s`.

    Phase 2 (subprocess SIGTERM drill): a control `task=train-and-serve`
    run consumes 3 on-disk chunks to completion; a drill run is
    SIGTERM-killed mid-loop after generation 2, then relaunched — the
    relaunch must resume from the generation-2 checkpoint, serve it
    immediately (no served-version regression), re-train generation 3
    BYTE-IDENTICALLY to the control run, and exit cleanly."""
    import jax

    import shutil
    import signal
    import tempfile
    import threading
    import urllib.request

    import lightgbm_tpu as lgb
    from lightgbm_tpu.basic import Booster
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.observability.registry import global_registry
    from lightgbm_tpu.online import (LocalPublisher, MemoryChunkSource,
                                     OnlineTrainer, write_chunk)
    from lightgbm_tpu.reliability import faults
    from lightgbm_tpu.serving import ServingClient, ServingDaemon
    from lightgbm_tpu.serving.daemon import serve_counters_reset

    n_rows = int(os.environ.get("BENCH_ONLINE_CHUNK_ROWS",
                                1500 if smoke else 20000))
    n_chunks = int(os.environ.get("BENCH_ONLINE_CHUNKS",
                                  5 if smoke else 10))
    n_threads = int(os.environ.get("BENCH_ONLINE_THREADS",
                                   4 if smoke else 8))
    req_rows = 4
    max_lag_s = float(os.environ.get("BENCH_ONLINE_MAX_LAG_S", 60.0))
    trees_per_chunk = 3

    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10, "device_predict": "true",
              "device_predict_min_bucket": 64,
              "serve_max_batch_rows": 256, "serve_queue_depth": 256,
              "serve_max_coalesce_wait_ms": 2.0,
              "metrics_port": 0,
              "online_trees_per_chunk": trees_per_chunk,
              "online_mode": "boost", "online_max_lag_s": max_lag_s,
              "online_publish_backoff_ms": 25.0}

    def mk_chunk(seed):
        X, y = make_higgs_like(n_rows, FEATURES, seed=seed)
        return X, y

    workdir = tempfile.mkdtemp(prefix="lgbm-online-bench-")
    failures: list = []
    samples: list = []       # (version, start, preds) under lat_lock
    lat_lock = threading.Lock()
    versions_models: dict = {}

    # chaos spec: publish of generation 2 fails once (must retry and
    # land); chunk generation 4 arrives corrupt (must be skipped with
    # generation 3 still serving)
    chaos = os.environ.get("BENCH_ONLINE_FAULT",
                           "online_publish_fail@2,online_chunk_corrupt@4")
    prev_fault = os.environ.get("LGBM_TPU_FAULT")
    corrupt_gens = {int(tok.split("@")[1]) for tok in chaos.split(",")
                    if tok.startswith("online_chunk_corrupt@")}
    try:
        serve_counters_reset()
        for key in ("online_generations_published",
                    "online_generations_skipped",
                    "online_publish_retries"):
            global_registry.inc(key, -global_registry.counter(key))
        if chaos:
            os.environ["LGBM_TPU_FAULT"] = chaos
        else:
            os.environ.pop("LGBM_TPU_FAULT", None)
        faults.reload()

        X0, y0 = mk_chunk(0)
        seed_booster = lgb.train(
            {k: v for k, v in params.items()
             if not k.startswith(("serve_", "online_", "metrics_"))},
            lgb.Dataset(X0, label=y0), num_boost_round=10)
        seed_path = os.path.join(workdir, "seed.txt")
        seed_booster.save_model(seed_path)

        daemon = ServingDaemon(Config(params)).start()
        source = MemoryChunkSource()
        ckpt_dir = os.path.join(workdir, "ckpt")

        def on_publish(gen, version, model_str):
            with lat_lock:
                versions_models[version] = model_str

        trainer = OnlineTrainer(source, LocalPublisher(daemon),
                                params=params, checkpoint_dir=ckpt_dir,
                                seed_model=seed_path,
                                on_publish=on_publish)
        trainer.start()

        pool, _ = make_higgs_like(2048, FEATURES, seed=99)
        pool = np.ascontiguousarray(pool, np.float32)
        stop_flag = threading.Event()

        def client(tid):
            rnd = 0
            while not stop_flag.is_set():
                rnd += 1
                start = ((tid * 2654435761 + rnd * 97)
                         % (len(pool) - req_rows))
                try:
                    fut = daemon.submit(trainer.model_name,
                                        pool[start:start + req_rows])
                    out = fut.result(timeout=120)
                except Exception as e:  # noqa: BLE001
                    with lat_lock:
                        failures.append(f"t{tid}r{rnd}: {e!r}")
                    time.sleep(0.05)
                    continue
                with lat_lock:
                    samples.append((fut.version, start,
                                    np.asarray(out)))

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_threads)]
        t0 = time.time()
        for t in threads:
            t.start()
        loop = threading.Thread(
            target=lambda: trainer.run(max_generations=n_chunks,
                                       idle_exit_s=60.0), daemon=True)
        loop.start()
        for g in range(1, n_chunks + 1):
            source.push(*mk_chunk(g))
            time.sleep(0.3 if smoke else 1.0)
        loop.join(timeout=600)
        stop_flag.set()
        for t in threads:
            t.join(timeout=60)
        wall = time.time() - t0
        stats = trainer.stats()
        if loop.is_alive():
            failures.append("trainer loop did not finish")

        # byte-identity: every sampled response must equal
        # Booster.predict of the exact version that served it (device
        # path forced: the daemon serves through the same float32
        # traversal, so the comparison is bit-for-bit)
        def _oracle(model_str):
            b = Booster(model_str=model_str)
            b._gbdt.config.device_predict = "true"
            return b

        with lat_lock:
            model_of = {v: _oracle(s)
                        for v, s in versions_models.items()}
        expected = {v: b.predict(pool) for v, b in model_of.items()}
        mismatches = 0
        for version, start, preds in samples:
            exp = expected.get(version)
            if exp is None or not np.array_equal(
                    preds, exp[start:start + req_rows]):
                mismatches += 1
        if mismatches:
            failures.append(f"{mismatches} responses not byte-identical "
                            "to their serving generation")

        published = int(global_registry.counter(
            "online_generations_published"))
        skipped = int(global_registry.counter(
            "online_generations_skipped"))
        retries = int(global_registry.counter("online_publish_retries"))
        lag = stats.get("freshness_lag_s")
        lag_ok = lag is not None and np.isfinite(lag) and lag <= max_lag_s

        # the freshness plane must be scrapable (docs/Online.md)
        metrics_scrape_ok = False
        scrape_error = None
        try:
            page = urllib.request.urlopen(
                f"http://127.0.0.1:{daemon.metrics_server.port}/metrics",
                timeout=30).read().decode()
            required = ["lgbm_model_freshness_lag_s",
                        "lgbm_online_generations_published",
                        "lgbm_online_generation"]
            if skipped:
                required.append("lgbm_online_generations_skipped")
            missing = [r for r in required if r not in page]
            if missing:
                scrape_error = f"missing series: {missing}"
            else:
                metrics_scrape_ok = True
        except Exception as e:  # noqa: BLE001 - reported in the JSON line
            scrape_error = str(e)
        daemon.stop(drain=True, timeout=30)
    finally:
        if prev_fault is None:
            os.environ.pop("LGBM_TPU_FAULT", None)
        else:
            os.environ["LGBM_TPU_FAULT"] = prev_fault
        faults.reload()

    # ---- phase 2: the SIGTERM kill/resume drill (subprocesses) ----
    drill = {"control_rc": None, "kill_rc": None, "resume_rc": None,
             "byte_exact": None, "served_no_regress": None,
             "error": None}
    try:
        chunks_a = os.path.join(workdir, "chunks-a")
        chunks_b = os.path.join(workdir, "chunks-b")
        os.makedirs(chunks_a)
        os.makedirs(chunks_b)
        drill_chunks = {}
        for g in (1, 2, 3):
            Xg, yg = mk_chunk(100 + g)
            drill_chunks[g] = write_chunk(chunks_a, g, Xg, yg)
        base_cmd = [sys.executable, "-m", "lightgbm_tpu",
                    "task=train-and-serve",
                    "objective=binary", "num_leaves=15", "verbosity=-1",
                    "min_data_in_leaf=10", "device_predict=true",
                    "device_predict_min_bucket=64", "serve_warmup=false",
                    "online_mode=boost", "online_trees_per_chunk=2",
                    "online_poll_interval_s=0.05",
                    f"input_model={seed_path}"]
        env = {k: v for k, v in os.environ.items()
               if k != "LGBM_TPU_FAULT"}
        # CPU drill: phase 1 ran in this process, which therefore holds
        # the chip on a TPU host; the kill/resume children check
        # checkpoint and publish semantics, which need no device
        env["JAX_PLATFORMS"] = "cpu"

        ck_a = os.path.join(workdir, "ckpt-a")
        res = subprocess.run(
            base_cmd + [f"online_chunk_dir={chunks_a}",
                        f"checkpoint_dir={ck_a}", "serve_port=-1",
                        "online_idle_exit_s=1.5"],
            capture_output=True, text=True, timeout=600, env=env)
        drill["control_rc"] = res.returncode
        control_final = open(os.path.join(ck_a, "ckpt_0000003.txt"),
                             "rb").read()
        control_g2 = open(os.path.join(ck_a, "ckpt_0000002.txt"),
                          "rb").read()

        # drill run: only generations 1-2 available, killed mid-loop
        for g in (1, 2):
            shutil.copy(drill_chunks[g], chunks_b)
        ck_b = os.path.join(workdir, "ckpt-b")
        ready1 = os.path.join(workdir, "ready-b1.json")
        child = subprocess.Popen(
            base_cmd + [f"online_chunk_dir={chunks_b}",
                        f"checkpoint_dir={ck_b}", "serve_port=-1",
                        "online_idle_exit_s=0",
                        f"serve_ready_file={ready1}"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        deadline = time.time() + 300
        while time.time() < deadline:
            if os.path.exists(os.path.join(ck_b, "ckpt_0000002.txt")):
                break
            if child.poll() is not None:
                break
            time.sleep(0.1)
        time.sleep(0.3)  # let the generation-2 publish settle
        child.send_signal(signal.SIGTERM)
        try:
            out_b1, _ = child.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            child.kill()
            out_b1, _ = child.communicate()
        drill["kill_rc"] = child.returncode

        # relaunch with generation 3 landed: must resume from the
        # generation-2 checkpoint, serve it immediately, and re-train
        # generation 3 byte-identically to the control run
        shutil.copy(drill_chunks[3], chunks_b)
        ready2 = os.path.join(workdir, "ready-b2.json")
        child2 = subprocess.Popen(
            base_cmd + [f"online_chunk_dir={chunks_b}",
                        f"checkpoint_dir={ck_b}", "serve_port=0",
                        "online_idle_exit_s=1.5",
                        f"serve_ready_file={ready2}"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        deadline = time.time() + 300
        port = None
        while time.time() < deadline and port is None:
            if os.path.exists(ready2):
                port = json.load(open(ready2)).get("port")
                break
            if child2.poll() is not None:
                break
            time.sleep(0.1)
        served_ok = None
        if port and port > 0:
            # the ready file lands right after the RESUME publish: the
            # served model must already be generation >= 2 — never the
            # seed (that would regress the fleet below its checkpoint)
            exp_g2 = _oracle(control_g2.decode()).predict(
                pool[:req_rows])
            exp_g3 = _oracle(control_final.decode()).predict(
                pool[:req_rows])
            try:
                cl = ServingClient.connect("127.0.0.1", int(port),
                                           request_timeout_s=60.0)
                got = np.asarray(cl.predict("online", pool[:req_rows]))
                cl.close()
                served_ok = (np.array_equal(got, exp_g2)
                             or np.array_equal(got, exp_g3))
            except Exception as e:  # noqa: BLE001
                served_ok = False
                drill["error"] = f"resume probe: {e!r}"
        drill["served_no_regress"] = served_ok
        try:
            out_b2, _ = child2.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            child2.kill()
            out_b2, _ = child2.communicate()
        drill["resume_rc"] = child2.returncode
        resumed_final_path = os.path.join(ck_b, "ckpt_0000003.txt")
        if os.path.exists(resumed_final_path):
            drill["byte_exact"] = (open(resumed_final_path, "rb").read()
                                   == control_final)
        else:
            drill["byte_exact"] = False
            drill["error"] = (drill["error"] or "") + \
                f" no resumed gen-3 checkpoint; b2 tail: {out_b2[-500:]}"
    except Exception as e:  # noqa: BLE001 - drill outcome rides the JSON line
        drill["error"] = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    drill_ok = (drill["control_rc"] == 0
                and drill["kill_rc"] in (143, -15)
                and drill["resume_rc"] == 0
                and drill["byte_exact"] is True
                and drill["served_no_regress"] is True)
    chaos_ok = (not chaos) or (retries >= 1 and skipped >= 1
                               and skipped == len(corrupt_gens))
    out = {
        "metric": "online_continual",
        "value": (round(lag, 3) if lag is not None else None),
        "unit": "freshness_lag_s",
        "generations_published": published,
        "generations_skipped": skipped,
        "publish_retries": retries,
        "freshness_lag_s": (round(lag, 4) if lag is not None else None),
        "freshness_lag_ok": bool(lag_ok),
        "online_max_lag_s": max_lag_s,
        "requests_ok": len(samples),
        "requests_failed": len(failures),
        "requests_per_s": round(len(samples) / max(wall, 1e-9), 1),
        "chunk_rows": n_rows,
        "chunks": n_chunks,
        "versions_served": sorted({v for v, _, _ in samples}),
        "chaos_spec": chaos or None,
        "chaos_ok": bool(chaos_ok),
        "metrics_scrape_ok": bool(metrics_scrape_ok),
        "metrics_scrape_error": scrape_error,
        "sigterm_drill": drill,
        "sigterm_drill_ok": bool(drill_ok),
        "errors": failures[:5],
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
    }
    print(json.dumps(out))
    ok = (not failures and published >= 3 and lag_ok and chaos_ok
          and metrics_scrape_ok and drill_ok
          and len(samples) > 0)
    return 0 if ok else 1


_MULTICHIP_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import numpy as np
import lightgbm_tpu as lgb

work = os.environ["BENCH_MULTICHIP_DIR"]
rng = np.random.RandomState(11)
X = rng.rand(1024, 5)
y = (3 * (X[:, 0] - 0.5) + X[:, 1] * X[:, 2]).astype(np.float64)
params = {
    "objective": "regression", "num_leaves": 7, "verbosity": -1,
    "min_data_in_leaf": 5, "learning_rate": 0.2,
    "tree_learner": "data", "tpu_growth_strategy": "wave",
    "metrics_dir": os.path.join(work, "metrics"),
    "checkpoint_dir": os.path.join(work, "ckpt"), "checkpoint_freq": 1,
    "auto_degrade": True,
    "stall_floor_s": float(os.environ.get("BENCH_STALL_FLOOR_S", "30")),
    "stall_factor": 10.0,
}
b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
assert np.isfinite(b.predict(X[:64])).all()
print("MULTICHIP_TRAIN_OK", b.current_iteration(), flush=True)
"""


def multichip_main(n_devices: int) -> int:
    """Guarded multi-chip smoke runner (ISSUE 7): train a short
    sharded-wave run over an `n_devices` mesh UNDER the stall watchdog,
    walking the degradation ladder across relaunches when an attempt
    hangs.  Prints one MULTICHIP-style JSON line that is
    self-explaining on failure: `stall_diagnosis` carries the wedged
    attempt's stack + knob fingerprint and `degraded_knobs` the ladder
    steps a recovered run needed — what a bare rc=124 with one stderr
    line (a rank wedged in a collective until the wall-clock cap) does
    not say.

    Fault injection for self-tests / driver drills:
    `BENCH_MULTICHIP_FAULT=hang@3` wedges attempt 0 at iteration 3.
    """
    import shutil
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from lightgbm_tpu.reliability.guard import (DEGRADE_LADDER,
                                                degraded_knobs,
                                                stall_file_path)
    from lightgbm_tpu.reliability.supervisor import classify_returncode

    timeout = float(os.environ.get("BENCH_MULTICHIP_TIMEOUT", "600"))
    env = dict(os.environ)
    env["BENCH_REPO"] = os.path.dirname(os.path.abspath(__file__))
    # where the children run is decided from the environment, not by
    # probing JAX (as __graft_entry__.dryrun_multichip): this parent never
    # touches JAX, one child at a time owns the devices.  JAX_PLATFORMS=cpu
    # makes it a CPU drill on n virtual devices; otherwise the child uses
    # the backend JAX finds, and its mesh raises when that backend has
    # fewer than n devices.
    if env.get("JAX_PLATFORMS", "").strip() == "cpu":
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_devices}").strip()
    if os.environ.get("BENCH_MULTICHIP_FAULT"):
        env["LGBM_TPU_FAULT"] = os.environ["BENCH_MULTICHIP_FAULT"]

    work = tempfile.mkdtemp(prefix="lgbtpu_multichip")
    metrics = os.path.join(work, "metrics")
    out = {"metric": "multichip_guarded", "n_devices": int(n_devices),
           "rc": None, "ok": False, "classification": None,
           "attempts": 0, "stall_diagnosis": None, "degraded_knobs": [],
           # recovery telemetry (ISSUE 8): which recovery machinery
           # fired and how long the run was down — so an r06+ line
           # names the mechanism, not just the outcome
           "time_to_recover_s": None, "elastic_shrinks": 0,
           "ckpt_fallbacks": 0, "preempt_ckpt_saved": 0,
           "tail": ""}
    first_failure_t = None
    try:
        env["BENCH_MULTICHIP_DIR"] = work
        script = os.path.join(work, "child.py")
        with open(script, "w") as f:
            f.write(_MULTICHIP_CHILD)
        # one first try + one relaunch per ladder rung: a run that still
        # hangs with every risky knob off is a real bug, not a knob
        for attempt in range(1 + len(DEGRADE_LADDER)):
            out["attempts"] = attempt + 1
            env["LGBM_TPU_FAULT_ATTEMPT"] = str(attempt)
            try:
                res = subprocess.run(
                    [sys.executable, script], capture_output=True,
                    text=True, timeout=timeout, env=env)
                rc = res.returncode
                out["tail"] = ((res.stdout or "") + (res.stderr or ""))[-2000:]
            except subprocess.TimeoutExpired as e:
                rc = 124
                out["tail"] = (str(e.stdout or "") + str(e.stderr or ""))[-2000:]
            out["rc"] = rc
            out["classification"] = classify_returncode(rc)
            if out["stall_diagnosis"] is None:
                spath = stall_file_path(metrics, 0)
                if os.path.exists(spath):
                    try:
                        out["stall_diagnosis"] = json.load(open(spath))
                    except (OSError, ValueError):
                        pass
            if rc == 0:
                out["ok"] = True
                if first_failure_t is not None:
                    out["time_to_recover_s"] = round(
                        time.monotonic() - first_failure_t, 3)
                break
            if first_failure_t is None:
                first_failure_t = time.monotonic()
            # hangs walk the degradation ladder on relaunch; preempts
            # and crashes relaunch unchanged, resuming from checkpoint
            # (injected faults are attempt-gated so they do not re-fire)
            if out["classification"] not in ("hang", "preempt", "crash"):
                break
        out["degraded_knobs"] = degraded_knobs(metrics)
        out.update(_recovery_counts(metrics))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


def _recovery_counts(metrics_dir):
    """Count recovery events across every rank's event log: which of
    the ISSUE-8 mechanisms (generation fallback, elastic shrink,
    preemption checkpoint) actually fired during the guarded run."""
    import glob
    counts = {"ckpt_fallbacks": 0, "elastic_shrinks": 0,
              "preempt_ckpt_saved": 0}
    for path in glob.glob(os.path.join(metrics_dir, "events-rank*.jsonl*")):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    ev = rec.get("event")
                    if ev == "ckpt_fallback":
                        counts["ckpt_fallbacks"] += 1
                    elif ev == "elastic_shrink":
                        counts["elastic_shrinks"] += 1
                    elif ev == "preempt" and rec.get("saved"):
                        counts["preempt_ckpt_saved"] += 1
        except OSError:
            continue
    return counts


def main():
    import jax
    device = _require_tpu()

    import lightgbm_tpu as lgb

    X, y = make_higgs_like(ROWS, FEATURES)
    Xte, yte = make_higgs_like(100_000, FEATURES, seed=1)
    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "learning_rate": 0.1,
        "max_bin": int(os.environ.get("BENCH_BINS", 255)),
        "min_data_in_leaf": 20,
        "verbosity": -1,
        # the timed loop never evaluates (headline comparability); the
        # metric exists for the instrumented eval-tick phase below
        "metric": "binary_logloss",
    }
    # setup split (ISSUE 5): construct = binning + device placement +
    # booster init; compile = first update through its device sync (the
    # part a persistent compilation cache removes on repeat runs —
    # enable with compile_cache_dir=<dir>)
    t0 = time.time()
    train_set = lgb.Dataset(X, label=y)
    booster = lgb.Booster(params=params, train_set=train_set)
    setup_construct_s = time.time() - t0

    # warmup: the first iteration compiles the whole-tree program and the
    # first post-compile execution pays one-time device autotuning; sync
    # before timing so the measured loop is steady-state
    t0 = time.time()
    booster.update()
    _ = np.asarray(booster._gbdt.scores[0][:8])
    setup_compile_s = time.time() - t0
    for _ in range(WARMUP - 1):
        booster.update()
    _ = np.asarray(booster._gbdt.scores[0][:8])
    t0 = time.time()
    for _ in range(ITERS):
        booster.update()
    # force all device work to finish
    _ = np.asarray(booster._gbdt.scores[0][:8])
    elapsed = (time.time() - t0) / ITERS

    # quality gate: held-out AUC after the timed iterations (speed must not
    # be bought with broken trees).  Measured BEFORE the instrumented
    # extra iterations below so the tree count matches iters_trained (and
    # the same-host oracle's iters_lo anchor).
    auc = _auc(yte, booster._gbdt.predict_raw(Xte))

    # phase breakdown (docs/Observability.md): a few EXTRA instrumented
    # iterations AFTER the timed loop — the timers' phase-boundary syncs
    # would de-pipeline the dispatch, so the headline number stays
    # uninstrumented.
    # The cost model rides the same window: compiled-HLO flop/byte
    # deltas against the ::device phase times give MEASURED per-phase
    # MFU and a roofline classification next to the analytic estimate
    from lightgbm_tpu.observability.costmodel import (backend_peaks,
                                                      global_cost_model)
    from lightgbm_tpu.utils.timer import global_timer
    timer_prev = global_timer.sync
    cost_prev = global_cost_model.enabled
    global_timer.sync = True
    global_cost_model.enabled = True
    global_timer.reset()
    cost_snap0 = global_cost_model.snapshot()
    timer_snap0 = global_timer.snapshot()
    for _ in range(3):
        booster.update()
        # eval tick, mirroring engine.train's scope: with device eval
        # this is ONE packed D2H (ops/metrics.py); its cost is the
        # host-block headline below
        with global_timer.scope("GBDT::eval"):
            booster.eval_train()
    _ = np.asarray(booster._gbdt.scores[0][:8])
    all_scopes = global_timer.items()
    timer_top = [[name, round(sec * 1000, 3), cnt]
                 for name, sec, cnt in all_scopes[:10]]
    phase_secs = {name: sec - timer_snap0.get(name, (0.0, 0))[0]
                  for name, (sec, _c) in global_timer.snapshot().items()}
    cost_snap1 = global_cost_model.snapshot()
    roofline_phases = global_cost_model.phase_roofline(
        cost_snap0, cost_snap1, phase_secs)
    # headline measured MFU: total compiled flops of the instrumented
    # window over its total attributed device seconds (the analytic
    # b10m_useful_mac_mfu's measured cross-check)
    _tot_flops = sum(v["flops"] for v in roofline_phases.values())
    _tot_dev_s = sum(v["device_s"] or 0.0
                     for v in roofline_phases.values())
    peak_flops, _peak_bw = backend_peaks()
    measured_mfu = (_tot_flops / _tot_dev_s / peak_flops
                    if _tot_dev_s > 0 else None)
    global_cost_model.enabled = cost_prev
    # host-block attribution (docs/Observability.md): the scopes that
    # synchronize the training thread on device results or host I/O —
    # the boundary the ISSUE-5 work shrinks (device eval metrics, async
    # checkpoint writer, pipelined tree materialization)
    _HOST_BLOCK_SCOPES = ("GBDT::eval", "GBDT::materialize_tree",
                          "Checkpoint::save")
    host_block_ms_per_iter = round(sum(
        sec * 1000 for name, sec, _cnt in all_scopes
        if name in _HOST_BLOCK_SCOPES) / 3.0, 3)
    global_timer.sync = timer_prev
    global_timer.reset()

    # peak device memory over the run (empty off-TPU: the CPU backend
    # exposes no memory_stats)
    from lightgbm_tpu.observability import sample_device_memory
    mem = sample_device_memory()

    # predict throughput: serving rows/s for device / native / python
    # paths over the just-trained model (the trajectory tracks serving
    # perf alongside s/iter)
    predict_rows_per_s = _predict_throughput(booster, X)

    # jaxpr-level IR audit over the entries this run actually compiled
    # (tools/tpulint/ir, ISSUE 12): the BENCH line records that the hot
    # path it just measured is f64-free and callback-free — the
    # guard rail the quantized-gradient/Pallas work lands behind.
    # Groups come from the cost model's window (what dispatched) plus
    # the inference ladder when the device predict path ran.
    t0 = time.time()
    from tools.tpulint.ir import run_ir_audit
    _groups = sorted(set(cost_snap1)
                     | ({"device_predict"}
                        if "device" in predict_rows_per_s else set()))
    _findings, _num = run_ir_audit(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "lightgbm_tpu"), groups=_groups)
    _active = [f for f in _findings if not f.suppressed]
    ir_audit_clean = not _active
    ir_audit = {"groups": _groups, "entries_traced": _num,
                "findings": len(_active),
                "s": round(time.time() - t0, 3)}

    # kernel-correctness gate (tools/kernel_checks.py): the Pallas kernel
    # unit tests skip off-TPU, so a chip run is the only place that
    # executes them; anything but "ok" fails the bench
    from tools.kernel_checks import run_checks
    kernel_checks = run_checks()
    if kernel_checks != "ok":
        sys.exit(f"bench.py: on-chip kernel checks failed: {kernel_checks}")

    # quality mode: the spike-wave config (wave_spike_reserve=16) trades
    # ~1.5x iteration cost for oracle-parity AUC (PERF_NOTES round-5
    # frontier); measured here so the driver line carries both points
    q_elapsed = q_auc = None
    if os.environ.get("BENCH_QUALITY_MODE", "1") != "0":
        qp = dict(params)
        qp["wave_spike_reserve"] = 16
        qb = lgb.Booster(params=qp, train_set=train_set)
        for _ in range(WARMUP):
            qb.update()
        _ = np.asarray(qb._gbdt.scores[0][:8])
        t0 = time.time()
        for _ in range(ITERS):
            qb.update()
        _ = np.asarray(qb._gbdt.scores[0][:8])
        q_elapsed = (time.time() - t0) / ITERS
        q_auc = _auc(yte, qb._gbdt.predict_raw(Xte))

    baseline = BASELINE_SEC_PER_ITER_10M * ROWS / HIGGS_ROWS
    out = {
        "metric": f"higgs_like_{ROWS//1000}k_binary_255leaves_sec_per_iter",
        "value": round(elapsed, 4),
        "unit": "s/iter",
        "vs_baseline": round(baseline / elapsed, 4),
        "auc": round(auc, 5),
        "iters_trained": WARMUP + ITERS,
        "kernel_checks": kernel_checks,
        "backend": jax.default_backend(),
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
        # setup split: construct (binning + placement + init) vs the
        # first-update compile a persistent compile_cache_dir removes
        "setup_construct_s": round(setup_construct_s, 3),
        "setup_compile_s": round(setup_compile_s, 3),
        # host-blocking ms per instrumented iteration (eval tick +
        # pipelined tree materialization + checkpoint capture)
        "host_block_ms_per_iter": host_block_ms_per_iter,
        # where the time goes: [scope, total_ms, calls] over 3
        # instrumented post-loop iterations (top scopes first)
        "timer_top_ms": timer_top,
        # compiled-HLO roofline over the same window
        # (docs/Observability.md): per-phase measured MFU, arithmetic
        # intensity and compute- vs HBM-bound classification
        "measured_mfu": (round(measured_mfu, 7)
                         if measured_mfu is not None else None),
        "roofline": {g: {"mfu": (round(v["mfu"], 7)
                                 if v.get("mfu") is not None else None),
                         "ai": (round(v["arithmetic_intensity"], 4)
                                if v.get("arithmetic_intensity")
                                is not None else None),
                         "bound": v.get("bound"),
                         "flops": v.get("flops"),
                         "bytes": v.get("bytes")}
                     for g, v in roofline_phases.items()},
        # serving throughput per predict path (rows/s; *_rows = measured
        # batch — python is subsampled, device shrinks off-TPU)
        "predict_rows_per_s": predict_rows_per_s,
        # jaxpr-level audit verdict for the entries this run compiled
        # (docs/StaticAnalysis.md v4): true = hot path proven f64-free,
        # callback-free, churn-free at the IR level
        "ir_audit_clean": ir_audit_clean,
        "ir_audit": ir_audit,
    }
    if mem.get("device_peak_bytes_in_use") is not None:
        out["peak_device_bytes"] = mem["device_peak_bytes_in_use"]
    if q_elapsed is not None:
        out["quality_mode_sec_per_iter"] = round(q_elapsed, 4)
        out["quality_mode_auc"] = round(q_auc, 5)
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--diff":
        if len(sys.argv) != 4:
            print("usage: python bench.py --diff A.json B.json",
                  file=sys.stderr)
            sys.exit(2)
        sys.exit(diff_main(sys.argv[2], sys.argv[3]))
    if len(sys.argv) >= 2 and sys.argv[1] == "--multichip":
        n = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        sys.exit(multichip_main(n))
    if len(sys.argv) >= 2 and sys.argv[1] == "--serve":
        sys.exit(serve_main(smoke="--smoke" in sys.argv[2:]))
    if len(sys.argv) >= 2 and sys.argv[1] == "--serve-fleet":
        sys.exit(serve_fleet_main(smoke="--smoke" in sys.argv[2:]))
    if len(sys.argv) >= 2 and sys.argv[1] == "--online":
        sys.exit(online_main(smoke="--smoke" in sys.argv[2:]))
    main()
