"""Compiled-HLO cost accounting: measured per-phase MFU and roofline
classification (docs/Observability.md).

The MFU number earlier rounds tracked (`useful_mac_mfu`) was a
single hand-derived analytic estimate in a measuring script (deleted,
PR 32) — a MAC guess divided by wall clock divided by a hardcoded
peak.  It says the chip is idle but not WHERE, so the Pallas-histogram work has nothing to
aim at.  This module asks the compiler instead: every hot jitted entry
point is already wrapped in a `RecompileDetector` (grow/grow-wave,
donated or not; the gradient program; DeviceEval's packed tick; every
bucket of the inference ladder), and XLA's lowered module carries its
own cost analysis — `fn.lower(...).cost_analysis()` returns the
program's flops and bytes_accessed without compiling anything
(jax.stages.Lowered; ~4 ms once per signature, then cached here).  The
detector reports each call into the `CostModel`, keyed by the SAME
(shape, dtype, static) signature the recompile watchdog fingerprints,
so the accounting can never disagree with the watchdog about which
executable ran.

Combined with the per-phase `::device` times (`Timer.block` credits the
settle wait to `<scope>::device`) and a per-device-kind peak table, the
per-iteration event and the serving stats gain measured MFU, arithmetic
intensity (flops/byte), and a roofline classification: an entry whose
intensity sits below the ridge point (peak_flops / peak_bytes_per_s) is
HBM-bound — more MXU utilization is physically impossible without
cutting bytes — while one above it is compute-bound and worth a kernel.
This is the measurement foundation the Pallas-histogram ROADMAP item
optimizes against.

Zero steady-state cost when disabled (one attribute check per wrapped
call); when enabled, a dict add behind a lock per call — the same
budget as the metrics registry.  `engine.train` enables it for metrics
runs and the serving daemon for its lifetime (param `roofline`).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional, Tuple

from ..utils import log

# (peak_flops_per_s, peak_hbm_bytes_per_s) by `device_kind`, as
# jax.devices()[0].device_kind reports it.  "TPU v5 lite" is one v5e
# chip: 197 TFLOP/s bf16, 819 GB/s HBM (Google Cloud documentation,
# "TPU v5e", system architecture table).  A TPU kind missing here is an
# error on the roofline path, never a borrowed row.  The "cpu" row is a
# nominal figure that exists only so the compute-/HBM-bound
# CLASSIFICATION runs in the CPU tests; no MFU is reported against it.
# LGBM_TPU_PEAK_FLOPS / LGBM_TPU_PEAK_BYTES_PER_S override either value
# (and stand in for a part the table does not list).
PEAK_TABLE: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (1e11, 2e10),
}


def current_device_kind() -> str:
    """The PEAK_TABLE key of the default backend's first device: its
    `device_kind` on a TPU, its platform name otherwise."""
    import jax
    d = jax.devices()[0]
    return d.device_kind if d.platform == "tpu" else d.platform


def backend_peaks(device_kind: Optional[str] = None) -> Tuple[float, float]:
    """(peak_flops_per_s, peak_bytes_per_s) for `device_kind` (default:
    the active device).  Raises for a device the table does not list
    unless both env overrides supply the peaks."""
    if device_kind is None:
        device_kind = current_device_kind()
    flops, bw = PEAK_TABLE.get(device_kind, (None, None))
    env_f = os.environ.get("LGBM_TPU_PEAK_FLOPS")
    env_b = os.environ.get("LGBM_TPU_PEAK_BYTES_PER_S")
    try:
        if env_f:
            flops = float(env_f)
        if env_b:
            bw = float(env_b)
    except ValueError:
        log.warning("Ignoring malformed LGBM_TPU_PEAK_FLOPS / "
                    "LGBM_TPU_PEAK_BYTES_PER_S override")
    if flops is None or bw is None:
        raise KeyError(
            f"no peak FLOP/s / bytes/s known for device kind "
            f"{device_kind!r}: add it to costmodel.PEAK_TABLE with its "
            "source, or set LGBM_TPU_PEAK_FLOPS and "
            "LGBM_TPU_PEAK_BYTES_PER_S")
    return flops, bw


def _extract_cost(analysis) -> Optional[Tuple[float, float]]:
    """(flops, bytes_accessed) out of the dict `cost_analysis()` returns;
    None when the module reports neither."""
    flops = float(analysis.get("flops", 0.0) or 0.0)
    bytes_accessed = float(analysis.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and bytes_accessed <= 0.0:
        return None
    return flops, bytes_accessed


def group_of(name: str) -> str:
    """Accounting group of a RecompileDetector name: the bucket-ladder
    entries (`device_predict[convert@4096]`) fold into one
    `device_predict` group; everything else groups by its own name."""
    return name.split("[", 1)[0]


# detector-name group -> the host timer scope whose ::device split times
# that group's dispatches (docs/Observability.md Timer scopes)
GROUP_PHASES: Dict[str, str] = {
    "grow_tree": "GBDT::grow_tree",
    "gradients": "GBDT::gradients",
    "device_eval": "GBDT::eval",
    "device_predict": "DevicePredictor::dispatch",
}


def roofline(flops: float, bytes_accessed: float, seconds: float,
             device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Roofline classification for `flops` / `bytes_accessed` of work
    that took `seconds` of device time, plus measured utilization (`mfu`,
    `bw_util`) when the device is a TPU — a share of a nominal CPU peak
    is not a number anyone should read."""
    if device_kind is None:
        device_kind = current_device_kind()
    peak_flops, peak_bw = backend_peaks(device_kind)
    out: Dict[str, Any] = {
        "flops": flops, "bytes": bytes_accessed,
        "peak_flops_per_s": peak_flops, "peak_bytes_per_s": peak_bw,
    }
    ridge = peak_flops / max(peak_bw, 1.0)
    ai = flops / bytes_accessed if bytes_accessed > 0 else None
    out["arithmetic_intensity"] = ai
    out["ridge_intensity"] = ridge
    # which roof binds this program: below the ridge the memory system
    # caps achievable flops/s no matter how good the kernel is
    out["bound"] = ("unknown" if ai is None
                    else "compute" if ai >= ridge else "hbm")
    out["mfu"] = None
    if seconds and seconds > 0:
        out["achieved_flops_per_s"] = flops / seconds
        out["achieved_bytes_per_s"] = bytes_accessed / seconds
        if device_kind.startswith("TPU"):
            out["mfu"] = flops / seconds / peak_flops
            out["bw_util"] = bytes_accessed / seconds / peak_bw
    return out


class CostModel:
    """Cumulative compiled-cost ledger over the wrapped jitted entries.

    `observe()` is called by RecompileDetector on every wrapped call
    (only when `enabled`): the first sighting of a (name, signature)
    harvests the lowered module's cost analysis, every call accumulates
    flops/bytes/calls into the entry's group.  `snapshot()` is the
    timer-snapshot analogue — per-iteration deltas come from diffing two
    snapshots (observability/callback record_metrics)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        # (name, sig) -> (flops, bytes) per call, or None when the entry
        # could not be harvested (no .lower, cost analysis unavailable)
        self._per_sig: Dict[Tuple[str, Any], Optional[Tuple[float, float]]] \
            = {}
        # name -> newest harvested (flops, bytes): O(1) lookup for call
        # sites that account their own dispatches (DevicePredictor)
        self._latest: Dict[str, Tuple[float, float]] = {}
        # group -> [flops, bytes, calls, unharvested_calls]
        self._totals: Dict[str, list] = {}

    # ------------------------------------------------------------- harvest
    def _harvest(self, fn, name: str, args, kwargs
                 ) -> Optional[Tuple[float, float]]:
        lower = getattr(fn, "lower", None)
        if lower is None:
            return None
        try:
            cost = _extract_cost(lower(*args, **kwargs).cost_analysis())
        except Exception as e:  # noqa: BLE001 - accounting must never kill the dispatch
            log.debug(f"cost_analysis harvest failed for {name}: {e}")
            return None
        if cost is not None:
            log.debug(f"cost model: {name} -> {cost[0]:.3e} flops, "
                      f"{cost[1]:.3e} bytes per call")
        return cost

    def observe(self, name: str, sig, fn, args, kwargs) -> None:
        """One call of a wrapped jitted entry with signature `sig`."""
        key = (name, sig)
        with self._lock:
            known = key in self._per_sig
            cost = self._per_sig.get(key)
        if not known:
            # harvest OUTSIDE the lock: lower() re-enters jax, and a
            # concurrent duplicate harvest is idempotent
            cost = self._harvest(fn, name, args, kwargs)
            with self._lock:
                self._per_sig[key] = cost
                if cost is not None:
                    self._latest[name] = cost
        group = group_of(name)
        with self._lock:
            tot = self._totals.setdefault(group, [0.0, 0.0, 0, 0])
            tot[2] += 1
            if cost is not None:
                tot[0] += cost[0]
                tot[1] += cost[1]
            else:
                tot[3] += 1

    # ------------------------------------------------------------- readout
    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Point-in-time cumulative totals {group: {flops, bytes, calls,
        unharvested}} — per-iteration roofline deltas diff two of these,
        exactly like Timer.snapshot."""
        with self._lock:
            return {g: {"flops": t[0], "bytes": t[1], "calls": t[2],
                        "unharvested": t[3]}
                    for g, t in self._totals.items()}

    def per_call(self, name: str) -> Optional[Tuple[float, float]]:
        """Harvested (flops, bytes) per call of `name`'s newest
        signature, or None.  O(1): dispatch-site accounting
        (DevicePredictor._run) reads this per serving dispatch."""
        with self._lock:
            return self._latest.get(name)

    def signatures_harvested(self) -> int:
        with self._lock:
            return sum(1 for c in self._per_sig.values() if c is not None)

    def reset(self) -> None:
        with self._lock:
            self._per_sig.clear()
            self._latest.clear()
            self._totals.clear()

    # ---------------------------------------------------------- aggregates
    def phase_roofline(self, prev: Dict[str, Dict[str, float]],
                       cur: Dict[str, Dict[str, float]],
                       phases: Dict[str, float],
                       device_kind: Optional[str] = None
                       ) -> Dict[str, Dict[str, Any]]:
        """Per-group roofline over one window: `prev`/`cur` are
        snapshot() results bracketing it, `phases` the timer's seconds
        deltas for the same window.  Device time prefers the
        `<scope>::device` split (pure settle wait) and falls back to the
        host scope total (which DeviceEval's synchronous fetch makes a
        fair device proxy)."""
        out: Dict[str, Dict[str, Any]] = {}
        for group, tot in cur.items():
            was = prev.get(group, {"flops": 0.0, "bytes": 0.0, "calls": 0})
            calls = int(tot["calls"] - was["calls"])
            if calls <= 0:
                continue
            flops = tot["flops"] - was["flops"]
            bytes_accessed = tot["bytes"] - was["bytes"]
            scope = GROUP_PHASES.get(group)
            dev_s = None
            if scope is not None:
                dev_s = phases.get(scope + "::device",
                                   phases.get(scope))
            entry = roofline(flops, bytes_accessed, dev_s or 0.0,
                             device_kind=device_kind)
            entry["calls"] = calls
            entry["device_s"] = dev_s
            # trim the verbose constants out of the per-iteration event
            # (they are invariant per backend; docs carry the table)
            for k in ("peak_flops_per_s", "peak_bytes_per_s",
                      "achieved_flops_per_s", "achieved_bytes_per_s"):
                entry.pop(k, None)
            out[group] = entry
        return out


# the process-wide ledger every RecompileDetector reports into
global_cost_model = CostModel()


def enable_cost_model(on: bool = True) -> bool:
    """Flip the process-wide cost model; returns the PREVIOUS state so
    scoped enablers (engine.train) can restore it."""
    prev = global_cost_model.enabled
    global_cost_model.enabled = bool(on)
    return prev
