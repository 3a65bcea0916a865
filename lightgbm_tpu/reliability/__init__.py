"""Fault tolerance for long training runs (ROADMAP: production-scale serving).

Three pillars, mirroring what the reference engine gets from its socket
layer and whole-file model writes (ref survey §1, src/network/):

* checkpoint/resume — `CheckpointManager` writes atomic, rotated
  checkpoints (model text + exact trainer state) so a job killed at
  iteration k restarts from k, not from zero (`checkpoint.py`).
* worker supervision — poll-based process watchdog + retry/backoff for
  the multi-process launcher (`supervisor.py`, used by `distributed.py`).
* fault injection — env-driven crash/NaN/write-failure hooks so the
  recovery paths above are testable without real hardware faults
  (`faults.py`, `LGBM_TPU_FAULT=worker_crash@3,...`).
* stall watchdog + degradation ladder — `guard.py` turns live-but-hung
  runs (a rank wedged in a collective until the wall-clock cap) into a
  structured stall diagnosis and a distinct exit code, and with
  `auto_degrade=true` relaunches from checkpoint with the next risky
  knob disabled.
"""

from __future__ import annotations

from ..utils.log import LightGBMError


class NonFiniteError(LightGBMError):
    """Raised when NaN/Inf gradients or eval scores are detected: boosting
    on non-finite values silently produces garbage trees, so training
    fails fast (or rolls back to the last checkpoint when one exists)."""


from . import faults  # noqa: E402
from .checkpoint import Checkpoint, CheckpointManager  # noqa: E402
from .elastic import ElasticDecision, ElasticPolicy  # noqa: E402
from .faults import WORKER_LOST_EXIT_CODE  # noqa: E402
from .guard import (DEGRADE_LADDER, STALL_EXIT_CODE,  # noqa: E402
                    RunGuard, classify_returncode)

__all__ = ["Checkpoint", "CheckpointManager", "NonFiniteError", "faults",
           "RunGuard", "STALL_EXIT_CODE", "DEGRADE_LADDER",
           "classify_returncode", "ElasticDecision", "ElasticPolicy",
           "WORKER_LOST_EXIT_CODE"]
