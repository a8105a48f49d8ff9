"""mxresil: the fault-tolerance subsystem.

The reference stack leaned on ps-lite's server-side fault handling
(ref: ps-lite van timeouts + kvstore_dist_server resends); this
TPU-native reproduction replaces parameter servers with collectives and
a thin async PS, so resilience has to be a first-class runtime layer of
its own. Four pillars, one package (ISSUE 4):

- :mod:`~mxnet_tpu.resil.faultplan` — deterministic, seedable fault
  injection (``MXRESIL_FAULT_PLAN``), with hooks wired into kvstore
  push/pull, PrefetchingIter, ServingEngine submit and CheckpointManager
  I/O. Drills and chaos benches run REAL failure paths, not mocks.
- :mod:`~mxnet_tpu.resil.policy` — composable retry/timeout policies:
  jittered exponential backoff, retry budgets, deadline propagation, and
  a circuit breaker that trips to a fail-fast degraded mode.
- :mod:`~mxnet_tpu.resil.guard` — :class:`TrainGuard`, the
  preemption-aware training scope: SIGTERM/SIGINT trigger an emergency
  checkpoint at the next step boundary; non-finite losses roll back to
  the last good checkpoint; restarts resume via
  ``CheckpointManager.restore_latest``.
- :mod:`~mxnet_tpu.resil.watchdog` — heartbeat/stall detection fed by
  the telemetry metrics registry (step-time EWMA, queue age,
  last-heartbeat gauges), emitting findings in the shared mxlint
  ``--json`` schema.

``tools/mxresil.py`` runs fault drills (MTTR / steps-lost reports).
Architecture: docs/resilience.md.
"""
from __future__ import annotations

from . import faultplan  # noqa: F401
from . import hooks  # noqa: F401
from . import policy  # noqa: F401
from .faultplan import (FaultInjectedError, FaultPlan,  # noqa: F401
                        WorkerKilled, WorkerPreempted, active_plan,
                        inject)
from .guard import Preempted, TrainGuard  # noqa: F401
from .policy import (BackoffSchedule, CircuitBreaker,  # noqa: F401
                     CircuitOpenError, RetryBudget, RetryPolicy,
                     RetryableError, deadline_scope, remaining_deadline)
from .watchdog import Watchdog  # noqa: F401

__all__ = ["faultplan", "policy", "hooks", "FaultPlan", "FaultInjectedError",
           "active_plan", "inject", "RetryPolicy", "RetryBudget",
           "RetryableError", "BackoffSchedule", "CircuitBreaker",
           "CircuitOpenError", "deadline_scope", "remaining_deadline",
           "TrainGuard", "Preempted", "Watchdog"]
