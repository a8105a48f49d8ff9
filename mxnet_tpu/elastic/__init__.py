"""mxelastic: elastic-membership training (ROADMAP 5(a)).

Workers leaving and joining mid-training without a restart. The
reference MXNet's dist_sync wedges forever on a dead peer and
dist_async silently bleeds throughput; the resil stack (PR 4) can
*detect* a stall and *survive* a preemption — this package makes the
job *adapt*:

- :mod:`~mxnet_tpu.elastic.membership` — the model: worker set +
  monotone **generation** number; every join/leave/lost-verdict bumps
  it once, and the typed :class:`MembershipChanged` fences every
  in-flight exchange tagged with a dead generation.
- :mod:`~mxnet_tpu.elastic.coordinator` — the rank-0 control plane:
  heartbeat ledger, generation-checked reduce rounds (deterministic
  sorted-worker fold), the rebuild barrier, join state-sync. Embedded
  in :class:`~mxnet_tpu.kvstore_server.KVServer` for multi-process
  jobs; shared directly by in-process drill workers.
- :mod:`~mxnet_tpu.elastic.session` — one worker's generation-scoped
  state: round numbering, effective-batch / LR-schedule accounting,
  snapshot/install for the join protocol (a rejoiner syncs from the
  group's LIVE state, never a checkpoint file).
- :mod:`~mxnet_tpu.elastic.kvstore` — the ``'elastic'`` kvstore type:
  synchronous flat-bucket allreduce that aborts typed instead of
  wedging (``elastic_abort = "generation"``, the contract
  ``passes/elasticlint.py`` audits).
- :mod:`~mxnet_tpu.elastic.stepfn` — the split-phase fused step: a
  world-size-independent grad program, the host-side fenced exchange,
  and an update program whose ``rescale_grad`` re-keys **exactly once**
  per world-size change.
- :mod:`~mxnet_tpu.elastic.drill` — the deterministic in-process
  kill/rejoin drill harness behind ``tools/mxresil.py elastic``.

Flags: ``MXELASTIC_HEARTBEAT_S`` / ``MXELASTIC_MISS_LIMIT`` /
``MXELASTIC_MIN_WORLD`` / ``MXELASTIC_LR_SCALE`` /
``MXELASTIC_LOSS_TOL``. Runbook + protocol walkthrough:
docs/resilience.md (elastic section).
"""
from __future__ import annotations

from .coordinator import ElasticCoordinator  # noqa: F401
from .kvstore import ElasticKVStore, RemoteGroup  # noqa: F401
from .membership import (ElasticTimeout, GroupFailed,  # noqa: F401
                         MembershipChanged, MembershipTracker,
                         MembershipView, WorkerEvicted)
from .session import ElasticSession  # noqa: F401

__all__ = ["MembershipChanged", "WorkerEvicted", "GroupFailed",
           "ElasticTimeout", "MembershipView", "MembershipTracker",
           "ElasticCoordinator", "ElasticSession", "ElasticKVStore",
           "RemoteGroup"]


def __getattr__(name):
    # heavy imports (jax tracing) stay lazy: the step function pulls in
    # the whole step/ stack
    if name == "ElasticStepFunction":
        from .stepfn import ElasticStepFunction
        return ElasticStepFunction
    if name == "run_elastic_drill":
        from .drill import run_elastic_drill
        return run_elastic_drill
    raise AttributeError(name)
