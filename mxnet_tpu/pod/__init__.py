"""mxpod: the multi-host process-group runtime.

Everything PRs 6-10 built for one controller — GSPMD sharded training,
elastic membership, silent-corruption voting — generalized to host
processes, the fault domain where preemption, NIC flaps and SDC
actually occur:

- :class:`~mxnet_tpu.pod.context.PodContext` — process-group
  bootstrap: rank/nprocs/coordinator resolution, rank-0 control plane
  (kvstore server + journaled elastic coordinator), ``jax.distributed``
  bring-up on accelerators, socket-transport exchange on CPU CI;
- :class:`~mxnet_tpu.pod.group.PodGroup` /
  :class:`~mxnet_tpu.pod.group.CoordinatorLost` — the hardened
  control-plane transport: bounded-backoff reconnect, typed fence when
  the coordinator is gone for good;
- :mod:`~mxnet_tpu.pod.transport` — the cross-process allreduce the
  dist_sync / horovod-compat surfaces ride on the CPU backend;
- :func:`~mxnet_tpu.pod.drill.run_pod_drill` — subprocess N-host
  drills (SIGKILL a host, corrupt a host, kill the coordinator) shared
  by ``tools/mxresil.py pod`` and tests.

See docs/resilience.md, multi-host section.
"""
from .context import PodContext, active_context  # noqa: F401
from .group import CoordinatorLost, PodGroup  # noqa: F401

__all__ = ["PodContext", "active_context", "CoordinatorLost",
           "PodGroup"]


def run_pod_drill(*args, **kwargs):
    """Lazy alias for :func:`mxnet_tpu.pod.drill.run_pod_drill` (keeps
    ``import mxnet_tpu.pod`` free of the subprocess harness)."""
    from .drill import run_pod_drill as _impl
    return _impl(*args, **kwargs)
