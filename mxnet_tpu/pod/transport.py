"""Socket-transport process allreduce: the CPU-CI stand-in for
cross-process collectives.

jaxlib's CPU backend initializes ``jax.distributed`` fine but cannot
RUN a cross-process collective ("Multiprocess computations aren't
implemented on the CPU backend") — the gap that kept the dist_sync /
horovod-compat multi-process tests skipped since PR 5. This module
closes it: on the CPU backend, ``parallel.collectives.
allreduce_across_processes`` routes through ONE process-level elastic
session against the rank-0 kvstore server (the same ``elastic.*``
fenced-round family the mxpod training exchange rides), so the sum is

- **synchronous** — a round completes when every registered rank
  contributed, folded in sorted-worker order (bit-identical regardless
  of arrival order);
- **typed-aborting** — a dead peer fences the blocked survivors with
  ``MembershipChanged`` instead of the dist_sync wedge, and a dead
  coordinator surfaces as ``CoordinatorLost`` after bounded backoff.

On TPU/GPU this module is never consulted: the collective compiles
into the step (``allreduce_across_processes``'s psum path).

The session registers ``host processes``, not training workers — a pod
training job uses its own :class:`ElasticKVStore` sessions; this
transport exists for the dist_sync/hvd compat surface where the caller
expects plain SPMD allreduce semantics (every process calls in
lockstep). One session per process, formed on first use.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as onp

from ..base import MXNetError, get_logger, worker_rank
from ..san.runtime import make_lock

__all__ = ["socket_mode", "host_allreduce", "host_barrier", "reset"]

_log = get_logger("mxnet_tpu.pod")

_LOCK = make_lock("pod.transport.session")
_SESSION = None


def _num_workers() -> int:
    import jax
    try:
        env_n = int(os.environ.get("MX_NUM_WORKERS", "1"))
    except ValueError:
        env_n = 1
    return max(env_n, jax.process_count())


def socket_mode() -> bool:
    """True when cross-process reduction must ride the socket
    transport: CPU backend + more than one launched process."""
    import jax
    if jax.default_backend() != "cpu":
        return False
    return _num_workers() > 1


def _ensure_session(timeout_s: float = 120.0):
    """Register this process and wait for the full world ONCE; later
    calls reuse the formed session (heartbeat pump keeps it alive
    through compile/IO gaps between reductions)."""
    global _SESSION
    with _LOCK:
        if _SESSION is not None:
            return _SESSION
        import jax
        from ..elastic.session import ElasticSession
        from ..kvstore_server import ensure_server
        from .group import PodGroup
        n = _num_workers()
        rank = jax.process_index() if jax.distributed.is_initialized() \
            else worker_rank()
        addr = ensure_server(n, rank)
        ses = ElasticSession(PodGroup(addr), f"hostred-{rank}",
                             devices=(rank,))
        ses.start_heartbeat_pump()
        deadline = time.monotonic() + timeout_s
        while ses.world < n:
            if time.monotonic() > deadline:
                raise MXNetError(
                    f"socket-transport formation timed out: "
                    f"{ses.world}/{n} processes registered at {addr} "
                    f"within {timeout_s:.0f}s")
            time.sleep(0.02)
            ses.refresh()
        ses.rebuild()  # one agreed generation before the first round
        _log.info("socket-transport exchange formed: rank %d of %d "
                  "at %s (CPU backend, fenced elastic rounds)",
                  rank, n, addr)
        _SESSION = ses
        return ses


def host_allreduce(x, timeout_s: float = 120.0) -> onp.ndarray:
    """Sum ``x`` (same shape on every process) across all launched
    processes through generation-fenced rounds. A peer death raises
    the typed ``MembershipChanged`` — dist_sync semantics have no
    elastic accounting, so the job fails LOUDLY rather than silently
    renormalizing the sum over fewer contributors."""
    ses = _ensure_session(timeout_s)
    return ses.allreduce("__hostred", onp.asarray(x))


def host_barrier(timeout_s: float = 120.0) -> None:
    """Zero-payload fenced round: completes when every process
    arrives, aborts typed when one dies."""
    ses = _ensure_session(timeout_s)
    ses.allreduce("__hostbar", onp.zeros((), onp.float32))


def reset() -> None:
    """Drop the formed session (tests). The next reduction re-forms."""
    global _SESSION
    with _LOCK:
        ses, _SESSION = _SESSION, None
    if ses is not None:
        try:
            ses.stop_heartbeat_pump()
            ses.leave()
        except Exception:
            pass
        close = getattr(ses.group, "close", None)
        if close:
            try:
                close()
            except Exception:
                pass
