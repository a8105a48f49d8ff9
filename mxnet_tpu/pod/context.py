"""PodContext: multi-host process-group bootstrap.

One object wires a host process into the pod so ``Trainer.fuse_step(
shard_plan=...)`` is UNCHANGED across 1..N host processes:

- **identity** — rank / nprocs / coordinator address resolve from the
  ``MXPOD_{RANK,NPROCS,COORDINATOR}`` flags, falling back to the
  ``MX_WORKER_ID`` / ``MX_NUM_WORKERS`` / ``MX_KV_SERVER`` env that
  ``tools/launch.py`` exports — the same launchers (local/ssh/mpi/sge/
  yarn) drive pods;
- **control plane** — rank 0 binds the kvstore server at the
  coordinator address; its embedded :class:`ElasticCoordinator` owns
  membership verdicts and (``MXPOD_JOURNAL_DIR``) the generation
  journal a RESTARTED rank-0 replays to re-form the group. Every rank
  reaches it through :class:`~mxnet_tpu.pod.group.PodGroup` — the
  bounded-backoff / typed-:class:`CoordinatorLost` transport;
- **accelerator wiring** — on TPU (any non-CPU backend),
  :meth:`maybe_init_jax_distributed` completes ``jax.distributed``
  bring-up so a ShardPlan mesh spans the pod's global devices and the
  gradient exchange stays IN-JIT (the PR-6 GSPMD path). jaxlib's CPU
  backend has no multiprocess collectives, so CPU CI instead rides the
  ElasticKVStore socket transport — same fenced-round protocol, the
  exchange just crosses the control socket (``ctx.kvstore()`` +
  ``gluon.Trainer(..., kvstore=ctx.kvstore())`` and the split-phase
  ElasticStepFunction take over);
- **group formation** — :meth:`form_group` blocks until all
  ``nprocs`` ranks registered, then meets them at the rebuild barrier
  so every rank starts the first exchange at one agreed generation;
- **host elasticity** — a lost host bumps the generation (missed
  beats on the control socket), survivors absorb the bump inside
  ``step()`` with zero user code, and a restarted host re-enters with
  ``join=True``: any stale identity from its previous life is shed
  (one immediate bump instead of waiting out the heartbeat budget)
  and the live state syncs FROM THE GROUP, never a checkpoint file.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

from ..base import MXNetError, get_logger, worker_rank

__all__ = ["PodContext", "active_context"]

_log = get_logger("mxnet_tpu.pod")

_ACTIVE: Optional["PodContext"] = None


def active_context() -> Optional["PodContext"]:
    """The process's live PodContext (checkpoint manifests record its
    topology; tools/diagnose.py reads it). None outside a pod run."""
    return _ACTIVE


class PodContext:
    def __init__(self, coordinator: Optional[str] = None,
                 rank: Optional[int] = None,
                 nprocs: Optional[int] = None,
                 journal_dir: Optional[str] = None,
                 join: Optional[bool] = None,
                 start_server: bool = True,
                 grace_s: Optional[float] = None):
        from .. import config
        global _ACTIVE
        if join is None:
            # the cluster-manager restart contract: a rescheduled host
            # (including a restarted rank 0, which must REPLAY its
            # journal rather than rotate it) comes back with
            # MXPOD_JOIN=1 and plain `PodContext()` user code — the
            # env is the default, the kwarg the override
            join = os.environ.get("MXPOD_JOIN") == "1"
        if rank is None:
            rank = int(config.get("MXPOD_RANK"))
            if rank < 0:
                rank = worker_rank()
        self.rank = int(rank)
        if nprocs is None:
            nprocs = int(config.get("MXPOD_NPROCS")) or \
                int(os.environ.get("MX_NUM_WORKERS", "1"))
        self.nprocs = int(nprocs)
        if coordinator is None:
            coordinator = str(config.get("MXPOD_COORDINATOR") or "") or \
                os.environ.get("MX_KV_SERVER")
        if coordinator is None:
            if self.nprocs > 1:
                raise MXNetError(
                    "PodContext needs a coordinator endpoint for a "
                    f"{self.nprocs}-process pod: set MXPOD_COORDINATOR="
                    "host:port (or launch via tools/launch.py, which "
                    "exports MX_KV_SERVER)")
            import socket as _socket
            with _socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                coordinator = f"127.0.0.1:{s.getsockname()[1]}"
        self.coordinator = coordinator
        self.join = bool(join)
        # one flag tunes host-loss detection end to end: the rank-0
        # verdict policy and every worker's pump read MXELASTIC_*
        hb = float(config.get("MXPOD_HEARTBEAT_S"))
        if hb > 0:
            config.set_flag("MXELASTIC_HEARTBEAT_S", hb)
        if journal_dir is not None:
            # reaches the server's lazily-created coordinator
            config.set_flag("MXPOD_JOURNAL_DIR", journal_dir)
        self.journal_dir = str(config.get("MXPOD_JOURNAL_DIR") or "")
        self.grace_s = grace_s
        self.worker_id = os.environ.get("MX_WORKER_ID_POD",
                                        f"w{self.rank}")
        self.restored = False
        self._server = None
        self._kv = None
        if self.is_coordinator_host and start_server:
            if not self.join:
                # FRESH job on this coordinator host: rotate any stale
                # journal so a reused MXPOD_JOURNAL_DIR cannot replay a
                # PREVIOUS job's members as phantoms (each would burn a
                # full heartbeat budget and spray host_lost verdicts).
                # A restarted coordinator re-entering a RUNNING job
                # must come back with join=True (MXPOD_JOIN=1 — the
                # cluster-manager restart contract, docs/resilience.md)
                # so the replay path stays armed for it.
                self._rotate_stale_journal()
            from ..kvstore_server import KVServer
            self._server = KVServer(self.coordinator, self.nprocs)
            # arm the membership plane NOW: a restarted rank-0 must
            # replay the journal before any worker's first command
            co = self._server._ensure_elastic()
            self.restored = co.restored
        from ..telemetry import metrics as _metrics
        _metrics.gauge("mxpod_rank", "this process's pod rank").set(
            self.rank)
        _metrics.gauge("mxpod_nprocs",
                       "host processes in the pod").set(self.nprocs)
        _ACTIVE = self
        _log.info("pod context: rank %d/%d, coordinator %s%s%s",
                  self.rank, self.nprocs, self.coordinator,
                  " (serving)" if self._server else "",
                  " [journal replayed]" if self.restored else "")

    def _rotate_stale_journal(self):
        path = os.path.join(self.journal_dir, "membership.jsonl") \
            if self.journal_dir else None
        if not path or not os.path.exists(path):
            return
        bak = path + ".prev"
        try:
            os.replace(path, bak)
            _log.warning(
                "pod: fresh start found an existing membership "
                "journal at %s — rotated to %s (a RESTARTED "
                "coordinator re-entering a running job must set "
                "MXPOD_JOIN=1 to replay it)", path, bak)
        except OSError as e:
            _log.warning("pod: could not rotate stale journal %s: %s",
                         path, e)

    # ------------------------------------------------------------------
    @property
    def is_coordinator_host(self) -> bool:
        return self.rank == 0

    def local_device_ids(self) -> Tuple[int, ...]:
        """Per-host device visibility recorded with the membership: the
        global jax device ids under an initialized ``jax.distributed``
        job, else the rank itself (CPU CI: one logical slot per host)."""
        import jax
        if jax.distributed.is_initialized():
            return tuple(d.id for d in jax.local_devices())
        return (self.rank,)

    def maybe_init_jax_distributed(self) -> bool:
        """Complete ``jax.distributed`` bring-up on accelerator
        backends so ShardPlan meshes span the pod and the exchange
        stays in-jit. On the CPU backend this is deliberately skipped:
        jaxlib-CPU has no multiprocess collectives, and the gradient
        exchange rides the ElasticKVStore socket transport instead
        (same fenced-round protocol either way)."""
        import jax
        from ..base import initialize_distributed
        if jax.distributed.is_initialized():
            return True
        if jax.default_backend() == "cpu":
            _log.info(
                "pod: CPU backend — jax.distributed collectives "
                "unavailable; gradient exchange rides the elastic "
                "socket transport (docs/resilience.md multi-host)")
            return False
        initialize_distributed(num_processes=self.nprocs,
                               process_id=self.rank)
        return jax.distributed.is_initialized()

    # ------------------------------------------------------------------
    def group(self):
        from .group import PodGroup
        return PodGroup(self.coordinator, grace_s=self.grace_s)

    def kvstore(self, join: Optional[bool] = None):
        """The pod's elastic kvstore: fenced-round exchange over the
        control socket, generation-aborted, guard-tappable. ``join=
        True`` re-enters through the group state-sync — shedding any
        stale identity a previous life of this host left behind (one
        immediate bump instead of waiting out the heartbeat budget)."""
        from ..elastic.kvstore import ElasticKVStore
        join = self.join if join is None else bool(join)
        group = self.group()
        if join:
            try:
                view = group.view()
                if self.worker_id in view.workers:
                    _log.info(
                        "pod rejoin: shedding stale identity %r from "
                        "generation %d before the join state-sync",
                        self.worker_id, view.generation)
                    group.leave(self.worker_id)
            except MXNetError:
                pass  # view is best-effort; join proceeds regardless
        kv = ElasticKVStore(group=group, worker_id=self.worker_id,
                            devices=self.local_device_ids(), join=join)
        if not join:
            kv.session.start_heartbeat_pump()
        self._kv = kv
        return kv

    def form_group(self, kv=None, timeout_s: float = 120.0):
        """Block until all ``nprocs`` ranks registered, then meet them
        at the rebuild barrier: every rank leaves with the same agreed
        generation before the first exchange (a joiner skips this —
        ``ElasticSession.join`` already ends inside the barrier)."""
        import time as _time
        kv = kv or self._kv
        if kv is None:
            raise MXNetError("form_group: call kvstore() first")
        ses = kv.session
        if self.join:
            return ses.view
        deadline = _time.monotonic() + float(timeout_s)
        while ses.world < self.nprocs:
            if _time.monotonic() > deadline:
                raise MXNetError(
                    f"pod formation timed out: {ses.world}/"
                    f"{self.nprocs} ranks registered within "
                    f"{timeout_s:.0f}s — check the launcher and "
                    f"coordinator {self.coordinator}")
            _time.sleep(0.05)
            ses.refresh()
        return ses.rebuild()

    # ------------------------------------------------------------------
    def topology(self) -> Dict[str, object]:
        """The manifest-recorded pod topology (checkpoint.py):
        ``{n_hosts, ranks, coordinator}``."""
        workers: Sequence[str] = ()
        if self._kv is not None and self._kv.session.view is not None:
            workers = self._kv.session.view.workers
        return {"n_hosts": len(workers) or self.nprocs,
                "ranks": list(workers) or
                [f"w{r}" for r in range(self.nprocs)],
                "coordinator": self.coordinator}

    def describe(self) -> Dict[str, object]:
        out = {"rank": self.rank, "nprocs": self.nprocs,
               "coordinator": self.coordinator,
               "coordinator_host": self.is_coordinator_host,
               "worker_id": self.worker_id,
               "journal_dir": self.journal_dir or None,
               "restored": self.restored,
               "join": self.join}
        if self._server is not None and \
                self._server._elastic is not None:
            out["control_plane"] = self._server._elastic.describe()
        return out

    def close(self):
        global _ACTIVE
        if self._kv is not None:
            try:
                self._kv.close()
            except Exception:
                pass
            self._kv = None
        if self._server is not None:
            self._server.stop()
            self._server = None
        if _ACTIVE is self:
            _ACTIVE = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
