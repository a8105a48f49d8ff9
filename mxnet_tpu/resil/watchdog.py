"""Heartbeat/stall watchdog over the telemetry metrics registry.

A wedged TPU job burns its whole reservation silently — the process is
alive, the step loop is not (a hung collective, a dead data worker, a
blocked host callback). The watchdog detects "alive but not
progressing" from signals that already exist (PR 2 metrics registry):

- **heartbeats** — :meth:`Watchdog.beat` is called from step
  boundaries (TrainGuard) and keeps a step-time EWMA; with no explicit
  caller it synthesizes beats from ``trainer_step_total`` counter
  progress via :meth:`poll`;
- **stall detection** — no heartbeat for ``max(MXRESIL_WATCHDOG_STALL_S,
  stall_factor × EWMA)`` ⇒ an ``error`` finding;
- **queue age** — ``mxserve_queue_depth > 0`` with no
  ``mxserve_dispatch_total`` progress across polls means the serving
  dispatcher is stuck while requests wait ⇒ an ``error`` finding;
- **breaker state** — any open circuit breaker ⇒ a ``warn`` finding
  (degraded mode is working as designed, but someone should look).

Gauges exported: ``mxresil_step_ewma_seconds``,
``mxresil_heartbeat_age_seconds``, ``mxresil_queue_age_seconds``.

Findings use the shared mxlint schema
(:class:`mxnet_tpu.passes.Finding` / ``findings_report``), so the same
automation that consumes ``tools/mxlint.py --json`` consumes watchdog
output (``tools/mxresil.py watch --json``). The clock is injectable:
tests drive stall windows with a fake clock and zero sleeping.

Extension points: :meth:`Watchdog.add_probe` registers extra detectors
(the elastic coordinator's per-worker missed-heartbeat probe emits
``worker_lost`` findings), and :meth:`Watchdog.on_verdict` registers
verdict ACTIONS — with none registered (the default) the watchdog
stays report-only; the elastic subsystem opts in a handler that turns
a ``worker_lost`` verdict into a membership-generation bump.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

from ..base import get_logger
from ..passes import Finding

__all__ = ["Watchdog", "host_liveness_probe"]

_log = get_logger("mxnet_tpu.resil.watchdog")

# counters whose progress counts as a training heartbeat in poll()
_STEP_COUNTERS = ("trainer_step_total",)


def host_liveness_probe(coordinator, dump: bool = True):
    """Pod host-scope liveness detector over an elastic coordinator
    (the rank-0 control plane of a multi-host process group,
    ``mxnet_tpu/pod/``). Returns a :meth:`Watchdog.add_probe`-shaped
    callable that, on every check:

    - exports one ``mxpod_host_beat_age_seconds_<worker>`` gauge per
      registered host process (last control-socket beat age);
    - emits a ``host_lost`` finding for every host over the heartbeat
      budget, naming the RANK and the last generation it was a member
      of — the pod-scope sibling of the coordinator's own
      ``worker_lost`` probe (which stays the verdict-action trigger);
    - freezes the crash flight recorder on the verdict (``dump=True``),
      so mxtrace captures what the group was doing when the host died
      (rate-limited per reason, trace/recorder.py).

    Wired by ``ElasticCoordinator.attach_watchdog`` (default on)."""
    import re as _re
    from ..telemetry import metrics as _metrics
    gauges: set = set()  # wids with a live beat-age gauge

    def _rank_of(wid: str, view) -> int:
        # the pod rank is encoded in the worker id (PodContext names
        # hosts w<rank>); the membership index is NOT the rank — it is
        # an arrival/sort position that shifts with departures
        m = _re.search(r"(\d+)$", wid)
        if m:
            return int(m.group(1))
        return view.rank_of(wid) if wid in view.workers else -1

    def probe() -> List[Finding]:
        findings: List[Finding] = []
        view = coordinator.view()
        threshold = coordinator.tracker.lost_after_s
        ages = coordinator.tracker.heartbeat_ages()
        # retire gauges of departed hosts: a dead host frozen at its
        # last pre-failure age would read healthy forever, and rejoin
        # churn would grow the registry unboundedly (the per-instance
        # gauge-leak class metriclint exists for)
        for wid in list(gauges - set(ages)):
            _metrics.unregister(f"mxpod_host_beat_age_seconds_{wid}")
            gauges.discard(wid)
        for wid, age in sorted(ages.items()):
            _metrics.gauge(
                f"mxpod_host_beat_age_seconds_{wid}",
                "seconds since this pod host's last control-socket "
                "heartbeat").set(age)
            gauges.add(wid)
            if age <= threshold:
                continue
            rank = _rank_of(wid, view)
            dump_path = None
            if dump:
                from ..trace import crash_dump
                dump_path = crash_dump(
                    "host_lost", site=f"pod.host.{wid}",
                    extra={"rank": rank, "worker": wid,
                           "generation": view.generation,
                           "beat_age_s": round(age, 3),
                           "budget_s": round(threshold, 3)})
            findings.append(Finding(
                "watchdog", "host_lost", f"pod.host.{wid}", "error",
                f"pod host {wid!r} (rank {rank}) silent for "
                f"{age:.2f}s (budget {threshold:.2f}s) at generation "
                f"{view.generation} — candidate for a host-loss "
                "membership bump"
                + (f"; flight recorder dumped to {dump_path}"
                   if dump_path else "")))
        return findings

    return probe


class Watchdog:
    """See module docstring. ``check()`` is pull-based (cheap, no
    thread); ``start(interval)`` runs it on a daemon thread and logs
    findings as they appear."""

    def __init__(self, stall_after_s: Optional[float] = None,
                 stall_factor: float = 10.0, ewma_alpha: float = 0.2,
                 min_stall_s: float = 1.0,
                 clock: Callable[[], float] = time.monotonic):
        from ..telemetry import metrics as _metrics
        if stall_after_s is None:
            from .. import config
            stall_after_s = float(config.get("MXRESIL_WATCHDOG_STALL_S"))
        self.stall_after_s = float(stall_after_s)  # 0 = auto (EWMA-based)
        self.stall_factor = float(stall_factor)
        self.ewma_alpha = float(ewma_alpha)
        self.min_stall_s = float(min_stall_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._ewma: Optional[float] = None
        self._last_beat: Optional[float] = None
        self._last_counts = {}  # step-counter values at the last poll
        self._queue_stuck_since: Optional[float] = None
        self._last_dispatch: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # pluggable detectors + the verdict-action registry (elastic
        # membership wires both — see on_verdict below). Both default
        # empty: the watchdog stays REPORT-ONLY unless a subsystem
        # explicitly opts a handler in.
        self._probes: List[Callable[[], List[Finding]]] = []
        self._verdict_handlers: List[Callable[[Finding], None]] = []
        self._g_ewma = _metrics.gauge(
            "mxresil_step_ewma_seconds", "EWMA of step wall time")
        self._g_age = _metrics.gauge(
            "mxresil_heartbeat_age_seconds",
            "seconds since the last training heartbeat")
        self._g_queue_age = _metrics.gauge(
            "mxresil_queue_age_seconds",
            "seconds the serving queue has been non-empty with no "
            "dispatch progress")
        self._m_stalls = _metrics.counter(
            "mxresil_stall_findings_total", "stall findings emitted")

    # -- feeding ----------------------------------------------------------
    def beat(self, step_seconds: Optional[float] = None):
        """One training heartbeat; ``step_seconds`` updates the EWMA."""
        with self._lock:
            self._last_beat = self._clock()
            if step_seconds is not None and step_seconds >= 0:
                self._ewma = (step_seconds if self._ewma is None
                              else self.ewma_alpha * step_seconds
                              + (1 - self.ewma_alpha) * self._ewma)
                self._g_ewma.set(self._ewma)

    def poll(self):
        """Synthesize heartbeats from registry progress (for loops that
        never call :meth:`beat` directly): any step-counter increase
        since the last poll is a beat; serving-queue progress is
        tracked for the queue-age signal."""
        from ..telemetry import metrics as _metrics
        reg = _metrics.all_metrics()
        now = self._clock()
        for name in _STEP_COUNTERS:
            m = reg.get(name)
            if m is None:
                continue
            v = m.value()
            prev = self._last_counts.get(name)
            self._last_counts[name] = v
            if prev is not None and v > prev:
                self.beat()
        depth = reg.get("mxserve_queue_depth")
        disp = reg.get("mxserve_dispatch_total")
        with self._lock:
            if depth is None or depth.value() <= 0:
                self._queue_stuck_since = None
                self._g_queue_age.set(0.0)
            else:
                d = disp.value() if disp is not None else 0
                if self._last_dispatch is not None and \
                        d > self._last_dispatch:
                    self._queue_stuck_since = None  # progress
                if self._queue_stuck_since is None:
                    self._queue_stuck_since = now
                self._g_queue_age.set(now - self._queue_stuck_since)
            if disp is not None:
                self._last_dispatch = disp.value()

    # -- extension points -------------------------------------------------
    def add_probe(self, probe: Callable[[], List[Finding]]
                  ) -> Callable[[], List[Finding]]:
        """Register an extra detector: a zero-arg callable returning
        mxlint-schema findings, run on every :meth:`check`. The
        elastic coordinator registers its missed-heartbeat probe here
        (``worker_lost`` findings, ElasticCoordinator.attach_watchdog)."""
        self._probes.append(probe)
        return probe

    def on_verdict(self, handler: Callable[[Finding], None]
                   ) -> Callable[[Finding], None]:
        """Register a verdict ACTION: called once per finding each
        :meth:`check`. With no handlers registered (the default) the
        watchdog is report-only — exactly the old behavior. The
        elastic subsystem opts in a handler that turns a
        ``worker_lost`` finding into a membership-generation bump
        instead of just a log line (docs/resilience.md). Handler
        exceptions are swallowed: the watchdog must never kill the
        job it guards."""
        self._verdict_handlers.append(handler)
        return handler

    # -- checking ---------------------------------------------------------
    def stall_threshold_s(self) -> float:
        if self.stall_after_s > 0:
            return self.stall_after_s
        with self._lock:
            ewma = self._ewma
        if ewma is None:
            return max(self.min_stall_s, 30.0)  # no data yet: be patient
        return max(self.min_stall_s, self.stall_factor * ewma)

    def check(self) -> List[Finding]:
        """Evaluate all detectors; returns mxlint-schema findings
        (empty list = healthy)."""
        findings: List[Finding] = []
        now = self._clock()
        with self._lock:
            last_beat = self._last_beat
            ewma = self._ewma
            queue_since = self._queue_stuck_since
        threshold = self.stall_threshold_s()
        if last_beat is not None:
            age = now - last_beat
            self._g_age.set(age)
            if age > threshold:
                self._m_stalls.inc()
                # a stall verdict freezes the flight recorder: the
                # dump's last spans show what the step loop was doing
                # when it stopped beating (trace/recorder.py)
                from ..trace import crash_dump
                dump = crash_dump(
                    "watchdog_stall",
                    extra={"age_s": round(age, 3),
                           "threshold_s": round(threshold, 3)})
                findings.append(Finding(
                    "watchdog", "stall", "trainer", "error",
                    f"no heartbeat for {age:.1f}s (threshold "
                    f"{threshold:.1f}s"
                    + (f", step EWMA {ewma:.3f}s" if ewma else "")
                    + ") — the step loop looks wedged"
                    + (f"; flight recorder dumped to {dump}"
                       if dump else "")))
        if queue_since is not None:
            q_age = now - queue_since
            if q_age > threshold:
                self._m_stalls.inc()
                findings.append(Finding(
                    "watchdog", "queue_stall", "serve", "error",
                    f"serving queue non-empty for {q_age:.1f}s with no "
                    "dispatch progress — dispatcher stuck or device "
                    "wedged"))
        from . import hooks
        for site, st in hooks.breaker_states().items():
            if st["state"] != "closed":
                findings.append(Finding(
                    "watchdog", "breaker_open", site, "warn",
                    f"circuit {site!r} is {st['state']} after "
                    f"{st['consecutive_failures']} consecutive "
                    "failures — running degraded"))
        for probe in list(self._probes):
            try:
                findings.extend(probe() or [])
            except Exception:  # a broken probe must not kill the job
                pass
        for f in findings:
            for handler in list(self._verdict_handlers):
                try:
                    handler(f)
                except Exception:  # actions are best-effort too
                    pass
        return findings

    # -- background mode --------------------------------------------------
    def start(self, interval_s: float = 5.0) -> "Watchdog":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.poll()
                    for f in self.check():
                        _log.warning("%r", f)
                except Exception:  # the watchdog must never kill the job
                    pass

        self._thread = threading.Thread(
            target=loop, name="mxresil-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._thread = None
