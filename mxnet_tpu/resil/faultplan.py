"""Deterministic, seedable fault injection (``MXRESIL_FAULT_PLAN``).

A fault plan is a semicolon-separated list of ``selector=action``
clauses evaluated at named injection *sites* — the hot call paths the
framework wires :func:`inject` into (kvstore.push / kvstore.pull / io /
serve.submit / checkpoint.write / checkpoint.restore / step):

    MXRESIL_FAULT_PLAN="step:40=preempt;kvstore.push@3=raise;io=stall:200ms"

Selectors:

- ``<site>``          every invocation of the site;
- ``<site>@K``        only the K-th invocation (1-based, per process);
- ``<site>%P``        each invocation with probability P — *seedable*:
                      the per-site RNG is ``MXRESIL_SEED ^ crc32(site)``,
                      so a given seed reproduces the same fault sequence
                      bit-for-bit (no wall clock, no global random state);
- ``step:N``          the ``step`` site when the training step counter
                      equals N (TrainGuard passes ``step=`` through);
- ``<site>:N+``       every invocation with step counter >= N — a
                      *persistent* fault that survives mxguard's
                      deterministic re-execution (a ``:N`` or ``@K``
                      clause clears on the re-executed attempt and
                      classifies as transient instead).

Actions:

- ``raise`` / ``raise:Name`` — raise :class:`FaultInjectedError` (a
  :class:`~mxnet_tpu.resil.policy.RetryableError`, so retry policies
  absorb it — that is the point: drills exercise the recovery path);
- ``stall:200ms`` / ``stall:1.5s`` — sleep in place (slow DCN / slow
  disk simulation; stall detection is the watchdog's job);
- ``preempt``   — SIGTERM to this process (the cloud-preemption signal;
  TrainGuard turns it into an emergency checkpoint + clean exit);
- ``kill``      — SIGKILL to this process (hard crash, nothing runs);
  in *thread mode* (``inject(..., thread_mode=True)``, the per-worker
  ``elastic.worker.<id>`` sites of the in-process elastic drills)
  preempt/kill instead raise the typed :class:`WorkerPreempted` /
  :class:`WorkerKilled` so exactly ONE worker thread dies;
- ``kill9``     — SIGKILL to this process ALWAYS, even under
  ``thread_mode`` — the process-scope action of the mxpod host drills
  (``pod.host.<rank>:K=kill9`` fires at step K of that host's step
  loop and takes the whole host process down, heartbeat pump and all;
  survivors must detect the dead HOST through missed beats on the
  control socket — mxnet_tpu/pod/drill.py);
- ``nan``       — return the token ``"nan"`` to the caller, which
  poisons that step's loss (TrainGuard's non-finite rollback drill);
- ``sdc`` / ``sdc:bitflip`` / ``sdc:scale`` — return the token
  ``"sdc:<mode>"`` to the caller: the mxguard fingerprint taps
  (``guard.sdc`` / ``guard.sdc.<worker_id>`` sites) consume it by corrupting
  ONE gradient element deterministically — ``bitflip`` flips the high
  exponent bit of the absmax element (loud: caught by cross-replica
  voting within the step), ``scale`` multiplies it by ``1 + 2^-10``
  (silent: below the vote threshold, found later by
  ``tools/mxresil.py replay``). The drill trigger for every mxguard
  test.

When ``MXRESIL_FAULT_PLAN`` is unset, :func:`inject` is a two-dict-read
no-op — the hooks cost nothing in production and record zero retries
(tests/test_resilience.py holds exactly that).
"""
from __future__ import annotations

import os
import random
import re
import signal
import threading
import time
import zlib
from typing import Dict, List, Optional

from ..base import MXNetError
from .policy import RetryableError

__all__ = ["FaultInjectedError", "WorkerKilled", "WorkerPreempted",
           "Clause", "FaultPlan", "parse_plan", "active_plan", "inject",
           "is_active", "reset"]

# the injection sites the framework wires up; inject() accepts any name
# (user code can add its own sites) but the parser warns on typos.
# Per-instance site families: elastic.worker.<rank> (thread-mode
# in-process drills), guard.sdc[.<worker_id>] (mxguard taps),
# pod.host.<rank> (the mxpod subprocess worker's step boundary)
KNOWN_SITES = ("kvstore.push", "kvstore.pull", "io", "serve.submit",
               "checkpoint.write", "checkpoint.restore", "step")


class FaultInjectedError(RetryableError):
    """An injected transient fault (``raise`` action). Retryable by
    contract: policies treat it exactly like a real transient failure."""


class WorkerKilled(MXNetError):
    """Thread-mode ``kill``: this in-process drill worker dies NOW —
    abrupt, no cleanup, no goodbye (the SIGKILL analog for worker
    threads; elastic drills detect the death via missed heartbeats).
    NOT retryable."""


class WorkerPreempted(MXNetError):
    """Thread-mode ``preempt``: this in-process drill worker received
    its preemption notice — it should leave the group gracefully
    (`ElasticSession.leave`) and exit (the SIGTERM analog). NOT
    retryable."""


_CLAUSE_RE = re.compile(
    r"^(?P<site>[a-zA-Z_][\w.]*)"
    r"(?:@(?P<nth>\d+)|%(?P<prob>0?\.\d+|1(?:\.0*)?)"
    r"|:(?P<step>\d+)(?P<step_from>\+)?)?"
    r"=(?P<action>[a-zA-Z_][a-zA-Z_0-9]*)(?::(?P<arg>[^;]+))?$")


def _parse_duration_s(arg: str) -> float:
    """``200ms`` / ``1.5s`` / bare number (= ms) -> seconds."""
    arg = arg.strip().lower()
    if arg.endswith("ms"):
        return float(arg[:-2]) / 1000.0
    if arg.endswith("s"):
        return float(arg[:-1])
    return float(arg) / 1000.0


class Clause:
    """One ``selector=action`` rule plus its firing state."""

    __slots__ = ("site", "nth", "prob", "step", "step_from", "action",
                 "arg", "stall_s", "fired", "_rng")

    def __init__(self, site: str, action: str, arg: Optional[str] = None,
                 nth: Optional[int] = None, prob: Optional[float] = None,
                 step: Optional[int] = None, step_from: bool = False,
                 seed: int = 0):
        if action not in ("raise", "stall", "preempt", "kill", "kill9",
                          "nan", "sdc"):
            raise MXNetError(f"fault plan: unknown action {action!r} "
                             "(raise|stall|preempt|kill|kill9|nan|sdc)")
        if action == "stall":
            if not arg:
                raise MXNetError("fault plan: stall needs a duration, "
                                 "e.g. stall:200ms")
            self.stall_s = _parse_duration_s(arg)
        else:
            self.stall_s = 0.0
        if action == "nan" and site in KNOWN_SITES and site != "step":
            # of the wired framework sites only the step boundary
            # consumes the nan token; anywhere else it would count an
            # "injected fault" that did nothing (custom user sites may
            # read inject()'s return and keep token semantics)
            raise MXNetError(
                "fault plan: the nan action only applies to the 'step' "
                f"site (got {site!r}); use raise/stall there instead")
        if action == "sdc":
            if arg not in (None, "bitflip", "scale"):
                raise MXNetError(
                    f"fault plan: sdc mode {arg!r} unknown — use "
                    "sdc:bitflip (loud) or sdc:scale (silent)")
            if not site.startswith("guard."):
                # only the mxguard taps consume the sdc token — at any
                # other site it would count a fault that did nothing
                raise MXNetError(
                    "fault plan: the sdc action only applies to the "
                    f"mxguard tap sites 'guard.*' (got {site!r})")
        self.site = site
        self.nth = nth
        self.prob = prob
        self.step = step
        self.step_from = bool(step_from)
        self.action = action
        self.arg = arg
        self.fired = 0
        # deterministic per-clause stream: seed ^ crc32(site) — stable
        # across processes and python hash randomization
        self._rng = random.Random(seed ^ zlib.crc32(site.encode()))

    def matches(self, invocation: int, step: Optional[int]) -> bool:
        if self.step is not None:
            if step is None:
                return False
            return step >= self.step if self.step_from \
                else step == self.step
        if self.nth is not None:
            return invocation == self.nth
        if self.prob is not None:
            return self._rng.random() < self.prob
        return True

    def describe(self) -> Dict[str, object]:
        sel = self.site
        if self.nth is not None:
            sel += f"@{self.nth}"
        elif self.prob is not None:
            sel += f"%{self.prob}"
        elif self.step is not None:
            sel += f":{self.step}" + ("+" if self.step_from else "")
        act = self.action + (f":{self.arg}" if self.arg else "")
        return {"selector": sel, "action": act, "fired": self.fired}


def parse_plan(spec: str, seed: int = 0) -> List[Clause]:
    clauses = []
    for raw in spec.split(";"):
        raw = raw.strip()
        if not raw:
            continue
        m = _CLAUSE_RE.match(raw)
        if m is None:
            raise MXNetError(
                f"fault plan: cannot parse clause {raw!r} — expected "
                "site[@K|%P|:STEP]=action[:arg]")
        d = m.groupdict()
        clauses.append(Clause(
            d["site"], d["action"], d["arg"],
            nth=int(d["nth"]) if d["nth"] else None,
            prob=float(d["prob"]) if d["prob"] else None,
            step=int(d["step"]) if d["step"] else None,
            step_from=bool(d["step_from"]),
            seed=seed))
    return clauses


class FaultPlan:
    """A parsed plan: per-site invocation counters + clause matching.

    Thread-safe — injection sites run on dispatcher/prefetch/checkpoint
    threads concurrently."""

    def __init__(self, spec: str, seed: int = 0):
        self.spec = spec
        self.seed = seed
        self.clauses = parse_plan(spec, seed)
        self._invocations: Dict[str, int] = {}
        self._lock = threading.Lock()

    def inject(self, site: str, step: Optional[int] = None,
               count: bool = True,
               thread_mode: bool = False) -> Optional[str]:
        """Evaluate the plan at ``site``; applies the matched action.

        Returns ``"nan"`` for the nan action (the caller poisons its
        loss), None otherwise. ``count=False`` re-evaluates without
        advancing the invocation counter (unused today; drills rely on
        every attempt counting so ``@K`` clauses clear on retry).

        ``thread_mode=True`` scopes process-level actions to the
        calling worker THREAD: ``kill``/``preempt`` raise the typed
        :class:`WorkerKilled` / :class:`WorkerPreempted` instead of
        signaling the whole process — the in-process elastic drills
        (``tools/mxresil.py elastic``) run N
        workers in one process and must kill exactly one
        (``elastic.worker.<id>`` sites, docs/resilience.md)."""
        with self._lock:
            inv = self._invocations.get(site, 0) + (1 if count else 0)
            if count:
                self._invocations[site] = inv
            hit = None
            for c in self.clauses:
                if c.site == site and c.matches(inv, step):
                    hit = c
                    c.fired += 1
                    break
        if hit is None:
            return None
        from ..telemetry import metrics as _metrics
        _metrics.counter("mxresil_injected_faults_total",
                         "faults injected by the active fault plan").inc()
        if hit.action == "stall":
            time.sleep(hit.stall_s)
            return None
        if hit.action == "raise":
            name = hit.arg or "FaultInjectedError"
            raise FaultInjectedError(
                f"injected fault at {site} (invocation {inv}"
                + (f", step {step}" if step is not None else "")
                + f"): {name}")
        if hit.action == "preempt":
            if thread_mode:
                raise WorkerPreempted(
                    f"injected preemption notice at {site} "
                    f"(invocation {inv}"
                    + (f", step {step}" if step is not None else "")
                    + ") — leave the group and exit")
            os.kill(os.getpid(), signal.SIGTERM)
            return None
        if hit.action == "kill":
            if thread_mode:
                raise WorkerKilled(
                    f"injected kill at {site} (invocation {inv}"
                    + (f", step {step}" if step is not None else "")
                    + ") — die without cleanup")
            os.kill(os.getpid(), signal.SIGKILL)
            return None  # unreachable
        if hit.action == "kill9":
            # process-scope by definition (the pod host drills): no
            # thread-mode downgrade — the whole host process dies
            os.kill(os.getpid(), signal.SIGKILL)
            return None  # unreachable
        if hit.action == "sdc":
            return "sdc:" + (hit.arg or "bitflip")
        return "nan"

    def report(self) -> Dict[str, object]:
        with self._lock:
            return {"spec": self.spec, "seed": self.seed,
                    "clauses": [c.describe() for c in self.clauses],
                    "invocations": dict(self._invocations)}


# -- the process-wide active plan -------------------------------------------
# cache keyed on the spec STRING so set_flag()/env changes re-parse but
# the per-clause counters survive across inject() calls of one plan
_ACTIVE: Optional[FaultPlan] = None
_ACTIVE_KEY: Optional[str] = None
_ACTIVE_LOCK = threading.Lock()


def _spec() -> str:
    from .. import config
    return config.get("MXRESIL_FAULT_PLAN") or ""


def is_active() -> bool:
    return bool(_spec())


def active_plan() -> Optional[FaultPlan]:
    """The plan parsed from ``MXRESIL_FAULT_PLAN`` (None when unset)."""
    global _ACTIVE, _ACTIVE_KEY
    spec = _spec()
    if not spec:
        if _ACTIVE is not None:
            with _ACTIVE_LOCK:
                _ACTIVE, _ACTIVE_KEY = None, None
        return None
    if spec != _ACTIVE_KEY:
        with _ACTIVE_LOCK:
            if spec != _ACTIVE_KEY:  # double-checked: parse once
                from .. import config
                _ACTIVE = FaultPlan(spec, int(config.get("MXRESIL_SEED")))
                _ACTIVE_KEY = spec
    return _ACTIVE


def inject(site: str, step: Optional[int] = None,
           thread_mode: bool = False) -> Optional[str]:
    """The hook every wired call site runs. No-op (and no allocation)
    when no fault plan is set."""
    plan = active_plan()
    if plan is None:
        return None
    return plan.inject(site, step=step, thread_mode=thread_mode)


def reset() -> None:
    """Drop the cached plan (tests): counters and RNG streams restart."""
    global _ACTIVE, _ACTIVE_KEY
    with _ACTIVE_LOCK:
        _ACTIVE, _ACTIVE_KEY = None, None
