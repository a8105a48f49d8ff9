"""Composable retry/timeout policies for transient-failure call sites.

The reference absorbed transient distributed failures inside ps-lite
(van-level resend + timeouts); with collectives and a thin async PS
there is no server to hide behind, so the *client* call sites (kvstore
push/pull, checkpoint I/O, serving submit) are wrapped in explicit,
inspectable policies:

- :class:`BackoffSchedule` — jittered exponential backoff. The jitter
  RNG is per-instance and seedable, and the clock/sleep functions are
  injectable, so tests verify whole schedules with a fake clock and
  zero real sleeping.
- :class:`RetryBudget` — an adaptive token bucket (the gRPC retry-
  throttling shape): each retry spends a token, each success refunds a
  fraction; when a dependency is hard-down the budget empties and
  retries stop amplifying the outage.
- deadline propagation — :func:`deadline_scope` installs a deadline in
  a ``contextvars`` scope; nested policies and the kvstore transport
  derive their per-attempt timeouts from :func:`remaining_deadline`
  instead of stacking independent worst-case timeouts.
- :class:`CircuitBreaker` — closed → open after N consecutive failures;
  while open, calls fail fast with :class:`CircuitOpenError` (degraded
  mode) instead of queueing behind a dead dependency; after a cooldown
  one half-open probe decides reset vs re-trip.
- :class:`RetryPolicy` — ties the above together as a callable wrapper /
  decorator. Only :class:`RetryableError` subclasses are retried by
  default: a typed transient error is an API contract, not a guess.

Every retry/giveup/trip is counted in the telemetry metrics registry
(``mxresil_*``) — tests/test_resilience.py holds that a clean run
records ZERO retries, so the wrappers are free when nothing fails.
"""
from __future__ import annotations

import contextlib
import contextvars
import random
import threading
import time
from typing import Callable, Optional, Tuple, Type

from ..base import MXNetError

__all__ = ["RetryableError", "CircuitOpenError", "RetryBudgetExhausted",
           "BackoffSchedule", "RetryBudget", "CircuitBreaker",
           "RetryPolicy", "deadline_scope", "remaining_deadline"]


class RetryableError(MXNetError):
    """Base class for transient failures a policy may safely retry.

    Raisers guarantee the failed attempt had no partial effect (or an
    idempotent one) — that is what makes blanket retry sound."""


class CircuitOpenError(MXNetError):
    """Fail-fast rejection while a circuit breaker is open (degraded
    mode). NOT retryable: the breaker exists to stop retry pressure."""


class RetryBudgetExhausted(MXNetError):
    """The shared retry budget is empty — the dependency looks
    hard-down and further retries would amplify the outage."""


# -- deadline propagation ---------------------------------------------------

_DEADLINE: contextvars.ContextVar[Optional[float]] = \
    contextvars.ContextVar("mxresil_deadline", default=None)


@contextlib.contextmanager
def deadline_scope(timeout_s: float, clock: Callable[[], float] = None):
    """``with deadline_scope(0.5): ...`` — everything inside (including
    nested scopes, which can only shrink the deadline) sees it via
    :func:`remaining_deadline`."""
    clock = clock or time.monotonic
    new = clock() + float(timeout_s)
    cur = _DEADLINE.get()
    token = _DEADLINE.set(min(cur, new) if cur is not None else new)
    try:
        yield
    finally:
        _DEADLINE.reset(token)


def remaining_deadline(clock: Callable[[], float] = None) -> Optional[float]:
    """Seconds left in the innermost deadline scope; None when no scope
    is active. Can be negative (deadline already passed)."""
    d = _DEADLINE.get()
    if d is None:
        return None
    return d - (clock or time.monotonic)()


# -- backoff ----------------------------------------------------------------

class BackoffSchedule:
    """Jittered exponential backoff: ``delay(k)`` for retry number k
    (0-based) is ``min(base * multiplier^k, max) * U[1-jitter, 1]``.

    Decorrelated-enough for a fleet (full-range jitter below the cap)
    while deterministic under a fixed ``seed`` — fault drills replay
    identical schedules."""

    def __init__(self, base_ms: Optional[float] = None,
                 max_ms: Optional[float] = None, multiplier: float = 2.0,
                 jitter: float = 0.5, seed: Optional[int] = None):
        from .. import config
        self.base_s = float(base_ms if base_ms is not None
                            else config.get("MXRESIL_RETRY_BASE_MS")) / 1e3
        self.max_s = float(max_ms if max_ms is not None
                           else config.get("MXRESIL_RETRY_MAX_MS")) / 1e3
        self.multiplier = float(multiplier)
        if not 0.0 <= jitter <= 1.0:
            raise MXNetError("jitter must be in [0, 1]")
        self.jitter = float(jitter)
        self._rng = random.Random(seed)

    def delay(self, retry: int) -> float:
        raw = min(self.base_s * (self.multiplier ** retry), self.max_s)
        if not self.jitter:
            return raw
        return raw * (1.0 - self.jitter * self._rng.random())


# -- retry budget -----------------------------------------------------------

class RetryBudget:
    """Token bucket shared across a site's callers: a retry spends 1.0,
    a first-try success refunds ``refund`` (capped at ``capacity``)."""

    def __init__(self, capacity: float = 10.0, refund: float = 0.1):
        self.capacity = float(capacity)
        self.refund = float(refund)
        self._tokens = float(capacity)
        self._lock = threading.Lock()

    @property
    def tokens(self) -> float:
        return self._tokens

    def try_spend(self) -> bool:
        with self._lock:
            if self._tokens < 1.0:
                return False
            self._tokens -= 1.0
            return True

    def credit(self):
        with self._lock:
            self._tokens = min(self.capacity, self._tokens + self.refund)


# -- circuit breaker --------------------------------------------------------

class CircuitBreaker:
    """closed → (N consecutive failures) → open → (cooldown) →
    half-open → one probe → closed | open.

    ``check()`` raises :class:`CircuitOpenError` while open; callers
    report outcomes via ``record_success``/``record_failure``. The
    injectable ``clock`` makes trip/reset fully testable without
    sleeping."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, name: str = "", failure_threshold: int = None,
                 cooldown_s: float = None,
                 clock: Callable[[], float] = time.monotonic):
        from .. import config
        from ..telemetry import metrics as _metrics
        self.name = name or "breaker"
        self.failure_threshold = int(
            failure_threshold if failure_threshold is not None
            else config.get("MXRESIL_BREAKER_FAILURES"))
        self.cooldown_s = float(
            cooldown_s if cooldown_s is not None
            else config.get("MXRESIL_BREAKER_COOLDOWN_S"))
        self._clock = clock
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probing = False
        self._probe_started = 0.0
        self._m_trips = _metrics.counter(
            "mxresil_breaker_trips_total", "circuit-breaker open events")
        self._m_fastfail = _metrics.counter(
            "mxresil_breaker_fastfail_total",
            "calls rejected while a breaker was open")

    @property
    def state(self) -> str:
        with self._lock:
            self._maybe_half_open()
            return self._state

    def _maybe_half_open(self):
        # under self._lock
        if self._state == self.OPEN and \
                self._clock() - self._opened_at >= self.cooldown_s:
            self._state = self.HALF_OPEN
            self._probing = False

    def check(self):
        """Admission control: raise while open; in half-open admit ONE
        probe and fail the rest fast. A probe whose outcome is never
        recorded (caller died, async future abandoned) expires after
        another cooldown so the breaker can never wedge half-open."""
        with self._lock:
            self._maybe_half_open()
            if self._state == self.CLOSED:
                return
            if self._state == self.HALF_OPEN and self._probing and \
                    self._clock() - self._probe_started >= self.cooldown_s:
                self._probing = False  # stuck probe: release the slot
            if self._state == self.HALF_OPEN and not self._probing:
                self._probing = True
                self._probe_started = self._clock()
                return
            self._m_fastfail.inc()
            left = max(0.0, self.cooldown_s
                       - (self._clock() - self._opened_at))
            raise CircuitOpenError(
                f"circuit {self.name!r} is {self._state} "
                f"({self._failures} consecutive failures; "
                f"~{left:.1f}s until half-open probe) — degraded mode, "
                "failing fast")

    def record_success(self):
        with self._lock:
            if self._state == self.OPEN:
                # a straggler admitted BEFORE the trip: one late success
                # must not cancel the cooldown — only the half-open
                # probe may close an opened breaker
                return
            self._failures = 0
            self._probing = False
            self._state = self.CLOSED

    def record_failure(self):
        with self._lock:
            self._failures += 1
            tripped = self._state == self.HALF_OPEN or \
                self._failures >= self.failure_threshold
            fresh_trip = tripped and self._state != self.OPEN
            if fresh_trip:
                self._state = self.OPEN
                self._opened_at = self._clock()
                self._probing = False
                self._m_trips.inc()
            elif tripped:  # re-trip from half-open probe failure
                self._opened_at = self._clock()
        if fresh_trip:
            # freeze the last-N-spans picture at the moment the
            # breaker opened: the dump's final spans show what the
            # replica was doing when it started failing
            # (trace/recorder.py; rate-limited per reason)
            from ..trace import crash_dump
            crash_dump("breaker_trip", site=self.name,
                       extra={"consecutive_failures": self._failures})

    def describe(self) -> dict:
        with self._lock:
            self._maybe_half_open()
            return {"name": self.name, "state": self._state,
                    "consecutive_failures": self._failures,
                    "failure_threshold": self.failure_threshold,
                    "cooldown_s": self.cooldown_s}


# -- the composed policy ----------------------------------------------------

class RetryPolicy:
    """Retry a callable on :class:`RetryableError` with jittered
    exponential backoff, bounded by max retries, the shared budget, the
    ambient deadline, and an optional circuit breaker.

    ``clock``/``sleep`` are injectable for fake-clock tests. Use as a
    wrapper (``policy.call(fn, *a)``) or decorator (``@policy``)."""

    def __init__(self, name: str = "", max_retries: Optional[int] = None,
                 backoff: Optional[BackoffSchedule] = None,
                 retry_on: Tuple[Type[BaseException], ...] =
                 (RetryableError,),
                 no_retry: Tuple[Type[BaseException], ...] = (),
                 budget: Optional[RetryBudget] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        from .. import config
        from ..telemetry import metrics as _metrics
        self.name = name or "retry"
        self.max_retries = int(max_retries if max_retries is not None
                               else config.get("MXRESIL_RETRY_MAX"))
        self.backoff = backoff or BackoffSchedule()
        self.retry_on = retry_on
        # ``no_retry`` fences specific RetryableError subtypes OUT of
        # blind retry: elastic MembershipChanged is retryable by
        # CONTRACT (no partial effect) but re-issuing under a stale
        # generation can never succeed — the caller's rebuild is the
        # retry, so the policy re-raises it immediately instead of
        # burning backoff (mxnet_tpu/elastic/, docs/resilience.md)
        self.no_retry = tuple(no_retry)
        self.budget = budget
        self.breaker = breaker
        self._clock = clock
        self._sleep = sleep
        self._m_retries = _metrics.counter(
            "mxresil_retries_total",
            "retry attempts across all resil policies")
        self._m_giveups = _metrics.counter(
            "mxresil_giveups_total",
            "calls that exhausted retries/budget/deadline")

    def call(self, fn: Callable, *args, **kwargs):
        if self.breaker is not None:
            self.breaker.check()
        retry = 0
        while True:
            try:
                result = fn(*args, **kwargs)
            except self.retry_on as e:
                if self.no_retry and isinstance(e, self.no_retry):
                    raise  # typed fence: the caller's rebuild retries
                reason = None
                if retry >= self.max_retries:
                    reason = f"retries exhausted ({self.max_retries})"
                elif self.budget is not None and not self.budget.try_spend():
                    reason = "retry budget exhausted"
                delay = self.backoff.delay(retry) if reason is None else 0.0
                left = remaining_deadline(self._clock)
                if reason is None and left is not None and delay >= left:
                    reason = f"deadline exceeded ({left:.3f}s left)"
                if reason is not None:
                    self._m_giveups.inc()
                    if self.breaker is not None:
                        self.breaker.record_failure()
                    raise type(e)(
                        f"{self.name}: {reason}; last error: {e}") from e
                self._m_retries.inc()
                if delay > 0:
                    self._sleep(delay)
                retry += 1
                continue
            except BaseException:
                if self.breaker is not None:
                    self.breaker.record_failure()
                raise
            if self.budget is not None and retry == 0:
                self.budget.credit()
            if self.breaker is not None:
                self.breaker.record_success()
            return result

    def __call__(self, fn: Callable) -> Callable:
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            return self.call(fn, *args, **kwargs)

        wrapped.retry_policy = self
        return wrapped
