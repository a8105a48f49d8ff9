"""Correlated span model: trace_id/span_id/parent over monotonic clocks.

One process-wide tracer for BOTH hot paths (docs/observability.md):

- a **span** is one timed operation (an endpoint request, a router
  pick, a scheduler admission, a fused-step dispatch, a bucket
  exchange, a guard vote) with a ``trace_id`` shared by everything the
  same logical unit of work touched, a unique ``span_id``, and a
  ``parent_id`` linking it into the tree ``tools/mxprof.py trace``
  reconstructs;
- propagation is **contextvar-based** on one thread (nested ``span()``
  blocks parent automatically) and **explicit** across threads: the
  serving scheduler stores :func:`current_context` on each submitted
  sequence and emits that sequence's phase spans with the stored
  parent (``emit`` / ``under``), so a request's spans land in ONE
  trace even though submit and decode run on different threads;
- clocks are ``time.perf_counter_ns()`` (monotonic — durations and
  orderings are exact within the process); one wall-clock anchor pair
  taken at import converts to absolute time for exports;
- completed spans land in **bounded per-thread buffers** (drained by
  exporters/tests), the flight-recorder rings
  (:mod:`~mxnet_tpu.trace.recorder`), and — when ``MXTRACE_EXPORT``
  names a file — one JSON line per span;
- while anyone takes a **profile** (``jax.profiler.trace``,
  ``mx.profiler``, a benchmark's traced run) a recorded ``span()`` is
  also a ``jax.profiler.TraceAnnotation`` of the same name (a root
  with a ``step`` attribute a ``StepTraceAnnotation``), so the
  program's spans are events of the profile's ``/host:CPU`` plane, on
  the clock of the device's ``XLA Ops`` and with no conversion.
  Retroactive ``emit()`` spans have no live interval and stay out;
- a span opened with ``cpu=True``, and every span under it, also
  records the thread's CPU time over its interval (attribute
  ``cpu_ns``): wall less ``cpu_ns`` is the time the thread was parked
  (inside the runtime, or off the core).

Cost model: tracing is ON by default (``MXTRACE``) because a span is
two clock reads, one small dict, a deque append and, with no profile
being taken, one check that none is. Measured on one TPU v5e (PERF.md
section 5, PR 25): a fused train step's ``step_ms`` with ``MXTRACE=1``
against ``MXTRACE=0`` read +0.2% (ResNet-50, 252 ms) and -0.9%
(BERT-base, 221 ms) over six runs a side on the same seeds, inside the
runs' own spread of 0.6-2.6%: not resolvable.
``MXTRACE_SAMPLE`` drops whole traces (the decision is made once at
the root and inherited), so high-QPS serving can run at 0.1 sampling
and still pay ~nothing on the untraced requests. Nothing here touches
jit cache keys: tracing can never cause a recompile.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..san.runtime import make_lock

__all__ = ["Span", "SpanContext", "enabled", "span", "emit",
           "emit_root", "under", "current_context", "drain", "reset",
           "wall_of_ns"]

# wall-clock anchor: perf_counter_ns <-> epoch seconds, taken once so
# every exported span converts consistently
_ANCHOR_NS = time.perf_counter_ns()
_ANCHOR_WALL = time.time()

_PID = os.getpid()
_IDS = itertools.count(1)
# sampling decisions only — per-root, and a torn read under free
# threading would just skew one sample, so no lock
_RNG = random.Random()

# (config generation, MXTRACE, MXTRACE_SAMPLE) — refreshed when a
# set_flag/unset_flag bumps the config generation; the hot-path check
# is two attribute reads and an int compare
_FLAG_CACHE = (-1, True, 1.0)
_BUF_LOCK = make_lock("trace.spans.buf")
_BUFFERS: Dict[int, deque] = {}   # thread ident -> finished-span deque
_LOCAL = threading.local()


def wall_of_ns(t_ns: int) -> float:
    """Epoch seconds for a perf_counter_ns stamp (export rendering)."""
    return _ANCHOR_WALL + (t_ns - _ANCHOR_NS) / 1e9


# the config module ref is cached after first use: a per-span
# `from .. import config` costs ~1.5us in importlib machinery
_CONFIG = []


def _cfg():
    if not _CONFIG:
        from .. import config
        _CONFIG.append(config)
    return _CONFIG[0]


def _flags():
    global _FLAG_CACHE
    config = _cfg()
    gen = config.generation()
    cached = _FLAG_CACHE
    if cached[0] == gen:
        return cached
    on = bool(config.get("MXTRACE"))
    sample = float(config.get("MXTRACE_SAMPLE"))
    _FLAG_CACHE = (gen, on, sample)
    return _FLAG_CACHE


def enabled() -> bool:
    return _flags()[1]


# trace ids only need process-lifetime uniqueness plus a cross-process
# discriminator (the pid) — a counter beats a locked RNG on the hot
# path; one random session prefix keeps ids distinct across restarts
# sharing an export file
_TIDS = itertools.count(1)
_SESSION = f"{random.SystemRandom().getrandbits(24):06x}"


def _new_trace_id() -> str:
    return f"{_SESSION}{_PID:x}t{next(_TIDS)}"


def _new_span_id() -> str:
    return f"{_PID:x}.{next(_IDS)}"


class SpanContext:
    """The propagated identity of an in-flight span: enough to parent
    a child from another thread. ``sampled=False`` contexts still
    propagate (children inherit the drop decision), and so does
    ``cpu`` (children of a span that counts its thread's CPU time
    count theirs). ``subsystem`` is the span's own, for a retroactive
    child that belongs in the same flight-recorder ring."""

    __slots__ = ("trace_id", "span_id", "sampled", "cpu", "subsystem")

    def __init__(self, trace_id: str, span_id: str, sampled: bool,
                 cpu: bool = False, subsystem: str = "app"):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled
        self.cpu = cpu
        self.subsystem = subsystem

    def __repr__(self):
        return (f"SpanContext({self.trace_id}, {self.span_id}, "
                f"sampled={self.sampled})")


_CURRENT = contextvars.ContextVar("mxtrace_ctx", default=None)


class Span:
    """One finished-or-open span. Mutate attributes via :meth:`set`;
    the dict form (:meth:`to_dict`) is the export/recorder unit."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name",
                 "subsystem", "t0_ns", "t1_ns", "attrs", "thread",
                 "status", "sampled", "cpu")

    def __init__(self, name: str, subsystem: str, trace_id: str,
                 span_id: str, parent_id: Optional[str],
                 t0_ns: Optional[int] = None, sampled: bool = True,
                 cpu: bool = False):
        self.name = name
        self.subsystem = subsystem
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_ns = time.perf_counter_ns() if t0_ns is None else t0_ns
        self.t1_ns = None
        self.attrs: Dict[str, object] = {}
        self.thread = threading.get_ident()
        self.status = "ok"
        self.sampled = sampled
        self.cpu = cpu

    def set(self, **attrs) -> "Span":
        """Attach typed attributes (JSON-serializable values)."""
        self.attrs.update(attrs)
        return self

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self.sampled,
                           self.cpu, self.subsystem)

    @property
    def duration_s(self) -> Optional[float]:
        if self.t1_ns is None:
            return None
        return (self.t1_ns - self.t0_ns) / 1e9

    def to_dict(self) -> Dict[str, object]:
        # no rounding here: this runs on the hot path for every
        # finished span; exporters own presentation precision
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "subsystem": self.subsystem,
            "ts_us": self.t0_ns / 1e3,
            "dur_us": ((self.t1_ns - self.t0_ns) / 1e3
                       if self.t1_ns is not None else None),
            "wall": wall_of_ns(self.t0_ns),
            "thread": self.thread,
            "status": self.status,
            "attrs": self.attrs,
        }

    def __repr__(self):
        dur = self.duration_s
        return (f"<Span {self.name} [{self.subsystem}] "
                f"{self.trace_id}/{self.span_id}"
                + (f" {dur * 1e3:.3f}ms" if dur is not None else "")
                + (f" {self.status}" if self.status != "ok" else "")
                + ">")


class _NullSpan:
    """Shared no-op span: returned when tracing is off (or a trace is
    unsampled) so call sites never branch on enablement."""

    __slots__ = ()
    trace_id = ""
    span_id = ""
    parent_id = None
    sampled = False
    status = "ok"

    def set(self, **attrs):
        return self

    def context(self):
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def _buffer() -> deque:
    buf = getattr(_LOCAL, "buf", None)
    if buf is None:
        config = _cfg()
        cap = max(16, int(config.get("MXTRACE_BUFFER_SPANS")))
        buf = deque(maxlen=cap)
        _LOCAL.buf = buf
        ident = threading.get_ident()
        with _BUF_LOCK:
            _BUFFERS[ident] = buf
            if len(_BUFFERS) > 128:
                # sweep buffers of dead threads (HTTP handler threads
                # come and go; their spans already reached the
                # recorder/export sink)
                live = {t.ident for t in threading.enumerate()}
                for dead in [i for i in _BUFFERS if i not in live]:
                    _BUFFERS.pop(dead, None)
    return buf


# resolved once at first record: per-span `from . import ...` lookups
# are measurable on the hot path
_SINKS = []


def _record(sp: Span):
    # buffers and the recorder hold Span OBJECTS (finished, never
    # mutated again); dict conversion is deferred to drain()/dump()
    # readers, off the hot path. Only an active MXTRACE_EXPORT sink
    # pays the dict+json cost per span.
    _buffer().append(sp)
    if not _SINKS:
        from . import export as _export
        from . import recorder as _recorder
        _SINKS.append((_recorder.get_recorder().add,
                       _export.sink_write_span,
                       _recorder._SIGTERM_INSTALLED,
                       _recorder.install_signal_handler))
    add, sink, sig_installed, sig_install = _SINKS[0]
    if not sig_installed[0]:
        # the documented SIGTERM dump trigger self-wires with the
        # first traced work; retried until a MAIN-thread span records
        # (signal handlers can only install there)
        sig_install()
    add(sp)
    sink(sp)


def drain() -> List[dict]:
    """Collect and clear every thread's finished-span buffer (tests,
    ad-hoc exporters). The flight-recorder rings are untouched.

    Pop-based on purpose: other threads keep APPENDING to their own
    deques without this lock (deque append/popleft are atomic), so
    iterating a live deque would raise 'mutated during iteration' —
    popleft-until-empty is safe against concurrent appends."""
    out: List[dict] = []
    with _BUF_LOCK:
        bufs = list(_BUFFERS.values())
    for buf in bufs:
        while True:
            try:
                out.append(buf.popleft().to_dict())
            except IndexError:
                break
    out.sort(key=lambda d: d["ts_us"])
    return out


def reset():
    """Clear buffers, the flight recorder, and dump rate limits
    (tests)."""
    with _BUF_LOCK:
        for buf in _BUFFERS.values():
            buf.clear()
    from . import recorder as _recorder
    _recorder.get_recorder().reset()
    from . import export as _export
    _export.reset_sink()


class _SpanCm:
    """The ``with span(...)`` context manager: opens a child of the
    ambient context (or a new sampled-or-not root), publishes itself
    as the ambient context, and records on exit — error status and
    exception type attached when the block raised. While a profile is
    being taken the block is also an annotation of the profile (a root
    that carries a ``step`` attribute a step annotation, which the
    profiler's own per-step analysis reads); with none, that costs the
    one ``is_enabled`` check."""

    __slots__ = ("span", "_token", "_annot", "_cpu0")

    def __init__(self, sp: Span):
        self.span = sp
        self._token = None
        self._annot = None

    def __enter__(self) -> Span:
        sp = self.span
        self._token = _CURRENT.set(sp.context())
        if TraceAnnotation.is_enabled():
            if sp.parent_id is None and "step" in sp.attrs:
                self._annot = StepTraceAnnotation(
                    sp.name, step_num=sp.attrs["step"])
            else:
                self._annot = TraceAnnotation(sp.name)
            self._annot.__enter__()
        if sp.cpu:
            # read inside the wall interval at both ends, so wall >=
            # cpu up to the kernel's accounting (10 ms ticks on the
            # chip's machine)
            self._cpu0 = time.thread_time_ns()
        return sp

    def __exit__(self, exc_type, exc, tb):
        sp = self.span
        if sp.cpu:
            sp.attrs["cpu_ns"] = time.thread_time_ns() - self._cpu0
        if self._annot is not None:
            self._annot.__exit__(exc_type, exc, tb)
        sp.t1_ns = time.perf_counter_ns()
        if exc_type is not None:
            sp.status = "error"
            sp.attrs.setdefault("error", exc_type.__name__)
            if exc is not None:
                sp.attrs.setdefault("error_msg", str(exc)[:200])
        _CURRENT.reset(self._token)
        if sp.sampled:
            _record(sp)
        return False


class _CtxOnlyCm:
    """Publish a context without recording anything: the unsampled
    branch of :func:`span` (children of a dropped trace inherit the
    drop) and the explicit-scope form :func:`under` share it."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: SpanContext):
        self._ctx = ctx
        self._token = None

    def __enter__(self):
        self._token = _CURRENT.set(self._ctx)
        return _NULL

    def __exit__(self, *exc):
        _CURRENT.reset(self._token)
        return False


def span(name: str, subsystem: str = "app", cpu: bool = False,
         **attrs):
    """``with trace.span("serve.request", "serve", model=m) as sp:`` —
    the one instrumentation primitive. Child of the ambient context;
    a new root (with the ``MXTRACE_SAMPLE`` decision) when there is
    none. ``cpu=True`` also records the thread's CPU time over the
    block as the attribute ``cpu_ns``, here and in every span under
    this one (two ``time.thread_time_ns()`` reads each, which a span
    that does not ask pays nothing of). Returns a no-op span when
    tracing is off."""
    gen, on, sample = _flags()
    if not on:
        return _NULL
    parent = _CURRENT.get()
    if parent is None:
        sampled = sample >= 1.0 or _RNG.random() < sample
        if not sampled:
            return _CtxOnlyCm(SpanContext(_new_trace_id(),
                                          _new_span_id(), False))
        sp = Span(name, subsystem, _new_trace_id(), _new_span_id(),
                  None, sampled=True, cpu=cpu)
    else:
        if not parent.sampled:
            return _CtxOnlyCm(SpanContext(parent.trace_id,
                                          _new_span_id(), False))
        sp = Span(name, subsystem, parent.trace_id, _new_span_id(),
                  parent.span_id, sampled=True,
                  cpu=cpu or parent.cpu)
    if attrs:
        sp.attrs.update(attrs)
    return _SpanCm(sp)


def emit(name: str, subsystem: str, t0_ns: int, t1_ns: int,
         parent: Optional[SpanContext] = None,
         attrs: Optional[dict] = None,
         status: str = "ok",
         span_id: Optional[str] = None) -> Optional[Span]:
    """Record a RETROACTIVE span over an already-measured interval
    under an explicit parent — the cross-thread form (the scheduler's
    queue/decode phases, measured by stamps on the sequence and
    emitted when the phase closes). No parent = no span (internal
    phases never start their own traces). ``span_id``: one taken
    beforehand, so that the span's children can be emitted before it,
    as a live span's are recorded before it closes."""
    if parent is None or not parent.sampled or not enabled():
        return None
    sp = Span(name, subsystem, parent.trace_id, span_id or _new_span_id(),
              parent.span_id, t0_ns=t0_ns, sampled=True)
    sp.t1_ns = t1_ns
    sp.status = status
    if attrs:
        sp.attrs.update(attrs)
    _record(sp)
    return sp


def emit_root(name: str, subsystem: str, t0_ns: int, t1_ns: int,
              trace_id: str, span_id: str,
              attrs: Optional[dict] = None,
              status: str = "ok") -> Optional[Span]:
    """Record a retroactive ROOT span with EXPLICIT identity — the
    cross-process stitching hook (mxnet_tpu/obs/): every rank derives
    the same (trace_id, span_id) from control-plane state, exactly one
    designated rank emits the root, and the others parent their local
    trees under it, so `mxprof trace --dir` reassembles one tree from
    per-rank span files. Per-process ids stay counter-based; only
    deliberately-shared roots take this path."""
    if not enabled():
        return None
    sp = Span(name, subsystem, str(trace_id), str(span_id), None,
              t0_ns=t0_ns, sampled=True)
    sp.t1_ns = t1_ns
    sp.status = status
    if attrs:
        sp.attrs.update(attrs)
    _record(sp)
    return sp


def under(ctx: Optional[SpanContext]):
    """``with trace.under(seq_ctx): ...`` — run a block with an
    explicit ambient context (cross-thread propagation: nested
    ``span()`` calls parent to ``ctx``). With ``ctx=None`` the block
    runs unchanged: spans inside root their own traces, which is what
    a standalone (un-attributed) engine wants."""
    if ctx is None:
        return contextlib.nullcontext(_NULL)
    return _CtxOnlyCm(ctx)


def current_context() -> Optional[SpanContext]:
    """The ambient span context (None outside any span) — what a
    cross-thread submitter stores for later ``emit``/``under``."""
    if not enabled():
        return None
    return _CURRENT.get()
