"""Set-up as the program sees it: the programs jax builds, and the eager
work around them.

Two sources feed one set of registry instruments and, with ``MXTRACE``
on, spans:

- **jax's program-building events.** jax reports every program it
  builds through ``jax.monitoring``: the jaxpr trace, the lowering to
  MLIR and the backend compile, each as a start (a scalar event) and a
  time span, and a compile that was a read of the persistent cache
  reports the read's seconds inside its own interval.
  :func:`on_start`, :func:`on_time_span` and :func:`on_duration` are the
  listeners (``step/cache.py`` registers them at import). Each event is
  a retroactive span ``jax.trace`` / ``jax.lower`` / ``jax.compile``
  (attribute ``fun_name``; a compile also ``source``, ``cache`` or
  ``compiler``, and on a cache read ``retrieval_s``) under the ambient
  span and in its subsystem, on the spans' ``perf_counter_ns`` clock:
  the end is the listener's own clock reading, the start the end less
  jax's duration.
  An event raised while another is open (a jit traced while an outer
  function is traced, a constant folded while one is lowered) is that
  one's child span and counts in no sum: only outermost events do.
- **Scopes of the program's set-up**, a contextvar each:
  :func:`step_program` (``StepFunction`` sets it around the one dispatch
  that builds its program: the outermost events there are the spans
  ``step.trace`` / ``step.lower`` / ``step.compile`` and feed the
  histograms ``step_program_{trace,lower,compile}_seconds``, a cache
  read also ``step_program_cache_retrieval_seconds``); :func:`aot`
  (the span ``step.aot`` round ``StepFunction.compiled``, whose builds
  feed nothing); :func:`eager` (``block.initialize``,
  ``block.forward``, ``step.resolve``, ``operator_tune.measure``: the
  outermost one's wall time feeds ``setup_eager_seconds``, the
  programs built under any of them ``eager_program_build_seconds`` and
  ``eager_programs_built_total``).

Events raised under no scope of the program (a plain ``jax.jit`` of a
caller's own) feed no instrument; with an ambient span they are still
its ``jax.*`` children. The registry is fed whether ``MXTRACE`` is on
or off. Nothing here runs while no program is built: a step that hits
its signature cache meets none of it.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time

from jax.core import Tracer

from . import spans as _spans

__all__ = ["step_program", "aot", "eager", "block_forward", "on_start",
           "on_time_span", "on_duration"]

# jax's event name -> the phase it times (jax/_src/dispatch.py)
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_STEP_DOCS = {
    "trace": "jaxpr trace of the fused step's forward, backward and "
             "update, a signature-cache miss",
    "lower": "lowering of the fused step's jaxpr to MLIR, a miss",
    "compile": "XLA compile of the fused step, or its read from the "
               "persistent cache, a miss",
}


class _Scope:
    """What the events under a scope feed: ``kind`` is ``step``,
    ``aot`` or ``eager``; ``block`` says a ``block.*`` span is open at
    or above it."""

    __slots__ = ("kind", "block")

    def __init__(self, kind, block=False):
        self.kind = kind
        self.block = block


_SCOPE = contextvars.ContextVar("mxtrace_setup_scope", default=None)
_STEP = _Scope("step")
_AOT = _Scope("aot")
_LOCAL = threading.local()  # .open: this thread's events in flight


class _Event:
    __slots__ = ("phase", "fun_name", "source", "retrieval_s", "children",
                 "t0_ns", "t1_ns")

    def __init__(self, phase, fun_name):
        self.phase = phase
        self.fun_name = fun_name
        self.source = "compiler"
        self.retrieval_s = None
        self.children = []


def _open():
    stack = getattr(_LOCAL, "open", None)
    if stack is None:
        stack = _LOCAL.open = []
    return stack


def on_start(event, value, fun_name="", **_):
    """Scalar listener: jax records the start of a trace, a lowering or
    a compile as a scalar event of the same name."""
    phase = PHASES.get(event)
    if phase is not None:
        _open().append(_Event(phase, str(fun_name)))


def on_duration(event, seconds, **_):
    """Duration listener: a cache read is reported inside the compile
    that it replaces."""
    if event != RETRIEVAL_EVENT:
        return
    stack = _open()
    if stack and stack[-1].phase == "compile":
        stack[-1].source = "cache"
        stack[-1].retrieval_s = float(seconds)


def on_time_span(event, start, end, fun_name="", **_):
    """Time-span listener: the event's end. Nested events wait in their
    parent; an outermost one feeds its scope's instruments and is
    emitted, with its children, under the ambient span."""
    phase = PHASES.get(event)
    if phase is None:
        return
    t1 = time.perf_counter_ns()
    stack = _open()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i].phase == phase:
            ev = stack[i]
            del stack[i:]  # and any start whose end never came
            break
    else:  # the listeners were registered while this event was open
        ev = _Event(phase, str(fun_name))
    ev.t1_ns = t1
    ev.t0_ns = t1 - int((end - start) * 1e9)
    if stack:
        if _spans.enabled():
            stack[-1].children.append(ev)
        return
    scope = _SCOPE.get()
    if scope is not None:
        _observe(scope.kind, ev, end - start)
    parent = _spans.current_context()
    if parent is not None:
        step = scope is not None and scope.kind == "step"
        _emit(ev, parent, "step" if step else "jax")


def _observe(kind, ev, seconds):
    from ..telemetry import metrics as _metrics
    if kind == "step":
        _metrics.histogram(f"step_program_{ev.phase}_seconds",
                           _STEP_DOCS[ev.phase]).observe(seconds)
        if ev.retrieval_s is not None:
            _metrics.histogram(
                "step_program_cache_retrieval_seconds",
                "persistent-cache reads of the fused step's program, "
                "inside step_program_compile_seconds").observe(
                ev.retrieval_s)
    elif kind == "eager":
        _metrics.histogram(
            "eager_program_build_seconds",
            "outermost trace, lowering and compile of the programs "
            "built under the program's eager set-up").observe(seconds)
        if ev.phase == "compile":
            _metrics.counter(
                "eager_programs_built_total",
                "programs compiled or read from the cache under the "
                "program's eager set-up").inc()


def _emit(ev, parent, prefix):
    """Record ``ev`` and its nested events in the parent's subsystem,
    children first: a flight-recorder ring then drops a parent no
    earlier than its children, as it does live spans."""
    attrs = {"fun_name": ev.fun_name}
    if ev.phase == "compile":
        attrs["source"] = ev.source
        if ev.retrieval_s is not None:
            attrs["retrieval_s"] = ev.retrieval_s
    me = _spans.SpanContext(parent.trace_id, _spans._new_span_id(), True,
                            subsystem=parent.subsystem)
    for child in ev.children:
        _emit(child, me, "jax")
    _spans.emit(f"{prefix}.{ev.phase}", parent.subsystem, ev.t0_ns,
                ev.t1_ns, parent=parent, attrs=attrs, span_id=me.span_id)


@contextlib.contextmanager
def _scoped(scope):
    token = _SCOPE.set(scope)
    try:
        yield
    finally:
        _SCOPE.reset(token)


def step_program():
    """Round the dispatch of a step whose signature just missed: the
    outermost builds inside are the step program's."""
    return _scoped(_STEP)


@contextlib.contextmanager
def aot():
    """Round ``StepFunction.compiled``: the span ``step.aot``; the
    programs built inside feed no instrument."""
    with _spans.span("step.aot", "train"), _scoped(_AOT):
        yield


@contextlib.contextmanager
def eager(name, **attrs):
    """A span of the program's eager set-up. The outermost such span
    feeds its wall time to ``setup_eager_seconds``; one opened under the
    step program's build (or its AOT compile) keeps that scope."""
    parent = _SCOPE.get()
    block = name.startswith("block.") or (parent is not None
                                         and parent.block)
    scope = _Scope(parent.kind if parent is not None else "eager", block)
    t0 = time.perf_counter()
    try:
        with _spans.span(name, "setup", **attrs) as sp, _scoped(scope):
            yield sp
    finally:
        if parent is None:
            from ..telemetry import metrics as _metrics
            _metrics.histogram(
                "setup_eager_seconds",
                "wall time of the program's outermost eager set-up "
                "spans (block.initialize, block.forward, step.resolve, "
                "operator_tune.measure)").observe(
                time.perf_counter() - t0)


_NO_SPAN = contextlib.nullcontext()


def _traced(a):
    return isinstance(getattr(a, "_data", a), Tracer)


def block_forward(block, args):
    """The scope of a ``Block.__call__``: ``block.forward`` where the
    call is an outermost eager one (no ``block.*`` span open above it,
    no argument being traced), nothing otherwise."""
    scope = _SCOPE.get()
    if (scope is not None and scope.block) or any(map(_traced, args)):
        return _NO_SPAN
    return eager("block.forward", block=block.name)
