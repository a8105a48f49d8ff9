"""Op-level tracing: propagate framework op names into jax/XLA traces.

The attribution problem on TPU (ISSUE 2; arXiv:2008.01040, 2301.13062):
XLA fuses and renames, so a raw XProf trace shows ``fusion.123`` and the
user cannot tell which MXNet op it came from. Two mechanisms, each where
the thing it names happens:

- :func:`trace_scope` — an op that runs **under a jax trace** (a fused
  step, a hybridized block, a bound executor) runs under
  :func:`jax.named_scope`, which stamps the op name into the jaxpr/HLO
  metadata: the name survives INTO the compiled program, so a profile
  of it, and the benchmark's per-phase readers, attribute fused kernels
  back to framework ops. Trace time only, whether or not any profiler
  runs; an eager dispatch has no tracer among its inputs and gets the
  shared null context;
- :func:`op_span` / :func:`maybe_instrument` — while the MXNet
  profiler is on, an op's **execution** is a
  :class:`jax.profiler.TraceAnnotation` (a host-side event of the jax
  profiler's timeline) plus a chrome-trace duration event and an
  aggregate-table update in our own profiler, so ``profiler.dump()``
  carries op names too. Gated on profiler state: :func:`active` is a
  couple of attribute reads when the profiler is off, and
  :func:`maybe_instrument` returns the raw function unchanged, so the
  eager hot path pays one predictable branch.

Domains mirror the reference's profiler config: ``imperative`` (eager /
nd dispatch, including under a CachedOp jit trace), ``symbolic``
(executor graph evaluation), ``memory`` (counter samples), ``api``
(user scopes / markers).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Optional

import jax

__all__ = ["active", "maybe_instrument", "op_span", "trace_scope"]

_NO_SCOPE = contextlib.nullcontext()


def trace_scope(name: str, values):
    """``jax.named_scope(name)`` where one of ``values`` (raw arrays) is
    a jax tracer, that is, where the op is being traced into a program;
    the shared null context for an eager dispatch."""
    for v in values:
        if isinstance(v, jax.core.Tracer):
            return jax.named_scope(name)
    return _NO_SCOPE


def active(domain: str = "imperative") -> bool:
    """True when the profiler is running, not paused, and the domain is
    enabled (profile_all overrides per-domain flags)."""
    from .. import profiler as _prof
    return _prof._active() and _prof._domain_enabled(domain)


def op_span(name: str, domain: str = "imperative", node: Optional[str] = None):
    """Context manager tracing one op execution, or a no-op when the
    profiler is off / the domain is filtered out."""
    if not active(domain):
        return _NO_SCOPE
    return _OpSpan(name, domain, node)


class _OpSpan:
    __slots__ = ("name", "domain", "node", "_t0", "_jannot")

    def __init__(self, name, domain, node=None):
        self.name = name
        self.domain = domain
        self.node = node

    def __enter__(self):
        self._jannot = jax.profiler.TraceAnnotation(self.name)
        self._jannot.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._jannot.__exit__(*exc)
        from .. import profiler as _prof
        if _prof._active():  # state may have flipped mid-span
            dur_us = (t1 - self._t0) / 1000.0
            args = {"domain": self.domain}
            if self.node:
                args["node"] = self.node
            _prof._append_event({
                "name": self.name, "ph": "X", "cat": self.domain,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "ts": self._t0 / 1000.0, "dur": dur_us, "args": args,
            })
            _prof._agg_update(self.name, dur_us)
        return False


def maybe_instrument(name: str, fn: Callable, domain: str = "imperative"
                     ) -> Callable:
    """Wrap ``fn`` in an op span when tracing is active for ``domain``;
    return it untouched otherwise.

    Called per dispatch (profiler state is dynamic), so the off path is
    just the :func:`active` check. The wrapper carries ``_mx_traced`` so
    downstream layers (``ndarray.invoke``) don't double-instrument.
    """
    if not active(domain):
        return fn

    def traced(*args, __fn=fn, **kwargs):
        with _OpSpan(name, domain):
            return __fn(*args, **kwargs)

    traced.__name__ = name
    traced.__qualname__ = name
    traced.__doc__ = fn.__doc__
    traced._mx_traced = True
    return traced
