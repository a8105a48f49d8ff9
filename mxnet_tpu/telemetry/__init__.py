"""mxnet_tpu.telemetry: unified runtime observability.

Three pillars (ISSUE 2), one package:

- :mod:`~mxnet_tpu.telemetry.tracing` — op-level tracing: a registered
  op traced into a program runs under ``jax.named_scope`` (its name
  survives into the compiled HLO, profiler or not), and while the
  profiler is on its execution is a ``jax.profiler.TraceAnnotation``
  plus a chrome-trace event, so MXNet op names reach XProf and the
  chrome-trace dump;
- :mod:`~mxnet_tpu.telemetry.recompile` /
  :mod:`~mxnet_tpu.telemetry.memory` — recompile & memory accounting:
  every jit-cache miss is counted and classified ("why did we
  recompile"), and periodic live-array/device-memory snapshots feed
  peak gauges and chrome-trace counter events;
- :mod:`~mxnet_tpu.telemetry.metrics` — process-wide counters / gauges /
  histograms with JSON-lines and Prometheus exporters.

The framework feeds it from its natural boundaries (ops/registry
dispatch, HybridBlock/Executor compiles, Trainer.step, kvstore
push/pull); ``tools/mxprof.py`` renders the dumps.

The CORRELATED layer on top — per-request/per-step span trees threaded
across subsystems, plus the crash flight recorder — lives in
:mod:`mxnet_tpu.trace` (ISSUE 13). Per-instance instruments here carry
owner tokens (:func:`metrics.owner`) audited by
``passes/metriclint.py``.

See docs/observability.md for the architecture.
"""
from __future__ import annotations

import time

from . import metrics  # noqa: F401
from . import memory  # noqa: F401
from . import recompile  # noqa: F401
from . import tracing  # noqa: F401
from .metrics import (counter, gauge, histogram, snapshot,  # noqa: F401
                      to_json_lines, to_prometheus, export_jsonl,
                      reset_metrics)
from .recompile import (record_recompile, recompile_count,  # noqa: F401
                        recompile_report, reset_recompiles)

__all__ = ["metrics", "memory", "recompile", "tracing", "counter", "gauge",
           "histogram", "snapshot", "to_json_lines", "to_prometheus",
           "export_jsonl", "reset_metrics", "record_recompile",
           "recompile_count", "recompile_report", "reset_recompiles",
           "record_step", "reset_all"]


def record_step(batch_size: int, seconds: float, prefix: str = "trainer"):
    """The step-boundary hook: called by ``gluon.Trainer.step`` once
    per optimization step. Updates the step counters,
    takes a throttled memory sample, and appends one JSON line to the
    ``MXNET_METRICS_EXPORT`` sink when configured."""
    metrics.counter(f"{prefix}_step_total", "optimization steps").inc()
    metrics.counter(f"{prefix}_samples_total",
                    "samples consumed by steps").inc(batch_size)
    metrics.histogram(f"{prefix}_step_seconds",
                      "wall-clock step latency").observe(seconds)
    if seconds > 0:
        metrics.gauge(f"{prefix}_throughput_samples_per_sec",
                      "instantaneous step throughput"
                      ).set(batch_size / seconds)
    memory.maybe_sample()
    from ..base import get_env
    sink = get_env("MXNET_METRICS_EXPORT", "")
    if sink:
        metrics.export_jsonl(sink)


def observe_latency(name: str, seconds: float, doc: str = ""):
    """Record one latency observation into histogram ``name`` —
    the kvstore push/pull hook."""
    metrics.histogram(name, doc).observe(seconds)


class timed_block:
    """``with timed_block("kvstore_push_seconds"): ...`` — histogram
    observation of the block's wall time."""

    def __init__(self, name: str, doc: str = ""):
        self._name = name
        self._doc = doc

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        observe_latency(self._name, time.perf_counter() - self._t0,
                        self._doc)
        return False


def reset_all():
    """Reset every telemetry store (tests / between runs)."""
    reset_metrics()
    reset_recompiles()
    memory.reset_peak()
