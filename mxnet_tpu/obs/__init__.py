"""mxobs: the pod-scale observability plane (ISSUE 17).

PR 12 built per-process observability (mxtrace spans, the flight
recorder, the metrics registry); PR 15 moved training into real host
processes. This package closes the gap between them:

- :mod:`~mxnet_tpu.obs.propagate` — cross-host trace propagation:
  control-plane messages carry the caller's span context, and every
  rank derives one shared ``pod.step`` root per (group uid,
  generation, step), so a pod-wide train step / rebuild / guard vote
  is ONE trace id stitched by ``mxprof trace --dir``;
- :mod:`~mxnet_tpu.obs.collector` — pod-merged metrics: hosts push
  mergeable snapshots over the heartbeat channel, rank 0 merges them
  (histogram counts exactly; owner-token lifecycle audited by
  ``passes/obslint.py``) and exports JSON-lines / Prometheus with
  per-rank labels;
- :mod:`~mxnet_tpu.obs.capture` — coordinated flight-recorder
  capture: one rank-0 dump trigger broadcasts over the heartbeat
  flags and every live rank freezes its recorder into the shared,
  rank-named dump directory.

Everything is behind ``MXOBS`` with the mxtrace cost discipline:
structurally zero-cost off (tests/test_obs.py), its cost when on not
measured on a chip, never touches jit cache keys.
docs/observability.md has the multi-host section.
"""
from __future__ import annotations

from . import capture, collector, propagate  # noqa: F401
from .capture import DumpFollower  # noqa: F401
from .collector import MetricsCollector, fleet_probe  # noqa: F401
from .collector import live_collectors  # noqa: F401
from .propagate import (bind, emit_pod_root, enabled,  # noqa: F401
                        pod_step_context, wire_context)

__all__ = ["propagate", "collector", "capture", "enabled",
           "wire_context", "bind", "pod_step_context", "emit_pod_root",
           "MetricsCollector", "live_collectors", "fleet_probe",
           "DumpFollower"]
