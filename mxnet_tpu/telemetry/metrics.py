"""Process-wide metrics registry: counters, gauges, histograms + exporters.

The reference had no first-class metrics surface — throughput numbers
lived in example scripts and the profiler's aggregate table. On TPU the
numbers that decide whether a run is healthy (step time, recompiles,
bytes in flight, kvstore latency) are cheap to count and expensive to
reconstruct after the fact, so this module keeps one process-wide
registry that the framework layers (gluon Trainer, kvstore, the
recompile auditor) feed at their natural boundaries.

Two exporters:

- :func:`to_json_lines` / :func:`export_jsonl` — one JSON object per
  snapshot, append-friendly (the ``MXNET_METRICS_EXPORT`` path gets one
  line per Trainer step);
- :func:`to_prometheus` — Prometheus text exposition format
  (``# TYPE``-annotated), for scraping out of a long-lived worker.

All operations are O(1) under one lock; a counter increment is cheap
enough to live on the kvstore push path.
"""
from __future__ import annotations

import json
import random as _random_mod
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..san.runtime import make_lock

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
           "all_metrics", "snapshot", "to_json_lines", "to_prometheus",
           "export_jsonl", "reset_metrics", "percentile_of",
           "merge_reservoirs", "mergeable_snapshot",
           "OwnerToken", "owner", "owners"]


def percentile_of(sorted_vals, q: float):
    """Nearest-rank percentile (0..100) over an ascending-sorted
    sequence; None when empty. The ONE quantile implementation shared by
    Histogram, the serving loadgen, and the CLIs."""
    if not sorted_vals:
        return None
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]

_LOCK = make_lock("telemetry.metrics.registry")
_METRICS: Dict[str, "Metric"] = {}


def merge_reservoirs(a, n_a, b, n_b, cap, rng=None):
    """Merge two recent-sample reservoirs into one of at most ``cap``
    samples, UNBIASED with respect to the full streams they summarize:
    each retained sample stands for ``n_side / len(side)`` raw
    observations, and selection is weighted sampling without
    replacement (exponential keys, the A-ES scheme), so a reservoir
    backed by 10x the observations contributes ~10x the mass. The
    obs collector merges per-rank histogram states through this.

    ``rng`` is injectable for deterministic tests."""
    a = list(a)
    b = list(b)
    if not a:
        return b[-cap:] if len(b) > cap else b
    if not b:
        return a[-cap:] if len(a) > cap else a
    if len(a) + len(b) <= cap:
        return a + b
    rng = rng or _random_mod
    w_a = max(float(n_a), float(len(a))) / len(a)
    w_b = max(float(n_b), float(len(b))) / len(b)
    keyed = [(rng.random() ** (1.0 / w_a), v) for v in a]
    keyed += [(rng.random() ** (1.0 / w_b), v) for v in b]
    keyed.sort(key=lambda kv: -kv[0])
    return [v for _, v in keyed[:cap]]


class Metric:
    """Base: a named, documented instrument."""

    kind = "untyped"

    def __init__(self, name: str, doc: str = ""):
        self.name = name
        self.doc = doc

    def value(self):
        raise NotImplementedError

    def reset(self):
        raise NotImplementedError


class Counter(Metric):
    """Monotone counter (steps taken, recompiles, samples seen)."""

    kind = "counter"

    def __init__(self, name, doc=""):
        super().__init__(name, doc)
        self._v = 0

    def inc(self, n=1):
        with _LOCK:
            self._v += n

    def value(self):
        return self._v  # single-field read: atomic in CPython

    def reset(self):
        with _LOCK:
            self._v = 0


class Gauge(Metric):
    """Point-in-time value (live bytes, throughput, learning rate)."""

    kind = "gauge"

    def __init__(self, name, doc=""):
        super().__init__(name, doc)
        self._v = 0.0

    def set(self, v):
        with _LOCK:
            self._v = v

    def max(self, v):
        """Set to max(current, v) — peak tracking."""
        with _LOCK:
            if v > self._v:
                self._v = v

    def value(self):
        return self._v  # single-field read: atomic in CPython

    def reset(self):
        with _LOCK:
            self._v = 0.0


class Histogram(Metric):
    """Streaming distribution: count / sum / min / max, plus quantiles
    over a bounded reservoir of the most recent observations.

    The streaming fields are exact over the full history; ``p50``/``p99``
    are computed from the last ``RESERVOIR`` samples (a deque — serving
    latency quantiles care about *recent* behavior, and a sliding window
    is the Prometheus-summary convention without the decay math)."""

    kind = "histogram"
    RESERVOIR = 512

    def __init__(self, name, doc=""):
        super().__init__(name, doc)
        self._reset_fields()

    def _reset_fields(self):
        # under _LOCK (reset(); __init__ runs before publication)
        self._count = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = float("-inf")
        self._recent = deque(maxlen=self.RESERVOIR)

    def observe(self, v):
        v = float(v)
        with _LOCK:
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v
            self._recent.append(v)

    def percentile(self, q: float):
        """q-th percentile (0..100) over the recent-sample reservoir;
        None when nothing has been observed."""
        with _LOCK:
            samples = sorted(self._recent)
        return percentile_of(samples, q)

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def value(self):
        # multi-field read: lock so count/sum/avg are mutually
        # consistent even against a concurrent observe()
        with _LOCK:
            if not self._count:
                return {"count": 0, "sum": 0.0}
            samples = sorted(self._recent)
            return {"count": self._count, "sum": self._sum,
                    "min": self._min, "max": self._max,
                    "avg": self._sum / self._count,
                    "p50": percentile_of(samples, 50),
                    "p99": percentile_of(samples, 99)}

    def state(self) -> dict:
        """The MERGEABLE form: exact streaming fields plus the raw
        reservoir — what a pod host pushes to the rank-0 collector
        (picklable/JSON-able, no Metric object crosses the wire)."""
        with _LOCK:
            return {"count": self._count, "sum": self._sum,
                    "min": self._min, "max": self._max,
                    "recent": list(self._recent)}

    def merge(self, other, rng=None) -> "Histogram":
        """Fold another histogram (a :class:`Histogram` or a
        :meth:`state` dict) into this one: count/sum/min/max merge
        EXACTLY; the reservoirs merge by count-weighted sampling
        (:func:`merge_reservoirs`), so quantiles stay representative
        of the combined stream. Returns self."""
        st = other.state() if isinstance(other, Histogram) else other
        o_count = int(st.get("count") or 0)
        if not o_count:
            return self
        o_recent = list(st.get("recent") or ())
        with _LOCK:
            merged = merge_reservoirs(
                list(self._recent), self._count,
                o_recent, o_count, self.RESERVOIR, rng=rng)
            self._count += o_count
            self._sum += float(st.get("sum") or 0.0)
            o_min = st.get("min")
            if o_min is not None and float(o_min) < self._min:
                self._min = float(o_min)
            o_max = st.get("max")
            if o_max is not None and float(o_max) > self._max:
                self._max = float(o_max)
            self._recent = deque(merged, maxlen=self.RESERVOIR)
        return self

    def reset(self):
        with _LOCK:
            self._reset_fields()


def _get_or_create(cls, name: str, doc: str) -> Metric:
    with _LOCK:
        m = _METRICS.get(name)
        if m is None:
            m = cls(name, doc)
            _METRICS[name] = m
        elif not isinstance(m, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{type(m).__name__}, requested {cls.__name__}")
        return m


def counter(name: str, doc: str = "") -> Counter:
    return _get_or_create(Counter, name, doc)


def gauge(name: str, doc: str = "") -> Gauge:
    return _get_or_create(Gauge, name, doc)


def histogram(name: str, doc: str = "") -> Histogram:
    return _get_or_create(Histogram, name, doc)


def unregister(name: str) -> bool:
    """Drop one instrument by name (per-engine gauges when their engine
    is closed/retired — a reload must not leave dead pools looking like
    live fully-free ones in ``/metrics``)."""
    with _LOCK:
        return _METRICS.pop(name, None) is not None


# -- owner tokens (the metriclint contract) ---------------------------------
#
# The recurring leak class fixed by hand in PRs 8, 10 and 11:
# per-INSTANCE instruments (per-engine pool gauges, per-replica breaker
# gauges, per-probe EWMA gauges) registered at construction and
# forgotten at close, leaving a dead engine looking live in /metrics.
# An OwnerToken makes the lifecycle auditable: the owning object adopts
# its instrument names at construction and close()s the token when it
# retires them; passes/metriclint.py flags any CLOSED owner whose
# adopted instruments are still registered.

_OWNERS: List["OwnerToken"] = []


class OwnerToken:
    """Lifecycle handle tying per-instance instruments to the object
    that registered them. Create via :func:`owner`."""

    __slots__ = ("name", "names", "closed")

    def __init__(self, name: str):
        self.name = str(name)
        self.names: set = set()
        self.closed = False

    def adopt(self, *names: str) -> "OwnerToken":
        """Associate instrument names (instrument objects accepted
        too) with this owner."""
        for n in names:
            self.names.add(n.name if isinstance(n, Metric) else str(n))
        return self

    def close(self) -> None:
        """Declare this owner retired — its adopted instruments must
        already be unregistered, or metriclint flags the leak."""
        self.closed = True

    def leaked(self) -> List[str]:
        """Adopted instruments still live after close (empty = clean)."""
        if not self.closed:
            return []
        with _LOCK:
            return sorted(n for n in self.names if n in _METRICS)

    def describe(self) -> Dict[str, object]:
        return {"owner": self.name, "closed": self.closed,
                "names": sorted(self.names)}

    def __repr__(self):
        return (f"<OwnerToken {self.name!r} {len(self.names)} "
                f"instrument(s){' closed' if self.closed else ''}>")


def owner(name: str) -> OwnerToken:
    """Register a new instrument owner (one per engine/replica/probe
    instance)."""
    tok = OwnerToken(name)
    with _LOCK:
        _OWNERS.append(tok)
        # bound the ledger: fully-retired CLEAN owners sweep out once
        # the list grows past 1024. Open owners and leaky closed
        # owners are never evicted — the leaky ones are what the lint
        # exists to surface, and evicting an open owner would blind
        # the audit to its eventual close. If everything is open or
        # leaky, the ledger grows (small objects; the lint is already
        # screaming at that point).
        if len(_OWNERS) > 1024:
            _OWNERS[:] = [
                t for t in _OWNERS
                if not t.closed or any(n in _METRICS
                                       for n in t.names)]
    return tok


def owners() -> List[OwnerToken]:
    with _LOCK:
        return list(_OWNERS)


def all_metrics() -> Dict[str, Metric]:
    with _LOCK:
        return dict(_METRICS)


def reset_metrics(clear: bool = False):
    """Zero every instrument (tests); ``clear=True`` drops them (and
    the owner ledger)."""
    with _LOCK:
        if clear:
            _METRICS.clear()
            _OWNERS.clear()
            return
    for m in all_metrics().values():
        m.reset()


def snapshot() -> Dict[str, object]:
    """{name: value} for every instrument; histogram values are dicts."""
    return {name: m.value() for name, m in sorted(all_metrics().items())}


def mergeable_snapshot() -> Dict[str, Dict[str, object]]:
    """{name: {"kind", ...}} over every instrument, in the form the
    pod collector can MERGE across hosts: counters/gauges carry their
    scalar, histograms their full :meth:`Histogram.state` (exact
    count/sum/min/max + raw reservoir). This is what one host pushes
    per MXOBS_PUSH_INTERVAL_S tick."""
    out: Dict[str, Dict[str, object]] = {}
    for name, m in sorted(all_metrics().items()):
        if isinstance(m, Histogram):
            out[name] = {"kind": "histogram", **m.state()}
        else:
            out[name] = {"kind": m.kind, "value": m.value()}
    return out


def to_json_lines(extra: Optional[Dict[str, object]] = None) -> str:
    """One JSON object: {"ts", "metrics": {...}, **extra} — a single
    snapshot line of the JSON-lines export stream."""
    line = {"ts": time.time(), "metrics": snapshot()}
    if extra:
        line.update(extra)
    return json.dumps(line)


def export_jsonl(path: str, extra: Optional[Dict[str, object]] = None):
    """Append one snapshot line to ``path`` (the MXNET_METRICS_EXPORT
    sink). Never raises — telemetry must not take down training."""
    try:
        with open(path, "a") as f:
            f.write(to_json_lines(extra) + "\n")
    except OSError:
        pass


def to_prometheus() -> str:
    """Prometheus text exposition format of the current snapshot."""
    lines: List[str] = []
    for name, m in sorted(all_metrics().items()):
        if m.doc:
            lines.append(f"# HELP {name} {m.doc}")
        if isinstance(m, Histogram):
            lines.append(f"# TYPE {name} summary")
            v = m.value()
            lines.append(f"{name}_count {v['count']}")
            lines.append(f"{name}_sum {v['sum']}")
            if v["count"]:
                lines.append(f"{name}_min {v['min']}")
                lines.append(f"{name}_max {v['max']}")
                lines.append(f'{name}{{quantile="0.5"}} {v["p50"]}')
                lines.append(f'{name}{{quantile="0.99"}} {v["p99"]}')
        else:
            lines.append(f"# TYPE {name} {m.kind}")
            lines.append(f"{name} {m.value()}")
    return "\n".join(lines) + "\n"
