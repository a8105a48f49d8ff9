"""Profiler: jax.profiler + chrome-trace export.

ref: src/profiler/profiler.h:251 + python/mxnet/profiler.py — the reference
emits chrome://tracing JSON per engine event. On TPU the deep trace comes
from jax.profiler (XProf/TensorBoard); this module keeps the reference's
control surface (set_config/set_state/dump, scoped ranges, REAL
pause/resume) and emits a chrome-trace JSON of the Python-level scopes
for parity. The telemetry layer (mxnet_tpu/telemetry/) feeds it op-name
duration events, recompile instants, and memory counter samples, so one
``dump()`` carries the whole attribution story; ``tools/mxprof.py``
summarizes it.

Domains mirror the reference's config bits and are HONORED here
(ref: profiler.h kSymbolic/kImperative/kMemory/kAPI): events tagged with
a domain are dropped unless the matching ``profile_<domain>`` config is
on (``profile_all`` overrides).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

import jax

__all__ = ["set_config", "set_state", "dump", "dumps", "pause", "resume",
           "is_running", "is_paused", "Scope", "scope", "Task", "Domain"]

_state = threading.local()
_config = {"filename": "profile.json", "profile_all": False,
           "profile_symbolic": True, "profile_imperative": True,
           "profile_memory": True, "profile_api": True,
           "aggregate_stats": False}
_events: List[dict] = []
_events_lock = threading.Lock()
_running = False
_paused = False
_jax_dir: Optional[str] = None


def set_config(**kwargs):
    """ref: python/mxnet/profiler.py set_config / MXSetProcessProfilerConfig"""
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    global _running, _paused, _jax_dir
    if profile_process == "server":
        # remote/server profiling: command the parameter server (ref:
        # kvstore_dist.h:99 kSetProfilerParams;
        # tests/nightly/test_server_profiling.py)
        _send_server_command("profiler_state", state)
        return
    if state == "run" and not _running:
        _running = True
        _paused = False
        _jax_dir = os.path.splitext(_config["filename"])[0] + "_xprof"
        try:
            jax.profiler.start_trace(_jax_dir)
        except Exception:
            _jax_dir = None
    elif state == "stop" and _running:
        _running = False
        _paused = False
        if _jax_dir:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


def pause(profile_process="worker"):
    """Suppress event collection without tearing down the trace session
    (ref: MXProfilePause — the reference stops attributing engine events
    while paused; here every _append_event/_agg_update is dropped)."""
    global _paused
    if profile_process == "server":
        _send_server_command("profiler_pause", "1")
        return
    _paused = True


def resume(profile_process="worker"):
    global _paused
    if profile_process == "server":
        _send_server_command("profiler_pause", "0")
        return
    _paused = False


def is_running() -> bool:
    return _running


def is_paused() -> bool:
    return _paused


def _active() -> bool:
    """Events are collected: running and not paused."""
    return _running and not _paused


def _domain_enabled(domain: Optional[str]) -> bool:
    """Honor the per-domain config bits (profile_all overrides).
    Unknown/None domains are always collected."""
    if domain is None or _config.get("profile_all"):
        return True
    return bool(_config.get(f"profile_{domain}", True))


def _append_event(ev: dict):
    """Collect one chrome-trace event — the single gate every producer
    (Scope, telemetry tracing/recompile/memory) goes through."""
    if not _active():
        return
    with _events_lock:
        _events.append(ev)


def events(category: Optional[str] = None) -> List[dict]:
    """Snapshot of collected events, optionally filtered by ``cat``."""
    with _events_lock:
        evs = list(_events)
    if category is None:
        return evs
    return [e for e in evs if e.get("cat") == category]


def reset():
    """Drop collected events and aggregate stats (tests / fresh run)."""
    with _events_lock:
        _events.clear()
    with _agg_lock:
        _agg.clear()


def dumps(reset=False) -> str:
    """Chrome-trace JSON, or the aggregate statistics table when
    aggregate_stats is configured (ref: src/profiler/aggregate_stats.cc
    DumpTable via MXAggregateProfileStatsPrint)."""
    if _config.get("aggregate_stats"):
        out = _aggregate_table()
    else:
        with _events_lock:
            out = json.dumps({"traceEvents": list(_events)}, indent=1)
    if reset:
        with _events_lock:
            _events.clear()
        with _agg_lock:
            _agg.clear()
    return out


def dump(finished=True, profile_process="worker"):
    if profile_process == "server":
        _send_server_command("profiler_dump", "")
        return
    with _events_lock:
        payload = json.dumps({"traceEvents": list(_events)}, indent=1)
    with open(_config["filename"], "w") as f:
        f.write(payload)


# -- aggregate stats (ref: profiler.h:327-331 + aggregate_stats.cc) ---------

_agg: dict = {}
_agg_lock = threading.Lock()


def _agg_update(name: str, dur_us: float):
    if not _active():
        return
    with _agg_lock:
        ent = _agg.get(name)
        if ent is None:
            _agg[name] = [1, dur_us, dur_us, dur_us]
        else:
            ent[0] += 1
            ent[1] += dur_us
            ent[2] = min(ent[2], dur_us)
            ent[3] = max(ent[3], dur_us)


def _aggregate_table(top_k: Optional[int] = None) -> str:
    if top_k is None:
        from .base import get_env
        top_k = int(get_env("MXNET_PROFILER_TOPK", 0))
    lines = ["Profile Statistics:",
             f"{'Name':<40}{'Total Count':>12}{'Time (ms)':>14}"
             f"{'Min (ms)':>12}{'Max (ms)':>12}{'Avg (ms)':>12}",
             "-" * 102]
    with _agg_lock:
        rows = sorted(_agg.items(), key=lambda kv: -kv[1][1])
    if top_k and top_k > 0:
        dropped = len(rows) - top_k
        rows = rows[:top_k]
    else:
        dropped = 0
    for name, (count, total, mn, mx) in rows:
        lines.append(f"{name[:39]:<40}{count:>12}{total / 1e3:>14.4f}"
                     f"{mn / 1e3:>12.4f}{mx / 1e3:>12.4f}"
                     f"{total / count / 1e3:>12.4f}")
    if dropped > 0:
        lines.append(f"... {dropped} more name(s) below the top-{top_k} "
                     f"cut (MXNET_PROFILER_TOPK)")
    return "\n".join(lines)


def get_summary(reset=False, top_k: Optional[int] = None) -> str:
    """ref: MXAggregateProfileStatsPrint — always the aggregate table,
    sorted by total time; ``top_k`` (default MXNET_PROFILER_TOPK, 0 =
    all) bounds the row count."""
    out = _aggregate_table(top_k)
    if reset:
        with _agg_lock:
            _agg.clear()
    return out


def _send_server_command(head: str, body: str):
    """Route a profiler command to the parameter-server role (ref:
    kvstore_dist.h:99 SendCommandToServers)."""
    from . import kvstore_server as srv
    addr = srv.server_address()
    if addr is None:
        return  # no server in this job
    try:
        client = srv.KVClient(addr, retries=5)
        client.request(head, None, body)
        client._sock.close()
    except Exception:
        pass


class Scope:
    """Named profiling scope (ref: profiler.scope; also jax named scopes).

    ``domain`` tags the emitted event for the per-domain filter —
    user-level scopes default to the ``api`` domain (ref: the kAPI
    profiler mode bit)."""

    _current = threading.local()

    def __init__(self, name="<unk>:", domain="api"):
        self.name = name
        self.domain = domain

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        self._jctx = jax.profiler.TraceAnnotation(self.name)
        self._jctx.__enter__()
        return self

    def __exit__(self, *exc):
        self._jctx.__exit__(*exc)
        t1 = time.perf_counter_ns()
        if _active() and _domain_enabled(self.domain):
            dur_us = (t1 - self._t0) / 1000.0
            _append_event({
                "name": self.name, "ph": "X", "cat": self.domain,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "ts": self._t0 / 1000.0, "dur": dur_us,
            })
            _agg_update(self.name, dur_us)


scope = Scope


class _Named:
    def __init__(self, name, domain=None):
        self.name = getattr(name, "name", name)
        self._domain = getattr(domain, "name", domain) or "api"

    def start(self):
        self._scope = Scope(self.name, domain=self._domain)
        self._scope.__enter__()

    def stop(self):
        self._scope.__exit__(None, None, None)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(name, self)



class Task(_Named):
    pass


# MXNET_PROFILER_AUTOSTART / MXNET_PROFILER_MODE (ref: env_var.md): start
# profiling at import with the configured mode bitmask.
def _maybe_autostart():
    from .base import get_env
    if get_env("MXNET_PROFILER_AUTOSTART", False):
        mode = int(get_env("MXNET_PROFILER_MODE", 0))
        if mode:
            set_config(profile_all=True)
        set_state("run")


_maybe_autostart()
