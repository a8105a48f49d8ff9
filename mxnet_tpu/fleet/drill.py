"""Subprocess fleet drills: the proof layer of mxfleet.

``run_fleet_drill`` spawns REAL host processes (``python -m
mxnet_tpu.fleet.worker`` — own jax runtime, own DecodeEngine, own
socket server), an in-parent coordinator (KVServer + fleet
directory), and a FleetController, then drives templated load through
``controller.predict`` while one scripted fault lands mid-load:

- ``mode="kill_decode"`` — SIGKILL a decode host: its in-flight
  requests surface as ``EngineCrashedError``, breaker-mark, and retry
  on a surviving host — the drill asserts ZERO accepted requests
  drop and that the controller's next sync shrinks the group;
- ``mode="kill_prefill"`` — SIGKILL the prefill host: the
  disaggregation leg fails silently and every prompt falls back to
  local prefill (the single-host path) — zero drops, served count
  unchanged;
- ``mode="controller_restart"`` — stop the coordinator server
  mid-load and bind a fresh one on the SAME port: worker heartbeats
  see ``fleet_heartbeat() -> False`` and re-register, the
  controller's PodGroup rides its bounded-backoff reconnect, and the
  data plane (direct worker sockets) never notices;
- ``mode="baseline"`` — no fault, same load (the comparison run).

Faults are request-count scripted, never timed.  Shared by
tests/test_fleet_drill.py (@slow, 3 modes).
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from ..base import MXNetError, get_logger

__all__ = ["run_fleet_drill", "FleetHarness"]

_log = get_logger("mxnet_tpu.fleet")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Worker:
    """One spawned fleet worker process + its FLEET event stream."""

    def __init__(self, wid: str, role: str, env: Dict[str, str]):
        self.wid = wid
        self.role = role
        self.events: List[Dict] = []
        self.raw: List[str] = []
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.fleet.worker"],
            env=env, cwd=_REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self._reader = threading.Thread(target=self._drain,
                                        daemon=True)
        self._reader.start()

    def _drain(self):
        for ln in self.proc.stdout:
            self.raw.append(ln)
            if ln.startswith("FLEET "):
                try:
                    evt = json.loads(ln[6:])
                except ValueError:
                    continue
                evt["_t"] = time.perf_counter()
                self.events.append(evt)

    def of(self, kind: str) -> List[Dict]:
        return [e for e in self.events if e.get("evt") == kind]

    def address(self) -> Optional[str]:
        ready = self.of("ready")
        return ready[0]["address"] if ready else None

    def kill_now(self):
        try:
            self.proc.kill()
        except OSError:
            pass

    def terminate(self):
        try:
            self.proc.terminate()
        except OSError:
            pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class FleetHarness:
    """Coordinator + N workers + controller, as the drill runs
    them. The parent process plays the
    controller host (binds the KVServer carrying the fleet
    directory)."""

    def __init__(self, *, n_decode: int = 2, n_prefill: int = 1,
                 page_size: int = 8, num_pages: int = 128,
                 max_inflight: int = 4, max_seq: int = 96,
                 max_new: int = 8, heartbeat_s: float = 0.25,
                 grace_s: float = 20.0):
        from .. import config
        from ..kvstore_server import KVServer
        from ..pod.group import PodGroup
        from .controller import FleetController
        self.page_size = int(page_size)
        self.max_new = int(max_new)
        self.heartbeat_s = float(heartbeat_s)
        config.set_flag("MXFLEET_HEARTBEAT_S", self.heartbeat_s)
        self.port = _free_port()
        self.addr = f"127.0.0.1:{self.port}"
        # one "worker" from the kvstore server's point of view: the
        # fleet directory rides the elastic sidecar ops only
        self.server = KVServer(self.addr, 1)
        base_env = dict(os.environ)
        for k in ("MX_COORDINATOR", "MX_KV_SERVER", "MX_WORKER_ID",
                  "MX_NUM_WORKERS", "XLA_FLAGS", "MXRESIL_FAULT_PLAN",
                  "MXPOD_JOIN", "MXFLEET_COORDINATOR"):
            base_env.pop(k, None)
        base_env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": _REPO_ROOT + os.pathsep
            + base_env.get("PYTHONPATH", ""),
            "MXFLEET_COORDINATOR": self.addr,
            "MXFLEET_HEARTBEAT_S": str(self.heartbeat_s),
            "MXPOD_COORDINATOR_GRACE_S": str(grace_s),
            "FLEET_PAGE": str(page_size),
            "FLEET_PAGES": str(num_pages),
            "FLEET_INFLIGHT": str(max_inflight),
            "FLEET_MAX_SEQ": str(max_seq),
        })
        self.base_env = base_env
        self.workers: List[_Worker] = []
        for i in range(int(n_decode)):
            self.workers.append(self._spawn(f"d{i}", "decode"))
        for i in range(int(n_prefill)):
            self.workers.append(self._spawn(f"p{i}", "prefill"))
        self.group = PodGroup(self.addr, grace_s=grace_s)
        self.controller = FleetController(
            self.group, page_size=page_size,
            heartbeat_s=self.heartbeat_s)

    def _spawn(self, wid: str, role: str) -> _Worker:
        env = dict(self.base_env)
        env["MXFLEET_ROLE"] = role
        env["MXFLEET_WORKER_ID"] = wid
        return _Worker(wid, role, env)

    def decode_workers(self) -> List[_Worker]:
        return [w for w in self.workers if w.role == "decode"]

    def prefill_workers(self) -> List[_Worker]:
        return [w for w in self.workers if w.role == "prefill"]

    def wait_ready(self, timeout_s: float = 180.0):
        """Block until every worker registered and the controller's
        group covers all decode workers (engines warm inside this
        window — the slow part of a host bring-up)."""
        deadline = time.monotonic() + timeout_s
        want = len(self.decode_workers())
        while time.monotonic() < deadline:
            for w in self.workers:
                if w.proc.poll() is not None:
                    raise MXNetError(
                        f"fleet worker {w.wid} died during bring-up "
                        f"(rc={w.proc.returncode}): "
                        f"{''.join(w.raw[-12:])[:1200]}")
            got = self.controller.sync(force=True)
            if got["decode"] == want and \
                    got["prefill"] == len(self.prefill_workers()):
                return
            time.sleep(0.2)
        raise MXNetError(
            f"fleet bring-up timed out after {timeout_s:.0f}s "
            f"(directory: {self.controller.describe()['decode']})")

    def restart_coordinator(self):
        """Kill the control plane and bind a fresh server on the SAME
        port — the coordinator-restart drill. Directory state is
        deliberately lost; workers re-register on their next beat."""
        self.server.stop()
        from ..kvstore_server import KVServer
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                self.server = KVServer(self.addr, 1)
                break
            except OSError:
                time.sleep(0.1)
        else:
            raise MXNetError("could not rebind coordinator port")
        self.group.reconnect()

    def close(self):
        for w in self.workers:
            w.terminate()
        deadline = time.monotonic() + 15.0
        for w in self.workers:
            while w.proc.poll() is None and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            if w.proc.poll() is None:
                w.kill_now()
        try:
            self.controller.close()
        except Exception:  # noqa: BLE001
            pass
        try:
            self.group.close()
        except Exception:  # noqa: BLE001
            pass
        self.server.stop()


def _make_payloads(n: int, prompt_len: int, page_size: int,
                   n_templates: int = 4, vocab: int = 64,
                   seed: int = 0) -> List[List[int]]:
    """Templated prompts: a shared leading template (>= 2 pages, so
    the affinity key and the prefix cache both engage) + a unique
    suffix per request."""
    import numpy as onp
    rs = onp.random.RandomState(seed)
    tpl_len = max(2 * page_size, (prompt_len * 2) // 3)
    templates = [rs.randint(0, vocab, size=(tpl_len,)).tolist()
                 for _ in range(n_templates)]
    out = []
    for i in range(n):
        tpl = templates[i % n_templates]
        suffix = rs.randint(0, vocab,
                            size=(max(1, prompt_len - tpl_len),))
        out.append([int(t) for t in tpl] + suffix.tolist())
    return out


def run_fleet_drill(mode: str = "kill_decode", *,
                    n_decode: int = 2, n_prefill: int = 1,
                    n_requests: int = 36, concurrency: int = 4,
                    prompt_len: int = 24, fault_after: int = 8,
                    page_size: int = 8, max_new: int = 8,
                    timeout_s: float = 300.0) -> Dict[str, object]:
    """One scripted fleet drill (module docstring); returns the
    report dict. Every submitted request is an ACCEPTED request —
    the zero-drop assertion is ``completed == n_requests``."""
    if mode not in ("baseline", "kill_decode", "kill_prefill",
                    "controller_restart"):
        raise MXNetError(f"unknown fleet drill mode {mode!r}")
    if mode == "kill_prefill" and n_prefill < 1:
        raise MXNetError("kill_prefill needs a prefill worker")
    t_start = time.perf_counter()
    h = FleetHarness(n_decode=n_decode, n_prefill=n_prefill,
                     page_size=page_size, max_new=max_new)
    fault_fired = threading.Event()
    failures: List[str] = []
    done = {"count": 0}
    from ..san.runtime import make_lock
    lock = make_lock("fleet.drill.counters")
    try:
        h.wait_ready(timeout_s=min(240.0, timeout_s))
        payloads = _make_payloads(n_requests, prompt_len, page_size)
        started = {"count": 0}

        def _fault():
            if mode == "kill_decode":
                h.decode_workers()[0].kill_now()
            elif mode == "kill_prefill":
                h.prefill_workers()[0].kill_now()
            elif mode == "controller_restart":
                h.restart_coordinator()

        def _run(idx: int, tokens: List[int]):
            try:
                out = h.controller.predict(
                    tokens, timeout_ms=60_000.0)
                if not out:
                    raise MXNetError("empty generation")
                with lock:
                    done["count"] += 1
            except Exception as e:  # noqa: BLE001 — the drill's
                # whole point is counting these
                with lock:
                    failures.append(
                        f"req {idx}: {type(e).__name__}: "
                        f"{str(e)[:160]}")

        threads: List[threading.Thread] = []
        sem = threading.Semaphore(int(concurrency))
        for idx, tokens in enumerate(payloads):
            sem.acquire()
            with lock:
                started["count"] += 1
                fire = (mode != "baseline"
                        and not fault_fired.is_set()
                        and started["count"] > int(fault_after))
                if fire:
                    fault_fired.set()
            if fire:
                _fault()

            def _wrapped(i=idx, tk=tokens):
                try:
                    _run(i, tk)
                finally:
                    sem.release()
            t = threading.Thread(target=_wrapped, daemon=True)
            t.start()
            threads.append(t)
        deadline = time.monotonic() + timeout_s
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                failures.append("request thread hung past deadline")
                break
        # post-fault convergence: the directory ages the dead host
        # out and the controller's group shrinks to the survivors
        post_sync = {}
        if mode == "kill_decode":
            conv_deadline = time.monotonic() + 10 * h.heartbeat_s
            while time.monotonic() < conv_deadline:
                post_sync = h.controller.sync(force=True)
                if post_sync.get("decode") == n_decode - 1:
                    break
                time.sleep(h.heartbeat_s)
        prefix_stats = {}
        for w in h.workers:
            if w.proc.poll() is not None:
                continue
            addr = w.address()
            if not addr:
                continue
            try:
                from .worker import EngineClient
                cli = EngineClient(addr)
                try:
                    prefix_stats[w.wid] = dict(
                        cli.request("stats")).get(
                            "prefix_cache") or {}
                finally:
                    cli.close()
            except Exception:  # noqa: BLE001
                pass
        return {
            "mode": mode,
            "requests": int(n_requests),
            "completed": int(done["count"]),
            "dropped": int(n_requests - done["count"]),
            "failures": failures[:10],
            "fault_fired": bool(fault_fired.is_set()),
            "post_fault_decode": post_sync.get("decode"),
            "prefix_stats": prefix_stats,
            "controller": h.controller.describe()["depths"],
            "duration_s": round(time.perf_counter() - t_start, 3),
        }
    finally:
        h.close()
