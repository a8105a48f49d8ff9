"""Fleet engine host: one serving worker PROCESS (decode or prefill).

:class:`EngineHost` wraps one DecodeEngine in a thread-per-connection
TCP server speaking the same framed-pickle wire as the kvstore
control plane (`kvstore_server._send_msg`/`_recv_msg`) — usable
in-process by the fast tests and as the data plane of the subprocess
drill.  :class:`EngineClient` is the matching blocking client;
remote exceptions come back TYPED (by serve-taxonomy class name) so
the controller's RemoteEngine can hand the Router the exact error
semantics it already understands.

``python -m mxnet_tpu.fleet.worker`` — spawned per host by
fleet/drill.py.  Each process builds the
SAME seeded pipeline-LM params as its siblings (env-seeded, so every
decode replica serves the identical model), warms the engine
(including the pagewire chunk programs), starts an EngineHost,
registers in the coordinator's fleet directory, and heartbeats at
MXFLEET_HEARTBEAT_S with its live queue depth.  One ``FLEET {json}``
line per event on stdout for the harness.  SIGTERM = drain + leave +
exit 0; a coordinator restart surfaces as ``fleet_heartbeat() ->
False`` and the worker simply re-registers (the directory is not
journaled — workers outlive it and re-announce).
"""
from __future__ import annotations

import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Dict, Optional

from ..base import MXNetError, get_logger
from ..san.runtime import make_lock

__all__ = ["EngineHost", "EngineClient", "RemoteEngineError"]

_log = get_logger("mxnet_tpu.fleet")


class RemoteEngineError(MXNetError):
    """A fleet worker reported an exception the serve taxonomy does
    not name — carried across the wire as its type name."""


def _typed_remote_error(etype: str, msg: str) -> BaseException:
    """Rebuild the serve-taxonomy exception the worker raised, so the
    Router's error semantics (client error vs backpressure vs crash)
    survive the wire."""
    from ..serve.batcher import (BatcherStoppedError,
                                 DeadlineExceededError,
                                 InvalidRequestError, QueueFullError,
                                 RequestTooLargeError)
    from ..serve.buckets import BucketOverflowError
    from ..serve2.kvcache import PagePoolExhausted
    from ..serve2.scheduler import EngineCrashedError
    known = {c.__name__: c for c in (
        BatcherStoppedError, DeadlineExceededError, InvalidRequestError,
        QueueFullError, RequestTooLargeError, BucketOverflowError,
        PagePoolExhausted, EngineCrashedError)}
    cls = known.get(etype)
    if cls is not None:
        return cls(msg)
    return RemoteEngineError(f"{etype}: {msg}")


class EngineHost:
    """Serve one engine over the framed-pickle wire.

    Ops: ``ping``, ``predict``, ``depth``, ``stats``, ``drain``,
    ``prefill_push`` (prefill worker: prefill + stream pages to a
    decode host), ``page_probe``/``page_install`` (decode worker:
    pagewire receive side).
    """

    def __init__(self, engine, *, role: str = "decode",
                 name: str = "host", port: int = 0,
                 pagewire_chunk: Optional[int] = None):
        from .. import config
        self.engine = engine
        self.role = str(role)
        self.name = str(name)
        self.pagewire_chunk = int(
            pagewire_chunk if pagewire_chunk is not None
            else config.get("MXFLEET_PAGEWIRE_CHUNK_PAGES"))
        self._lock = make_lock("fleet.worker.host")
        self._threads = []
        self._stopping = False
        self._listener = socket.socket(socket.AF_INET,
                                       socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", int(port)))
        self._listener.listen(64)
        self.address = "%s:%d" % self._listener.getsockname()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"fleet-host-{name}",
            daemon=True)
        self._accept_thread.start()

    # -- server loop ---------------------------------------------------
    def _accept_loop(self):
        while not self._stopping:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            if self._stopping:
                conn.close()
                return
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _serve(self, conn: socket.socket):
        from ..kvstore_server import _recv_msg, _send_msg
        try:
            while True:
                try:
                    req = _recv_msg(conn)
                except (OSError, EOFError, ConnectionError):
                    return
                try:
                    value = self._dispatch(req.get("op"), req)
                    reply = {"ok": True, "value": value}
                except BaseException as e:  # noqa: BLE001 — every
                    # worker-side failure must reach the caller typed;
                    # the worker process itself stays up
                    reply = {"ok": False,
                             "etype": type(e).__name__,
                             "error": str(e)[:500]}
                try:
                    _send_msg(conn, reply)
                except (OSError, ConnectionError):
                    return
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _dispatch(self, op, kw: Dict):
        eng = self.engine
        if op == "ping":
            return {"role": self.role, "name": self.name,
                    "warmed": bool(eng.warmed),
                    "address": self.address}
        if op == "predict":
            return [int(t) for t in eng.predict(
                kw["tokens"], timeout_ms=kw.get("timeout_ms"))]
        if op == "depth":
            return int(eng.queue_depth())
        if op == "stats":
            st = dict(eng.stats())
            st["role"] = self.role
            return st
        if op == "drain":
            return bool(eng.drain(kw.get("timeout")))
        if op == "page_probe":
            # how many leading keys of the chain the local cache holds
            cache = eng.prefix
            if cache is None:
                return 0
            have = 0
            for k in kw["keys"]:
                if cache.find(k) is None:
                    break
                have += 1
            return have
        if op == "page_install":
            from .pagewire import install_chunks
            return install_chunks(eng, kw["keys"], kw["chunks"],
                                  self.pagewire_chunk)
        if op == "prefill_push":
            return self._prefill_push(kw["tokens"], kw.get("dst"))
        raise MXNetError(f"unknown fleet op {op!r}")

    def _prefill_push(self, tokens, dst: Optional[str]) -> Dict:
        """Prefill worker: compute the prompt through the PUBLIC
        engine path (pages land in the local prefix cache), then
        stream the cached pages the destination decode host does not
        already hold."""
        from .pagewire import collect_pages, export_chunks
        eng = self.engine
        h = eng.submit(tokens, max_new_tokens=1)
        h.wait()
        keys, pages = collect_pages(eng, tokens)
        out = {"cached_pages": len(pages), "streamed": 0}
        if not pages or not dst:
            if pages:
                eng.alloc.free(pages)
            return out
        try:
            cli = EngineClient(dst)
            try:
                have = int(cli.request("page_probe", keys=keys))
                send_keys = keys[have:]
                send_pages = pages[have:]
                if send_pages:
                    chunks = export_chunks(eng.lm, send_pages,
                                           self.pagewire_chunk)
                    out["streamed"] = int(cli.request(
                        "page_install", keys=send_keys,
                        chunks=chunks))
            finally:
                cli.close()
        finally:
            eng.alloc.free(pages)
        return out

    def stop(self):
        """Refuse connections from here on. close() alone does not wake
        a thread blocked in accept() on Linux (the stopped host would
        serve one more connection); shutdown() does, and the loop ends
        before close() frees the port."""
        self._stopping = True
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._accept_thread.join(timeout=5.0)
        try:
            self._listener.close()
        except OSError:
            pass


class EngineClient:
    """Blocking framed-pickle client for one EngineHost. One socket,
    serialized by a lock — controller callers that want concurrency
    hold one client per thread (RemoteEngine does)."""

    def __init__(self, address: str, connect_timeout_s: float = 5.0):
        self.address = address
        host, _, port = address.partition(":")
        self._lock = make_lock("fleet.worker.client")
        self._sock = socket.create_connection(
            (host or "127.0.0.1", int(port)),
            timeout=connect_timeout_s)
        # ops block for the remote predict duration — no socket
        # timeout; host death surfaces as ECONNRESET/EOF instead
        self._sock.settimeout(None)

    def request(self, op: str, **kw):
        from ..kvstore_server import _recv_msg, _send_msg
        kw["op"] = op
        with self._lock:
            _send_msg(self._sock, kw)
            reply = _recv_msg(self._sock)
        if reply.get("ok"):
            return reply.get("value")
        raise _typed_remote_error(reply.get("etype", "Exception"),
                                  reply.get("error", ""))

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass


# ----------------------------------------------------------------------
# subprocess entry
# ----------------------------------------------------------------------
def _emit(evt: str, **kw):
    kw["evt"] = evt
    print("FLEET " + json.dumps(kw), flush=True)


def build_engine(*, seed: int, vocab: int, n_layers: int, d_model: int,
                 n_heads: int, page_size: int, num_pages: int,
                 max_inflight: int, max_seq_len: int,
                 pagewire_chunk: int, name: str,
                 prefill_buckets=None):
    """The shared engine recipe: every fleet host builds the SAME
    seeded params (greedy decode is then deterministic fleet-wide —
    the cross-host parity test and the zero-drop retry path both ride
    on it)."""
    from ..parallel.pipeline_lm import init_pipeline_lm
    from ..serve2 import DecodeEngine
    params = init_pipeline_lm(
        int(seed), vocab=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, d_head=d_model // n_heads, d_ff=2 * d_model,
        n_experts=2)
    return DecodeEngine(
        params, page_size=page_size, num_pages=num_pages,
        max_inflight=max_inflight, max_seq_len=max_seq_len,
        prefill_buckets=prefill_buckets,
        prefix_cache=True, pagewire_chunk=pagewire_chunk, name=name)


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from .. import config
    from ..pod.group import PodGroup

    role = os.environ.get("MXFLEET_ROLE", "decode")
    wid = os.environ.get("MXFLEET_WORKER_ID", f"{role}-{os.getpid()}")
    coord = os.environ.get("MXFLEET_COORDINATOR") \
        or os.environ.get("MXPOD_COORDINATOR") or ""
    beat_s = float(config.get("MXFLEET_HEARTBEAT_S"))
    chunk = int(config.get("MXFLEET_PAGEWIRE_CHUNK_PAGES"))

    stopping = {"flag": False}

    def _on_sigterm(signum, frame):
        stopping["flag"] = True

    signal.signal(signal.SIGTERM, _on_sigterm)

    # per-role pool override (FLEET_PAGES_DECODE / FLEET_PAGES_PREFILL):
    # decode hosts size their pool for batch state + their affinity
    # shard of the template set; a prefill host is a cache host and
    # may be provisioned larger
    pages = int(os.environ.get(f"FLEET_PAGES_{role.upper()}")
                or os.environ.get("FLEET_PAGES", "128"))
    buckets = [int(b) for b in
               os.environ.get("FLEET_BUCKETS", "").split(",")
               if b.strip()] or None
    engine = build_engine(
        seed=int(os.environ.get("FLEET_SEED", "0")),
        vocab=int(os.environ.get("FLEET_VOCAB", "64")),
        n_layers=int(os.environ.get("FLEET_LAYERS", "2")),
        d_model=int(os.environ.get("FLEET_D_MODEL", "32")),
        n_heads=int(os.environ.get("FLEET_HEADS", "2")),
        page_size=int(os.environ.get("FLEET_PAGE", "8")),
        num_pages=pages,
        max_inflight=int(os.environ.get("FLEET_INFLIGHT", "4")),
        max_seq_len=int(os.environ.get("FLEET_MAX_SEQ", "96")),
        pagewire_chunk=chunk, name=f"fleet-{wid}",
        prefill_buckets=buckets)
    engine.warmup()
    host = EngineHost(engine, role=role, name=wid,
                      port=int(os.environ.get("FLEET_PORT", "0")),
                      pagewire_chunk=chunk)
    _emit("ready", worker_id=wid, role=role, address=host.address,
          pid=os.getpid())

    group = PodGroup(coord) if coord else None
    registered = False
    try:
        while not stopping["flag"]:
            if group is not None:
                try:
                    if not registered:
                        group.fleet_register(
                            wid, role, host.address,
                            meta={"pid": os.getpid()})
                        registered = True
                        _emit("registered", worker_id=wid)
                    elif not group.fleet_heartbeat(
                            wid, depth=engine.queue_depth()):
                        # restarted coordinator: empty directory —
                        # announce again
                        registered = False
                        continue
                except Exception as e:  # noqa: BLE001 — keep serving
                    # through control-plane outages; the data plane
                    # is independent
                    _emit("control_plane_error",
                          error=str(e)[:200])
                    registered = False
            time.sleep(beat_s)
        engine.drain(float(os.environ.get("FLEET_DRAIN_S", "10")))
        if group is not None and registered:
            try:
                group.fleet_leave(wid)
            except Exception:
                pass
        _emit("stopped", worker_id=wid)
        return 0
    finally:
        host.stop()
        try:
            engine.close()
        except Exception:
            pass
        if group is not None:
            try:
                group.close()
            except Exception:
                pass


if __name__ == "__main__":
    sys.exit(main())
