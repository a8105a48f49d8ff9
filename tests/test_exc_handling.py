"""Exception propagation & failure detection
(ref: tests/python/unittest/test_exc_handling.py + SURVEY.md §5.3).

The reference engine captures std::exception_ptr per-op and rethrows at
wait boundaries (threaded_engine.h:64-65,387); here errors surface at
the dispatch/sync points of the eager layer, through CustomOp python
callbacks, through the kvstore client, and — for failure detection —
at dist barriers (timeout + dead-peer)."""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- op-level propagation ---------------------------------------------------

def test_invalid_op_param_raises():
    a = nd.zeros((2, 3))
    with pytest.raises(Exception):
        nd.reshape(a, shape=(7,)).asnumpy()  # size mismatch


def test_custom_op_exception_propagates():
    """A python CustomOp raising must surface to the caller, not kill a
    worker thread (ref: custom-inl.h push thread + test_exc_handling)."""
    import mxnet_tpu.operator as op_mod

    class Bad(op_mod.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            raise ValueError("custom op boom")

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            pass

    @op_mod.register("bad_op_exc")
    class BadProp(op_mod.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return Bad()

    x = nd.ones((2, 2))
    with pytest.raises(Exception, match="custom op boom"):
        nd.Custom(x, op_type="bad_op_exc").asnumpy()


def test_autograd_backward_through_failing_custom_op():
    """Errors raised inside a custom Function backward surface at
    .backward(), the tape's wait boundary."""
    from mxnet_tpu import autograd

    class BoomFn(autograd.Function):
        def forward(self, x):
            return x * 2

        def backward(self, dy):
            raise RuntimeError("backward boom")

    x = nd.ones((3,))
    x.attach_grad()
    fn = BoomFn()
    with autograd.record():
        y = fn(x)
    with pytest.raises(Exception, match="backward boom"):
        y.backward()


# -- kvstore error + failure-detection tier ---------------------------------

def test_kvstore_server_error_surfaces_to_client():
    from mxnet_tpu.kvstore_server import KVClient, KVServer
    addr = f"127.0.0.1:{_free_port()}"
    server = KVServer(addr, num_workers=1)
    try:
        c = KVClient(addr)
        with pytest.raises(MXNetError, match="not init'd"):
            c.request("pull", key="never_created")
        c.close()
    finally:
        server.stop()


def test_barrier_timeout_detected():
    """SURVEY §5.3: a worker stuck alone at a barrier gets a diagnosis
    on the MXNET_KVSTORE_BARRIER_TIMEOUT deadline instead of hanging."""
    from mxnet_tpu import config
    from mxnet_tpu.kvstore_server import KVClient, KVServer
    addr = f"127.0.0.1:{_free_port()}"
    server = KVServer(addr, num_workers=2)
    config.set_flag("MXNET_KVSTORE_BARRIER_TIMEOUT", 1.5)
    try:
        c = KVClient(addr)
        t0 = time.monotonic()
        with pytest.raises(MXNetError, match="barrier timeout: only 1/2"):
            c.request("barrier")
        assert time.monotonic() - t0 < 30.0
        c.close()
    finally:
        config.unset_flag("MXNET_KVSTORE_BARRIER_TIMEOUT")
        server.stop()


def test_barrier_detects_dead_peer():
    """A peer whose connection drops abnormally releases barrier
    waiters with an error immediately (no need to wait out the full
    timeout) — dead-worker detection at the sync point."""
    from mxnet_tpu import config
    from mxnet_tpu.kvstore_server import KVClient, KVServer
    addr = f"127.0.0.1:{_free_port()}"
    server = KVServer(addr, num_workers=2)
    config.set_flag("MXNET_KVSTORE_BARRIER_TIMEOUT", 60.0)
    try:
        waiter = KVClient(addr)
        err = []

        def wait_barrier():
            try:
                waiter.request("barrier")
            except MXNetError as e:
                err.append(e)

        th = threading.Thread(target=wait_barrier)
        th.start()
        time.sleep(0.3)  # let the waiter arrive at the barrier
        # second worker connects, does some work, then dies abruptly
        peer = KVClient(addr)
        peer.request("init", key="w", payload=onp.zeros(2))
        peer._sock.close()  # no clean 'stop' — simulated crash
        th.join(timeout=20)
        assert not th.is_alive(), "barrier waiter still blocked"
        assert err and "dropped" in str(err[0])
        waiter.close()
    finally:
        config.unset_flag("MXNET_KVSTORE_BARRIER_TIMEOUT")
        server.stop()


def test_barrier_completes_when_all_arrive():
    """The failure-detection path must not break the happy path."""
    from mxnet_tpu.kvstore_server import KVClient, KVServer
    addr = f"127.0.0.1:{_free_port()}"
    server = KVServer(addr, num_workers=2)
    try:
        a, b = KVClient(addr), KVClient(addr)
        done = []
        th = threading.Thread(
            target=lambda: done.append(a.request("barrier")))
        th.start()
        b.request("barrier")
        th.join(timeout=20)
        assert not th.is_alive() and len(done) == 1
        a.close()
        b.close()
    finally:
        server.stop()


def test_server_profiling_commands(tmp_path, monkeypatch):
    """Worker-commanded server profiling (ref: kvstore_dist.h:99
    kSetProfilerParams; tests/nightly/test_server_profiling.py): a
    profiler.set_state(profile_process='server') call must reach the
    parameter server and flip ITS profiler."""
    from mxnet_tpu import profiler
    from mxnet_tpu.kvstore_server import KVServer
    addr = f"127.0.0.1:{_free_port()}"
    server = KVServer(addr, num_workers=1)
    monkeypatch.setenv("MX_KV_SERVER", addr)
    # the server role runs in this process: its profile goes beside the
    # configured file, not into the checkout's root
    profiler.set_config(filename=str(tmp_path / "profile.json"))
    try:
        assert not profiler.is_running()
        profiler.set_state("run", profile_process="server")
        # the server process (here: in-process server role) saw the
        # command and started its profiler
        assert profiler.is_running()
        profiler.set_state("stop", profile_process="server")
        assert not profiler.is_running()
    finally:
        profiler.set_state("stop")
        profiler.set_config(filename="profile.json")
        server.stop()
