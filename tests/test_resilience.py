"""mxresil subsystem tests (ISSUE 4): fault plans, retry/backoff
policies (fake clock — no real sleeping), circuit breaker trip/reset,
deadline propagation, TrainGuard preempt/rollback, watchdog stall
findings in the mxlint schema, checkpoint corruption detection, kvstore
timeout typing, and batcher dispatcher-crash fail-fast.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointManager
from mxnet_tpu.resil import (BackoffSchedule, CircuitBreaker,
                             CircuitOpenError, FaultInjectedError,
                             Preempted, RetryBudget, RetryPolicy,
                             TrainGuard, Watchdog, deadline_scope,
                             faultplan, hooks, remaining_deadline)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_resil_state():
    """Every test starts with no plan, fresh policies/breakers."""
    config.unset_flag("MXRESIL_FAULT_PLAN")
    hooks.reset()
    yield
    config.unset_flag("MXRESIL_FAULT_PLAN")
    hooks.reset()


class FakeClock:
    """Deterministic clock + sleep for schedule/breaker tests."""

    def __init__(self, t0=0.0):
        self.t = float(t0)
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s

    def advance(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------

def test_plan_parses_issue_grammar():
    plan = faultplan.FaultPlan(
        "step:40=preempt;kvstore.push@3=raise;io=stall:200ms")
    sels = [c.describe()["selector"] for c in plan.clauses]
    assert sels == ["step:40", "kvstore.push@3", "io"]
    assert plan.clauses[2].stall_s == pytest.approx(0.2)


def test_plan_rejects_garbage():
    with pytest.raises(MXNetError):
        faultplan.FaultPlan("kvstore.push=explode")
    with pytest.raises(MXNetError):
        faultplan.FaultPlan("not a clause")
    with pytest.raises(MXNetError):
        faultplan.FaultPlan("io=stall")  # stall needs a duration


def test_nth_invocation_clause_fires_exactly_once():
    plan = faultplan.FaultPlan("s@2=raise")
    plan.inject("s")  # 1st: clean
    with pytest.raises(FaultInjectedError):
        plan.inject("s")  # 2nd: fires
    for _ in range(10):
        plan.inject("s")  # 3rd+: clean again
    assert plan.clauses[0].fired == 1


def test_step_clause_matches_step_not_invocation():
    plan = faultplan.FaultPlan("step:5=raise")
    for s in range(5):
        plan.inject("step", step=s)
    with pytest.raises(FaultInjectedError):
        plan.inject("step", step=5)


def test_probabilistic_clause_is_seed_deterministic():
    def fire_pattern(seed):
        plan = faultplan.FaultPlan("s%0.5=nan", seed=seed)
        return [plan.inject("s") == "nan" for _ in range(64)]

    a, b = fire_pattern(7), fire_pattern(7)
    assert a == b  # same seed -> identical fault sequence
    assert fire_pattern(8) != a  # and the seed actually matters
    assert any(a) and not all(a)


def test_inject_is_noop_without_plan():
    assert faultplan.active_plan() is None
    assert faultplan.inject("kvstore.push") is None


def test_active_plan_follows_flag_and_reparses():
    config.set_flag("MXRESIL_FAULT_PLAN", "s@1=nan")
    assert faultplan.active_plan().inject("s") == "nan"
    config.set_flag("MXRESIL_FAULT_PLAN", "t@1=nan")
    plan = faultplan.active_plan()
    assert [c.site for c in plan.clauses] == ["t"]
    config.unset_flag("MXRESIL_FAULT_PLAN")
    assert faultplan.active_plan() is None


# ---------------------------------------------------------------------------
# backoff / retry policy (fake clock, zero real sleeps)
# ---------------------------------------------------------------------------

def test_backoff_schedule_exponential_with_cap():
    b = BackoffSchedule(base_ms=10, max_ms=80, jitter=0.0)
    assert [b.delay(k) for k in range(5)] == \
        pytest.approx([0.01, 0.02, 0.04, 0.08, 0.08])


def test_backoff_jitter_bounded_and_seeded():
    b = BackoffSchedule(base_ms=100, max_ms=1000, jitter=0.5, seed=3)
    ds = [b.delay(0) for _ in range(50)]
    assert all(0.05 <= d <= 0.1 for d in ds)
    b2 = BackoffSchedule(base_ms=100, max_ms=1000, jitter=0.5, seed=3)
    assert ds == [b2.delay(0) for _ in range(50)]


def test_retry_policy_retries_then_succeeds_without_sleeping():
    clk = FakeClock()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise FaultInjectedError("transient")
        return "ok"

    pol = RetryPolicy("t", max_retries=3,
                      backoff=BackoffSchedule(base_ms=10, jitter=0.0),
                      clock=clk, sleep=clk.sleep)
    assert pol.call(flaky) == "ok"
    assert calls["n"] == 3
    assert clk.sleeps == pytest.approx([0.01, 0.02])  # full schedule


def test_retry_policy_gives_up_and_keeps_error_type():
    clk = FakeClock()
    pol = RetryPolicy("t", max_retries=2,
                      backoff=BackoffSchedule(base_ms=1, jitter=0.0),
                      clock=clk, sleep=clk.sleep)

    def always():
        raise FaultInjectedError("down")

    with pytest.raises(FaultInjectedError, match="retries exhausted"):
        pol.call(always)
    assert len(clk.sleeps) == 2


def test_retry_policy_does_not_retry_untyped_errors():
    pol = RetryPolicy("t", max_retries=5)
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise ValueError("a real bug, not a transient")

    with pytest.raises(ValueError):
        pol.call(bug)
    assert calls["n"] == 1


def test_retry_budget_stops_retry_amplification():
    clk = FakeClock()
    budget = RetryBudget(capacity=2.0, refund=0.0)
    pol = RetryPolicy("t", max_retries=10,
                      backoff=BackoffSchedule(base_ms=1, jitter=0.0),
                      budget=budget, clock=clk, sleep=clk.sleep)

    def always():
        raise FaultInjectedError("down")

    with pytest.raises(FaultInjectedError, match="budget exhausted"):
        pol.call(always)
    assert budget.tokens < 1.0


def test_deadline_propagation_caps_retries():
    clk = FakeClock()
    pol = RetryPolicy("t", max_retries=50,
                      backoff=BackoffSchedule(base_ms=100, jitter=0.0),
                      clock=clk, sleep=clk.sleep)

    def always():
        raise FaultInjectedError("down")

    with deadline_scope(0.25, clock=clk):
        with pytest.raises(FaultInjectedError, match="deadline"):
            pol.call(always)
    # 0.1 + 0.2 would blow the 0.25s deadline -> gave up on retry 2
    assert clk.sleeps == pytest.approx([0.1])


def test_deadline_scopes_nest_and_only_shrink():
    clk = FakeClock()
    with deadline_scope(10.0, clock=clk):
        with deadline_scope(1.0, clock=clk):
            assert remaining_deadline(clk) == pytest.approx(1.0)
        # inner scope popped; outer deadline still active
        assert remaining_deadline(clk) == pytest.approx(10.0)
    assert remaining_deadline(clk) is None


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_trips_cools_down_probes_and_resets():
    clk = FakeClock()
    brk = CircuitBreaker("t", failure_threshold=3, cooldown_s=10.0,
                         clock=clk)
    for _ in range(3):
        brk.check()
        brk.record_failure()
    assert brk.state == "open"
    with pytest.raises(CircuitOpenError):
        brk.check()  # fail fast while open
    clk.advance(10.1)
    assert brk.state == "half_open"
    brk.check()  # the single probe is admitted...
    with pytest.raises(CircuitOpenError):
        brk.check()  # ...a second concurrent call is not
    brk.record_success()
    assert brk.state == "closed"
    brk.check()


def test_breaker_straggler_success_does_not_cancel_cooldown():
    """A success from a call admitted BEFORE the trip must not re-close
    an open breaker — only the half-open probe may."""
    clk = FakeClock()
    brk = CircuitBreaker("t", failure_threshold=2, cooldown_s=10.0,
                         clock=clk)
    brk.record_failure()
    brk.record_failure()
    assert brk.state == "open"
    brk.record_success()  # straggler resolves late
    assert brk.state == "open"
    with pytest.raises(CircuitOpenError):
        brk.check()


def test_breaker_retrips_from_failed_probe():
    clk = FakeClock()
    brk = CircuitBreaker("t", failure_threshold=2, cooldown_s=5.0,
                         clock=clk)
    brk.record_failure()
    brk.record_failure()
    clk.advance(5.1)
    brk.check()  # half-open probe
    brk.record_failure()  # probe fails -> straight back to open
    assert brk.state == "open"
    with pytest.raises(CircuitOpenError):
        brk.check()


def test_breaker_abandoned_probe_slot_expires():
    """A half-open probe whose caller never reports back must not wedge
    the breaker: the slot expires after another cooldown."""
    clk = FakeClock()
    brk = CircuitBreaker("t", failure_threshold=1, cooldown_s=5.0,
                         clock=clk)
    brk.record_failure()
    clk.advance(5.1)
    brk.check()  # probe admitted... and then abandoned (no outcome)
    with pytest.raises(CircuitOpenError):
        brk.check()
    clk.advance(5.1)
    brk.check()  # stale slot released: a NEW probe is admitted
    brk.record_success()
    assert brk.state == "closed"


def test_predict_async_records_breaker_outcome_on_completion():
    """predict_async futures report their outcome back to the breaker
    when they RESOLVE — async-only clients both trip and heal it."""
    from mxnet_tpu import serve

    state = {"fail": True}

    def model(x):
        if state["fail"]:
            raise RuntimeError("model down")
        return x * 2

    engine = serve.ServingEngine(model, input_specs=[(4,)],
                                 ladder=serve.parse_bucket_spec("1,2"),
                                 name="async-breaker",
                                 max_linger_ms=1.0)
    x = onp.ones((1, 4), "float32")
    threshold = int(config.get("MXRESIL_BREAKER_FAILURES"))
    for _ in range(threshold):
        req = engine.predict_async(x)
        assert req.wait(30.0)
        assert isinstance(req.error, RuntimeError)
    with pytest.raises(CircuitOpenError):  # completions tripped it
        engine.predict_async(x)
    # recovery through the async path alone
    state["fail"] = False
    hooks.site_breaker("serve.submit").cooldown_s = 0.0
    req = engine.predict_async(x)  # the half-open probe
    assert req.wait(30.0) and req.error is None
    assert hooks.site_breaker("serve.submit").state == "closed"
    assert engine.predict_async(x).wait(30.0)
    engine.close()


def test_engine_breaker_degrades_serving_and_recovers():
    from mxnet_tpu import serve

    net = mx.gluon.nn.Dense(4, flatten=False)
    net.initialize()
    net(nd.zeros((1, 8)))
    engine = serve.ServingEngine(net, input_specs=[(8,)],
                                 ladder=serve.parse_bucket_spec("1,2"),
                                 batching=False, name="resil-test")
    x = onp.ones((1, 8), "float32")
    assert engine.predict(x).shape == (1, 4)
    # trip the submit breaker via injected faults (every call fails)
    config.set_flag("MXRESIL_FAULT_PLAN", "serve.submit=raise")
    threshold = int(config.get("MXRESIL_BREAKER_FAILURES"))
    for _ in range(threshold):
        with pytest.raises(FaultInjectedError):
            engine.predict(x)
    with pytest.raises(CircuitOpenError):  # open: degraded fail-fast
        engine.predict(x)
    config.unset_flag("MXRESIL_FAULT_PLAN")
    with pytest.raises(CircuitOpenError):  # still cooling down
        engine.predict(x)
    hooks.site_breaker("serve.submit").cooldown_s = 0.0
    assert engine.predict(x).shape == (1, 4)  # probe passes -> closed
    assert hooks.site_breaker("serve.submit").state == "closed"
    engine.close()


# ---------------------------------------------------------------------------
# wired sites: kvstore, io, checkpoint
# ---------------------------------------------------------------------------

def test_kvstore_push_injection_is_retried_and_converges():
    config.set_flag("MXRESIL_FAULT_PLAN", "kvstore.push@2=raise")
    kv = mx.kv.create("local")
    kv.init("w", nd.zeros((2, 2)))
    kv.push("w", nd.ones((2, 2)))
    kv.push("w", nd.ones((2, 2)))  # injected once, retried, applied once
    out = nd.zeros((2, 2))
    kv.pull("w", out=out)
    assert onp.array_equal(out.asnumpy(), onp.full((2, 2), 2.0))
    from mxnet_tpu.telemetry import metrics
    assert metrics.counter("mxresil_retries_total").value() >= 1


def test_kvstore_clean_path_records_zero_retries():
    from mxnet_tpu.telemetry import metrics
    before = metrics.counter("mxresil_retries_total").value()
    kv = mx.kv.create("local")
    kv.init("w", nd.zeros((2, 2)))
    for _ in range(10):
        kv.push("w", nd.ones((2, 2)))
    out = nd.zeros((2, 2))
    kv.pull("w", out=out)
    assert metrics.counter("mxresil_retries_total").value() == before


def test_kvstore_timeout_is_typed_and_retryable():
    from mxnet_tpu.kvstore import KVStoreTimeoutError
    from mxnet_tpu.kvstore_server import KVClient
    from mxnet_tpu.resil.policy import RetryableError

    assert issubclass(KVStoreTimeoutError, RetryableError)
    # a listener that accepts and never replies: the data-plane request
    # must time out with the typed error instead of hanging
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]
    config.set_flag("MXNET_KVSTORE_TIMEOUT_MS", 150.0)
    try:
        client = KVClient(f"127.0.0.1:{port}")
        t0 = time.monotonic()
        with pytest.raises(KVStoreTimeoutError):
            client.request("pull", "w")
        assert time.monotonic() - t0 < 5.0  # did not sit out 300s+
    finally:
        config.unset_flag("MXNET_KVSTORE_TIMEOUT_MS")
        srv.close()


def test_kvstore_timeout_honors_deadline_scope():
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]
    from mxnet_tpu.kvstore import KVStoreTimeoutError
    from mxnet_tpu.kvstore_server import KVClient
    try:
        client = KVClient(f"127.0.0.1:{port}")
        t0 = time.monotonic()
        with deadline_scope(0.2):  # no flag set: the deadline caps it
            with pytest.raises(KVStoreTimeoutError):
                client.request("pull", "w")
        assert time.monotonic() - t0 < 5.0
    finally:
        srv.close()


def test_prefetch_iter_survives_injected_io_fault():
    from mxnet_tpu.io import NDArrayIter, PrefetchingIter

    config.set_flag("MXRESIL_FAULT_PLAN", "io@1=raise")
    base = NDArrayIter(onp.arange(32, dtype="float32").reshape(8, 4),
                       onp.zeros((8,), "float32"), batch_size=2)
    it = PrefetchingIter(base)
    # the injected worker fault ships through the sentinel and re-raises
    # at next() — the consumer is never stranded on an empty queue
    with pytest.raises(FaultInjectedError):
        while True:
            it.next()


def test_checkpoint_detects_truncation_and_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    w = onp.arange(16, dtype="float32").reshape(4, 4)
    mgr.save(1, params={"w": nd.array(w)})
    mgr.save(2, params={"w": nd.array(w * 2)})
    with open(os.path.join(str(tmp_path), "step_2", "params"),
              "r+b") as f:
        f.truncate(8)
    with pytest.raises(MXNetError, match="truncated|corrupt"):
        mgr.restore(2)
    assert mgr.restore_latest() == 1  # newest INTACT step
    params, _, _ = mgr.restore(1)
    assert onp.array_equal(params["w"].asnumpy(), w)


def test_checkpoint_detects_content_corruption_same_size(tmp_path):
    """Same-size corruption that the loader itself cannot see: the
    loaded arrays no longer match the manifest's per-array digests."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, params={"w": nd.array(onp.zeros((4, 4), "float32"))})
    # rewrite the checkpoint's params with DIFFERENT values of the same
    # shape/dtype (a valid container, wrong bytes — what a partial
    # overwrite or mirrored-write race leaves behind)
    from mxnet_tpu.ndarray import ndarray as nd_mod
    path = os.path.join(str(tmp_path), "step_1", "params")
    size_before = os.path.getsize(path)
    nd_mod.save(path, {"w": nd.array(onp.ones((4, 4), "float32"))})
    assert os.path.getsize(path) == size_before
    with pytest.raises(MXNetError, match="digest|corrupt"):
        mgr.restore(1)
    assert mgr.restore_latest() is None


def test_checkpoint_digest_survives_dtype_canonicalization(tmp_path):
    """Digests are computed from the canonicalized arrays that hit the
    disk: int64/float64 host params (narrowed by jax with x64 off) must
    still restore cleanly."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, params={"w": onp.arange(6),           # int64 host array
                        "b": onp.ones(3, "float64")})
    params, _, _ = mgr.restore(1)  # must not trip the digest check
    assert onp.array_equal(params["w"].asnumpy(), onp.arange(6))
    assert mgr.restore_latest() == 1


def test_checkpoint_write_fault_is_retried(tmp_path):
    config.set_flag("MXRESIL_FAULT_PLAN", "checkpoint.write@1=raise")
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, params={"w": nd.array(onp.ones((2, 2), "float32"))})
    mgr.wait()  # must NOT raise: the injected fault was absorbed
    assert mgr.all_steps() == [3]


def test_checkpoint_restore_transient_fault_is_retried(tmp_path):
    """A transient restore fault must be absorbed by the site policy —
    NOT silently demote resume to an older checkpoint."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, params={"w": nd.array(onp.zeros((2, 2), "float32"))})
    mgr.save(2, params={"w": nd.array(onp.ones((2, 2), "float32"))})
    config.set_flag("MXRESIL_FAULT_PLAN", "checkpoint.restore@1=raise")
    assert mgr.restore_latest() == 2  # newest, despite the fault
    from mxnet_tpu.telemetry import metrics
    assert metrics.counter("mxresil_retries_total").value() >= 1


# ---------------------------------------------------------------------------
# TrainGuard
# ---------------------------------------------------------------------------

def _guarded_loop(mgr, w, target, preempt_at=None, ckpt_every=5):
    params_fn = lambda: {"w": nd.array(w["v"])}  # noqa: E731
    with TrainGuard(mgr, params_fn=params_fn,
                    checkpoint_every=ckpt_every) as guard:
        start = guard.resume()
        for step in range(start, target):
            w["v"] = w["v"] + 1.0
            if step == preempt_at:
                os.kill(os.getpid(), signal.SIGTERM)
            guard.completed(step, loss=float(w["v"].sum()))
    return start


def test_guard_sigterm_commits_emergency_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    w = {"v": onp.zeros((2, 2), "float32")}
    with pytest.raises(Preempted) as exc:
        _guarded_loop(mgr, w, target=100, preempt_at=12)
    assert exc.value.step == 12
    mgr2 = CheckpointManager(str(tmp_path))
    _, _, extra = mgr2.restore(mgr2.latest_step())
    assert extra["emergency"] is True
    assert extra["next_step"] == 13  # steps lost on restart: 0
    # restart resumes exactly where the emergency checkpoint left off
    w2 = {"v": onp.zeros((2, 2), "float32")}
    start = _guarded_loop(mgr2, w2, target=20)
    assert start == 13


def test_guard_restores_prior_signal_handlers(tmp_path):
    prev = signal.getsignal(signal.SIGTERM)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    with TrainGuard(mgr, params_fn=lambda: {}) as _:
        assert signal.getsignal(signal.SIGTERM) != prev
    assert signal.getsignal(signal.SIGTERM) == prev


def test_guard_rolls_back_nonfinite_loss(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    w = {"v": onp.zeros((2, 2), "float32")}
    params_fn = lambda: {"w": nd.array(w["v"])}  # noqa: E731
    restored = []

    def restore_fn(params, _opt, _extra):
        w["v"] = params["w"].asnumpy()
        restored.append(True)

    from mxnet_tpu.telemetry import metrics
    rb0 = metrics.counter("mxresil_rollbacks_total").value()
    with TrainGuard(mgr, params_fn=params_fn, restore_fn=restore_fn,
                    checkpoint_every=1) as guard:
        assert guard.completed(0, loss=1.0)
        w["v"] = w["v"] + 99.0  # the diverged update...
        assert not guard.completed(1, loss=float("nan"))
        assert onp.array_equal(w["v"], onp.zeros((2, 2)))  # ...undone
        assert restored
        assert guard.completed(2, loss=2.0)  # streak reset
    assert metrics.counter("mxresil_nonfinite_steps_total").value() >= 1
    assert metrics.counter("mxresil_rollbacks_total").value() == rb0 + 1


def test_guard_params_fn_without_restore_fn_skips_not_rolls(tmp_path):
    """Without a restore channel the guard cannot install state — it
    must report a SKIP (False, no rollback counted), never claim a
    rollback it did not perform."""
    from mxnet_tpu.telemetry import metrics
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    rb0 = metrics.counter("mxresil_rollbacks_total").value()
    with TrainGuard(mgr, params_fn=lambda: {"w": nd.zeros((1,))},
                    checkpoint_every=1) as guard:
        assert guard.completed(0, loss=1.0)
        assert not guard.completed(1, loss=float("nan"))
    assert metrics.counter("mxresil_rollbacks_total").value() == rb0


def test_guard_raises_after_consecutive_divergence(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    with TrainGuard(mgr, params_fn=lambda: {"w": nd.zeros((1,))},
                    checkpoint_every=1, nonfinite_limit=2) as guard:
        guard.completed(0, loss=0.0)
        with pytest.raises(MXNetError, match="diverged"):
            for s in range(1, 10):
                guard.completed(s, loss=float("inf"))


def test_guard_step_fault_plan_nan_drill(tmp_path):
    config.set_flag("MXRESIL_FAULT_PLAN", "step:1=nan")
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    with TrainGuard(mgr, params_fn=lambda: {"w": nd.zeros((1,))},
                    checkpoint_every=1) as guard:
        assert guard.completed(0, loss=0.5)
        assert not guard.completed(1, loss=0.5)  # plan poisoned it


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_stall_finding_in_mxlint_schema():
    clk = FakeClock()
    wd = Watchdog(stall_after_s=5.0, clock=clk)
    wd.beat(step_seconds=0.1)
    assert wd.check() == []
    clk.advance(6.0)
    findings = wd.check()
    assert [f.check for f in findings] == ["stall"]
    d = findings[0].to_dict()
    assert d["pass"] == "watchdog" and d["severity"] == "error"
    assert set(d) >= {"pass", "check", "obj", "severity", "message"}
    wd.beat()
    assert wd.check() == []  # heartbeat clears the stall


def test_watchdog_auto_threshold_tracks_step_ewma():
    clk = FakeClock()
    wd = Watchdog(stall_after_s=0.0, stall_factor=10.0, clock=clk)
    for _ in range(20):
        wd.beat(step_seconds=0.5)
    assert wd.stall_threshold_s() == pytest.approx(5.0, rel=0.05)
    clk.advance(4.0)
    assert wd.check() == []  # under 10x EWMA: slow, not stalled
    clk.advance(2.0)
    assert [f.check for f in wd.check()] == ["stall"]


def test_watchdog_poll_synthesizes_beats_from_registry():
    from mxnet_tpu.telemetry import metrics
    clk = FakeClock()
    wd = Watchdog(stall_after_s=3.0, clock=clk)
    ctr = metrics.counter("trainer_step_total", "steps")
    wd.poll()
    ctr.inc()
    wd.poll()  # progress observed -> heartbeat
    clk.advance(1.0)
    assert wd.check() == []
    clk.advance(3.0)
    assert [f.check for f in wd.check()] == ["stall"]


def test_watchdog_reports_open_breaker():
    clk = FakeClock()
    brk = hooks.site_breaker("kvstore.push")
    for _ in range(brk.failure_threshold):
        brk.record_failure()
    wd = Watchdog(stall_after_s=1000.0, clock=clk)
    findings = wd.check()
    assert [f.check for f in findings] == ["breaker_open"]
    assert findings[0].severity == "warn"


# ---------------------------------------------------------------------------
# batcher dispatcher-crash fail-fast
# ---------------------------------------------------------------------------

def test_batcher_dispatcher_crash_fails_futures_fast():
    from mxnet_tpu.serve.batcher import BatcherStoppedError, DynamicBatcher

    b = DynamicBatcher(lambda key, reqs: [None] * len(reqs),
                       max_batch_size=4, max_linger_ms=5.0,
                       queue_depth=16, name="crash-test")
    # break the dispatcher OUTSIDE the per-group dispatch_fn guard —
    # the occupancy observe runs after dispatch in the loop body.
    # _m_occ is the process-global registry histogram: restore it.
    def boom(*_a, **_k):
        raise RuntimeError("dispatcher thread died")
    saved = b._m_occ.observe
    b._m_occ.observe = boom
    try:
        t0 = time.monotonic()
        with pytest.raises(BatcherStoppedError, match="crashed"):
            # no timeout_ms: before the fix this would hang forever
            b.submit([onp.zeros((1, 2), "float32")], 1, ("k",), None)
        assert time.monotonic() - t0 < 5.0
        # and the batcher stays failed-fast for later submitters
        with pytest.raises(BatcherStoppedError, match="crashed"):
            b.submit([onp.zeros((1, 2), "float32")], 1, ("k",), None)
    finally:
        b._m_occ.observe = saved


def test_batcher_dispatch_exception_still_fails_group():
    from mxnet_tpu.serve.batcher import DynamicBatcher

    b = DynamicBatcher(
        lambda key, reqs: (_ for _ in ()).throw(RuntimeError("model")),
        max_batch_size=4, max_linger_ms=1.0, queue_depth=16,
        name="exc-test")
    with pytest.raises(RuntimeError, match="model"):
        b.submit([onp.zeros((1, 2), "float32")], 1, ("k",), None)
    b.stop()


# ---------------------------------------------------------------------------
# CLI + schema integration
# ---------------------------------------------------------------------------

def test_mxresil_plan_cli_roundtrip():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxresil.py"),
         "plan", "--plan", "kvstore.push@3=raise;io=stall:50ms",
         "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert len(rep["clauses"]) == 2


def test_mxresil_watch_cli_emits_findings_schema():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxresil.py"),
         "watch", "--json"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "MXTPU_FORCE_CPU_BACKEND": "1"})
    assert out.returncode in (0, 2), out.stderr
    rep = json.loads(out.stdout)
    assert rep["tool"] == "mxresil.watch"
    assert "findings" in rep and "summary" in rep


def test_resil_flags_registered_and_documented():
    for name in ("MXRESIL_FAULT_PLAN", "MXRESIL_SEED",
                 "MXRESIL_RETRY_MAX", "MXRESIL_RETRY_BASE_MS",
                 "MXRESIL_RETRY_MAX_MS", "MXRESIL_BREAKER_FAILURES",
                 "MXRESIL_BREAKER_COOLDOWN_S",
                 "MXRESIL_WATCHDOG_STALL_S",
                 "MXNET_KVSTORE_TIMEOUT_MS"):
        assert name in config.flags(), name
    doc = open(os.path.join(ROOT, "docs", "env_vars.md")).read()
    assert "MXRESIL_FAULT_PLAN" in doc
    assert "MXNET_KVSTORE_TIMEOUT_MS" in doc


@pytest.mark.slow
def test_mxresil_drill_preempt_acceptance():
    """The ISSUE acceptance drill: preempt at step 40, restart, resume
    from the emergency checkpoint with <=1 step lost and bitwise-equal
    final params vs an uninterrupted run."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxresil.py"),
         "drill", "--plan", "step:40=preempt", "--steps", "60",
         "--step-sleep", "0.005"],
        capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["restarts"] == 1
    assert rec["steps_lost"] <= 1
    assert rec["bitwise_equal"] is True
