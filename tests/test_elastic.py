"""Elastic fault drills (VERDICT r2 item 8 + ISSUE 4): kill or preempt
a worker mid-epoch, restart it (the cluster-manager role), and assert
it resumes from the latest checkpoint and the job completes — survivors
keep training throughout (dist_async: no barrier to wedge).

Three drills:

- SIGKILL a dist worker (hard crash: nothing runs, resume is from the
  last PERIODIC checkpoint);
- SIGTERM the resil drill worker (graceful preemption: TrainGuard
  commits an EMERGENCY checkpoint at the step boundary, exit 42, and
  the restart loses <= 1 step);
- corrupt-checkpoint restore (the newest checkpoint is truncated after
  the kill; the restart falls back to the newest INTACT step instead of
  crashing on torn weights).

All three spawn subprocess workers and are ``slow`` (tier-1 runs them
in the nightly lane; the single-process resilience unit tests live in
tests/test_resilience.py).

Ref: SURVEY §5.3 failure detection / §5.4 checkpoint-resume; the
reference's analogous tier is tests/nightly restarts under yarn/k8s.
"""
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "nightly", "elastic_worker.py")
RESIL_WORKER = os.path.join(ROOT, "tests", "nightly", "resil_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(rank, env):
    e = dict(env)
    e["MX_WORKER_ID"] = str(rank)
    return subprocess.Popen([sys.executable, WORKER], env=e,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


@pytest.mark.slow
def test_sigkill_worker_restarts_from_checkpoint(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MX_KV_SERVER": f"127.0.0.1:{port}",
        "MX_NUM_WORKERS": "2",
        "ELASTIC_CKPT_DIR": str(tmp_path),
        "ELASTIC_TARGET_STEPS": "400",
        "ELASTIC_CKPT_EVERY": "5",
        "ELASTIC_STEP_SLEEP": "0.15",
    })

    w0 = _spawn(0, env)
    w1 = _spawn(1, env)
    # kill as soon as rank 1 has committed at least one checkpoint —
    # guaranteed mid-epoch (400 steps x 0.15 s leaves plenty of runway)
    ckpt1 = os.path.join(str(tmp_path), "rank1")
    deadline = time.time() + 180
    while time.time() < deadline:
        if os.path.isdir(ckpt1) and any(
                d.startswith("step_") for d in os.listdir(ckpt1)):
            break
        if w1.poll() is not None:
            raise AssertionError(w1.communicate()[0][-2000:])
        time.sleep(0.5)
    else:
        raise AssertionError("rank 1 never wrote a checkpoint")
    time.sleep(1.0)  # a little further into the epoch
    assert w1.poll() is None, w1.communicate()[0][-2000:]
    os.kill(w1.pid, signal.SIGKILL)  # mid-epoch hard kill
    w1.wait()
    out1_first = w1.communicate()[0]

    # rank 0 must SURVIVE the peer death (async: no barrier to wedge)
    time.sleep(2)
    assert w0.poll() is None or w0.returncode == 0, \
        w0.communicate()[0][-2000:]

    # the cluster-manager role: restart the SAME worker command
    w1b = _spawn(1, env)
    out1 = w1b.communicate(timeout=300)[0]
    assert w1b.returncode == 0, out1[-2000:]
    out0 = w0.communicate(timeout=300)[0]
    assert w0.returncode == 0, out0[-2000:]

    # fresh boot started at 0; the restart resumed PAST it
    assert "RESUMED rank=1 from=0" in out1_first
    resumed = [ln for ln in out1.splitlines()
               if ln.startswith("RESUMED rank=1")]
    assert resumed, out1[-1000:]
    from_step = int(resumed[0].split("from=")[1])
    assert from_step > 0, "restart did not resume from a checkpoint"
    assert f"DONE rank=1 ran={400 - from_step}" in out1
    assert "DONE rank=0 ran=400" in out0


def _run_resil_worker(env, timeout=240):
    proc = subprocess.run([sys.executable, RESIL_WORKER], env=env,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          timeout=timeout)
    return proc.returncode, proc.stdout


def _resil_env(tmp_path, target=60, sleep=0.02):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXRESIL_FAULT_PLAN", None)
    env.update({
        "RESIL_CKPT_DIR": str(tmp_path),
        "RESIL_TARGET_STEPS": str(target),
        "RESIL_CKPT_EVERY": "5",
        "RESIL_STEP_SLEEP": str(sleep),
    })
    return env


@pytest.mark.slow
def test_sigterm_graceful_preempt_resumes_with_bounded_loss(tmp_path):
    """Graceful preemption: SIGTERM mid-run -> TrainGuard emergency
    checkpoint + exit(42); the restart resumes with <= 1 step lost and
    finishes with the same params as an uninterrupted run."""
    # uninterrupted reference for the bitwise check
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    rc, out = _run_resil_worker(_resil_env(ref_dir))
    assert rc == 0, out[-2000:]
    ref_final = [ln for ln in out.splitlines()
                 if ln.startswith("FINAL")][0]

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    env = _resil_env(run_dir)
    proc = subprocess.Popen([sys.executable, RESIL_WORKER], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    # preempt once the worker is mid-run (a checkpoint exists)
    deadline = time.time() + 120
    while time.time() < deadline:
        if any(d.startswith("step_") for d in os.listdir(run_dir)):
            break
        if proc.poll() is not None:
            raise AssertionError(proc.communicate()[0][-2000:])
        time.sleep(0.2)
    else:
        raise AssertionError("worker never wrote a checkpoint")
    os.kill(proc.pid, signal.SIGTERM)
    out1 = proc.communicate(timeout=120)[0]
    assert proc.returncode == 42, out1[-2000:]  # graceful preempt exit
    preempted = [ln for ln in out1.splitlines()
                 if ln.startswith("PREEMPTED step=")]
    assert preempted, out1[-1000:]
    executed = int(preempted[0].split("=")[1]) + 1

    # cluster-manager role: restart the same command
    rc, out2 = _run_resil_worker(env)
    assert rc == 0, out2[-2000:]
    resumed = int([ln for ln in out2.splitlines()
                   if ln.startswith("RESUMED from=")][0].split("=")[1])
    assert executed - resumed <= 1  # emergency ckpt bounds the loss
    final = [ln for ln in out2.splitlines()
             if ln.startswith("FINAL")][0]
    assert final == ref_final  # bitwise-equal post-resume params


@pytest.mark.slow
def test_corrupt_checkpoint_restore_falls_back(tmp_path):
    """Kill the worker, truncate its NEWEST checkpoint (a torn write),
    and assert the restart resumes from an older INTACT step instead of
    crashing on corrupt weights."""
    env = _resil_env(tmp_path, target=1000, sleep=0.02)
    proc = subprocess.Popen([sys.executable, RESIL_WORKER], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 120
    while time.time() < deadline:
        steps = sorted(int(d[5:]) for d in os.listdir(tmp_path)
                       if d.startswith("step_"))
        if len(steps) >= 2:
            break
        if proc.poll() is not None:
            raise AssertionError(proc.communicate()[0][-2000:])
        time.sleep(0.2)
    else:
        raise AssertionError("worker never wrote two checkpoints")
    proc.kill()
    proc.wait()

    steps = sorted(int(d[5:]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    newest = steps[-1]
    with open(os.path.join(tmp_path, f"step_{newest}", "params"),
              "r+b") as f:
        f.truncate(8)

    env["RESIL_TARGET_STEPS"] = str(newest + 10)  # finish quickly
    rc, out = _run_resil_worker(env)
    assert rc == 0, out[-2000:]
    resumed = int([ln for ln in out.splitlines()
                   if ln.startswith("RESUMED from=")][0].split("=")[1])
    assert resumed in steps[:-1]  # an older intact step, not 0,
    assert resumed != newest      # and NOT the corrupt newest


# ===========================================================================
# Tier-1 elastic-membership tests (ISSUE 9): the generation protocol
# driven by in-memory fake workers — fake clock, no sockets, no sleeps
# for correctness (bounded cv ticks only). The subprocess drills above
# stay in the slow lane.
# ===========================================================================
import threading

import numpy as onp
import pytest as _pytest

from mxnet_tpu.elastic import (ElasticCoordinator, ElasticSession,
                               GroupFailed, MembershipChanged,
                               MembershipTracker, WorkerEvicted)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _coordinator(clock, hb=1.0, miss=3, min_world=1, timeout=30.0):
    tr = MembershipTracker(heartbeat_interval_s=hb, miss_limit=miss,
                           min_world=min_world, clock=clock)
    return ElasticCoordinator(tracker=tr, timeout_s=timeout,
                              tick_s=0.002)


def _spawn(fn, *args):
    th = threading.Thread(target=fn, args=args, daemon=True)
    th.start()
    return th


# -- tracker unit behavior --------------------------------------------------

def test_tracker_generation_monotone_and_heartbeat_policy():
    clock = FakeClock()
    tr = MembershipTracker(heartbeat_interval_s=1.0, miss_limit=3,
                           min_world=1, clock=clock)
    v1 = tr.join("a")
    v2 = tr.join("b")
    assert v2.generation > v1.generation
    assert v2.workers == ("a", "b") and v2.leader == "a"
    clock.advance(2.0)
    tr.heartbeat("a")          # a stays fresh
    clock.advance(1.5)         # b is now 3.5s silent (> 3.0 budget)
    lost = tr.check()
    assert lost == ["b"]
    v3 = tr.view()
    assert v3.workers == ("a",) and v3.generation == v2.generation + 1
    # the evicted worker cannot resume its old identity
    with _pytest.raises(WorkerEvicted):
        tr.heartbeat("b")
    # one check with several stale members = ONE bump
    tr.join("c")
    tr.join("d")
    gen = tr.generation
    clock.advance(10.0)
    tr.heartbeat("a")
    assert sorted(tr.check()) == ["c", "d"]
    assert tr.generation == gen + 1


def test_tracker_min_world_hard_fail():
    clock = FakeClock()
    tr = MembershipTracker(heartbeat_interval_s=1.0, miss_limit=3,
                           min_world=2, clock=clock)
    tr.join("a")
    tr.join("b")
    tr.leave("b")  # world 1 < min 2
    with _pytest.raises(GroupFailed):
        tr.heartbeat("a")


# -- the coordinator: leave / lost fencing ---------------------------------

def test_leave_fences_inflight_reduce_and_survivors_rebuild():
    clock = FakeClock()
    co = _coordinator(clock)
    for w in ("a", "b", "c"):
        co.register(w)
    gen = co.view().generation

    # a full round reduces deterministically (sorted-worker fold, SUM)
    out = {}

    def contribute(wid, val):
        out[wid] = co.allreduce(wid, gen, 0, "g", onp.full(3, val))

    ths = [_spawn(contribute, w, v)
           for w, v in (("a", 1.0), ("b", 2.0), ("c", 4.0))]
    for th in ths:
        th.join(10)
    assert all((out[w] == 7.0).all() for w in ("a", "b", "c"))

    # worker c leaves with a round in flight: a and b get the typed
    # fence, not a wedge
    errs = {}

    def fenced(wid):
        try:
            co.allreduce(wid, gen, 1, "g", onp.ones(3))
        except MembershipChanged as e:
            errs[wid] = e

    ths = [_spawn(fenced, w) for w in ("a", "b")]
    import time as _t
    _t.sleep(0.05)  # both blocked in the round (bounded: just entry)
    co.leave("c")
    for th in ths:
        th.join(10)
    assert set(errs) == {"a", "b"}
    assert all(e.generation == gen + 1 for e in errs.values())

    # the survivors agree at the rebuild barrier and the next round
    # reduces over the shrunken set
    views = {}

    def rebuild_then_reduce(wid, val):
        views[wid] = co.rebuild_barrier(wid)
        out[wid] = co.allreduce(wid, views[wid].generation, 0, "g",
                                onp.full(2, val))

    ths = [_spawn(rebuild_then_reduce, w, v)
           for w, v in (("a", 1.0), ("b", 2.0))]
    for th in ths:
        th.join(10)
    assert views["a"].workers == ("a", "b")
    assert views["a"].generation == views["b"].generation
    assert (out["a"] == 3.0).all() and (out["b"] == 3.0).all()


def test_missed_heartbeats_convert_blocked_wait_into_fence():
    clock = FakeClock()
    co = _coordinator(clock)
    co.register("a")
    co.register("b")
    gen = co.view().generation
    got = {}

    def waiter():
        try:
            co.allreduce("a", gen, 0, "g", onp.ones(2))
        except MembershipChanged as e:
            got["a"] = e

    th = _spawn(waiter)
    import time as _t
    _t.sleep(0.05)
    clock.advance(100.0)  # b silent; a's wait ticks keep beating a
    th.join(10)
    assert isinstance(got["a"], MembershipChanged)
    assert co.view().workers == ("a",)


def test_double_leave_two_bumps_single_survivor_continues():
    clock = FakeClock()
    co = _coordinator(clock)
    for w in ("a", "b", "c"):
        co.register(w)
    gen = co.view().generation
    seen = []

    def survivor():
        g = gen
        while True:
            try:
                out = co.allreduce("a", g, 0, "g", onp.ones(1))
                seen.append((g, float(out[0])))
                return
            except MembershipChanged:
                g = co.rebuild_barrier("a").generation

    th = _spawn(survivor)
    import time as _t
    _t.sleep(0.03)
    co.leave("b")
    _t.sleep(0.03)
    co.leave("c")
    th.join(10)
    # survived BOTH bumps; the final round was a world-1 reduce
    assert seen and seen[0][1] == 1.0
    assert co.view().workers == ("a",)
    assert co.view().generation >= gen + 2


def test_leave_during_rebuild_reforms_barrier():
    clock = FakeClock()
    co = _coordinator(clock)
    for w in ("a", "b", "c"):
        co.register(w)
    co.leave("c")  # first bump: a and b head for the barrier
    views = {}
    release_b = threading.Event()

    def worker_a():
        views["a"] = co.rebuild_barrier("a")

    def worker_b():
        release_b.wait(10)
        views["b"] = co.rebuild_barrier("b")

    tha, thb = _spawn(worker_a), _spawn(worker_b)
    import time as _t
    _t.sleep(0.05)  # a is waiting at the gen+1 barrier, b not yet
    # d joins mid-rebuild: the barrier must RE-FORM at the newer
    # generation instead of completing without d
    co.register("d")
    release_b.set()
    deadline = _t.time() + 10
    while "d" not in views and _t.time() < deadline:
        try:
            views["d"] = co.rebuild_barrier("d")
        except MembershipChanged:
            continue
    tha.join(10)
    thb.join(10)
    assert views["a"].workers == ("a", "b", "d")
    assert views["a"].generation == views["b"].generation \
        == views["d"].generation


# -- rejoin via group state sync -------------------------------------------

def test_rejoin_admitted_with_leader_state_and_one_bump():
    clock = FakeClock()
    co = _coordinator(clock)
    co.register("a")
    co.register("b")
    gen = co.view().generation
    got = {}

    def joiner():
        co.announce_join("x")
        view, state, meta = co.wait_admitted("x")
        got["view"], got["state"], got["meta"] = view, state, meta
        got["barrier"] = co.rebuild_barrier("x")

    th = _spawn(joiner)
    import time as _t
    _t.sleep(0.03)
    view, flags = co.heartbeat("a")
    assert flags["pending_join"]
    admitted = co.admit_joiners("a", {"params": [("w", onp.ones(2))]},
                                {"step": 41})
    assert admitted.generation == gen + 1  # ONE bump admits the batch
    bars = {}

    def member(wid):
        bars[wid] = co.rebuild_barrier(wid)

    ths = [_spawn(member, w) for w in ("a", "b")]
    for t2 in ths:
        t2.join(10)
    th.join(10)
    assert got["meta"]["step"] == 41
    assert got["barrier"].workers == ("a", "b", "x")
    assert bars["a"].generation == got["barrier"].generation


# -- session accounting -----------------------------------------------------

def test_session_schedule_accounting_and_rounds():
    clock = FakeClock()
    co = _coordinator(clock)
    s = ElasticSession(co, "a", clock=clock)
    co.register("b")
    s.refresh()
    assert s.world == 2
    s._ref_world = 2
    s.note_step(8)           # world 2 at ref 2: one virtual update
    assert s.schedule_updates() == 1
    assert s.samples_seen == 16.0
    co.leave("b")
    assert s.heartbeat() is True     # bump observed at the boundary
    s.rebuild()
    assert s.world == 1 and s._round == 0
    s.note_step(8)           # world 1 at ref 2: HALF a virtual update
    assert s.samples_seen == 24.0
    assert abs(s._virtual_updates - 1.5) < 1e-9


def test_session_snapshot_positional_roundtrip():
    # no trainer: snapshot degrades to meta-only
    clock = FakeClock()
    co = _coordinator(clock)
    s = ElasticSession(co, "a", clock=clock)
    state, meta = s.snapshot_state(step=5)
    assert state is None and meta["step"] == 5


# -- watchdog wiring (satellite: on_verdict registry) ----------------------

def test_watchdog_probe_reports_and_action_bumps():
    from mxnet_tpu.resil import Watchdog
    clock = FakeClock()
    co = _coordinator(clock)
    co.register("a")
    co.register("b")
    clock.advance(10.0)
    co.tracker.heartbeat("a")  # only b is stale
    wd = Watchdog(stall_after_s=1e6, clock=clock)
    co.attach_watchdog(wd)     # report-only default
    found = [f for f in wd.check() if f.check == "worker_lost"]
    assert len(found) == 1 and found[0].obj == "elastic.b"
    assert co.view().workers == ("a", "b")  # NO action taken
    # opt in the verdict action: the same finding now bumps
    wd2 = Watchdog(stall_after_s=1e6, clock=clock)
    co.attach_watchdog(wd2, act=True)
    [f for f in wd2.check()]
    assert co.view().workers == ("a",)


# -- the silent-wedge lint --------------------------------------------------

def test_elasticlint_flags_wedge_class_and_live_registry_clean():
    from mxnet_tpu.kvstore import KVStoreBase
    from mxnet_tpu.passes import default_manager
    from mxnet_tpu.passes.elasticlint import ElasticAbortAudit

    p = ElasticAbortAudit()
    # the IN-REPO stores carry the contract (registered in the default
    # manager so every `mxlint` audit covers them). Audit the concrete
    # in-repo classes explicitly: the default subclass walk would also
    # see fixture classes other tests may have defined in-process.
    from mxnet_tpu.elastic.kvstore import ElasticKVStore
    from mxnet_tpu.kvstore import (KVStoreDist, KVStoreDistAsync,
                                   KVStoreLocal)
    assert "elasticlint" in default_manager().names()
    live = p.run([KVStoreBase, KVStoreLocal, KVStoreDist,
                  KVStoreDistAsync, ElasticKVStore])
    assert not [f for f in live if f.severity == "error"], live

    class WedgeStore(KVStoreBase):
        def allreduce_flat(self, key, value):  # pragma: no cover
            return value

    class PaperworkStore(KVStoreBase):
        elastic_abort = "generation"

        def allreduce_flat(self, key, value):  # pragma: no cover
            return value

    fs = p.run([WedgeStore, PaperworkStore])
    assert any(f.check == "silent-wedge" and f.severity == "error"
               for f in fs)
    assert any(f.check == "unwired-generation-abort" for f in fs)


# -- bucket relayout + live shard-plan re-inference ------------------------

def test_gradient_buckets_layout_key_includes_world():
    from mxnet_tpu.step.buckets import GradientBuckets
    items = [(0, (4, 4), "float32", 64), (1, (8,), "float32", 32)]
    b2 = GradientBuckets(items, world_size=2)
    b3 = GradientBuckets(items, world_size=3)
    assert b2.layout_key() != b3.layout_key()
    assert b2.layout_key()[0] == b3.layout_key()[0]  # same assignment


def test_shard_plan_live_reinfer_batch_axis():
    import jax
    from mxnet_tpu.shard import ShardPlan
    plan = ShardPlan(axes={"batch": -1})
    assert plan.n_batch == len(jax.devices())
    smaller = plan.reinfer(devices=jax.devices()[:4])
    assert smaller.n_batch == 4
    assert smaller.batch_axis == plan.batch_axis
    assert smaller.zero == plan.zero


# -- the wire: typed fences across the kvstore server ----------------------

def test_remote_group_typed_membership_over_sockets():
    import socket as _socket
    from mxnet_tpu.elastic import RemoteGroup
    from mxnet_tpu.kvstore_server import KVServer

    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = KVServer(f"127.0.0.1:{port}", num_workers=2)
    try:
        ga = RemoteGroup(f"127.0.0.1:{port}")
        gb = RemoteGroup(f"127.0.0.1:{port}")
        va = ga.register("a")
        vb = gb.register("b")
        assert vb.workers == ("a", "b")
        gen = vb.generation
        out = {}

        def reduce_a():
            out["a"] = ga.allreduce("a", gen, 0, "g", onp.ones(2))

        th = _spawn(reduce_a)
        out["b"] = gb.allreduce("b", gen, 0, "g", onp.full(2, 2.0))
        th.join(10)
        assert (out["a"] == 3.0).all() and (out["b"] == 3.0).all()

        # a leave fences the peer's next round WITH THE TYPE intact
        def reduce_then_fence():
            try:
                ga.allreduce("a", gen, 1, "g", onp.ones(2))
            except MembershipChanged as e:
                out["fence"] = e

        th = _spawn(reduce_then_fence)
        import time as _t
        _t.sleep(0.05)
        gb.leave("b")
        th.join(10)
        assert isinstance(out["fence"], MembershipChanged)
        assert out["fence"].generation == gen + 1
        ga.close()
        gb.close()
    finally:
        server.stop()


# -- end to end: kill + rejoin through the real training stack -------------

def test_inprocess_kill_and_rejoin_drill():
    """The tier-1 integration cut of the acceptance drill: 3 elastic
    workers (real gluon Trainers + split-phase ElasticStepFunction),
    thread-mode kill of one at a scripted step, survivors rebuild and
    finish with exactly one update-program re-key, a fresh worker
    rejoins from group state-sync (never a checkpoint), and no
    steady-state recompiles remain."""
    from mxnet_tpu.elastic.drill import run_elastic_drill
    rep = run_elastic_drill(
        n_workers=3, steps=16, kill_step=5, kill_rank=1, rejoin=True,
        rejoin_after_steps=3, batch=4, in_dim=8, hidden=8, out_dim=2,
        hb_interval=0.15, timeout_s=90.0)
    per = rep["per_worker"]
    assert per["w1"]["death"] == "killed"
    assert per["w0"]["steps"] == 16 and per["w2"]["steps"] == 16
    # rejoiner entered mid-run from the GROUP's live state
    assert per["w3"]["start_step"] > 0
    assert rep["rejoin_gen"] is not None
    # the re-key budget: one grad program ever; one update program per
    # world size; nothing further after the rebuilds
    for wid in ("w0", "w2"):
        assert rep["rekeys"][wid]["grad"] == 1
        assert rep["rekeys"][wid]["update"] == \
            len(rep["rekeys"][wid]["worlds"])
    assert rep["recompiles_after_rebuild"] == 0
    assert rep["recovery_s"] is not None and rep["recovery_s"] < 30
    assert rep["final_loss"] is not None


def test_elastic_step_takes_its_rates_as_one_host_array():
    """The split-phase step shares the one-program step's ``_hyper()``
    and update segment: one host f32 ``(2, leaves)`` array into the
    update program, bitwise the plain fused step at a world of one,
    in f32 and with bf16 parameters and momentum."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.elastic import ElasticKVStore
    from mxnet_tpu.elastic.stepfn import ElasticStepFunction

    for dtype in ("float32", "bfloat16"):
        x = nd.array(onp.linspace(-1, 1, 24).reshape(4, 6)
                     .astype("float32")).astype(dtype)
        y = nd.array(onp.ones((4, 3), "float32")).astype(dtype)
        steps = {}
        for kind in ("plain", "elastic"):
            mx.random.seed(11)
            net = gluon.nn.Dense(3, in_units=6)
            net.initialize()
            net.cast(dtype)
            kv = {}
            if kind == "elastic":
                kv = {"kvstore": ElasticKVStore(
                    group=_coordinator(FakeClock()), worker_id="a"),
                    "update_on_kvstore": False}
            trainer = gluon.Trainer(
                net.collect_params(), "sgd",
                {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.01}, **kv)
            fused = trainer.fuse_step(net, gluon.loss.L2Loss())
            for _ in range(3):
                fused.step(x, y)
            steps[kind] = (net, fused)
        fused = steps["elastic"][1]
        assert isinstance(fused, ElasticStepFunction)
        hyper = fused._hyper()
        assert type(hyper) is onp.ndarray
        assert hyper.shape == (2, 2) and hyper.dtype == onp.float32
        for a, b in zip(steps["plain"][0].collect_params().values(),
                        steps["elastic"][0].collect_params().values()):
            assert str(b.data().dtype) == dtype
            assert onp.array_equal(a.data().asnumpy(),
                                   b.data().asnumpy())


def test_trainer_eager_path_absorbs_membership_change():
    """Zero-user-code contract on the EAGER path: a gluon Trainer over
    an ElasticKVStore keeps training straight through a peer's leave —
    trainer.step() absorbs the typed fence, rebuilds, re-exchanges."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.elastic import ElasticKVStore

    clock = FakeClock()
    co = _coordinator(clock)
    done = {}

    def worker(wid, n_steps):
        mx.random.seed(7)
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize()
        kv = ElasticKVStore(group=co, worker_id=wid)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=kv,
                                update_on_kvstore=False)
        from mxnet_tpu import autograd
        x = nd.array(onp.ones((2, 3), "float32"))
        y = nd.array(onp.zeros((2, 2), "float32"))
        loss_fn = gluon.loss.L2Loss()
        for i in range(n_steps):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(2)  # absorbs the fence when b leaves
        done[wid] = trainer
        if wid == "b":
            kv.session.leave()

    tb = _spawn(worker, "b", 3)
    ta = _spawn(worker, "a", 6)
    ta.join(60)
    tb.join(60)
    assert "a" in done and "b" in done
    tr = done["a"]
    assert tr._elastic is not None
    assert tr._elastic.world == 1  # finished alone after b left


def test_eager_bucketed_exchange_no_partial_effect_on_fence():
    """A MembershipChanged on the SECOND bucket must leave the first
    bucket's grads UNTOUCHED, so the post-rebuild retry re-exchanges
    the original gradients — a per-bucket rebind would feed reduced
    sums back in and double-count them (review finding, pinned)."""
    import jax.numpy as jnp
    from mxnet_tpu import config, gluon

    class FakeSession:
        world = 2
        generation = 1

        def heartbeat(self, step=None):
            return False

        def rebuild(self):
            self.rebuilt = getattr(self, "rebuilt", 0) + 1

        def note_step(self, batch):
            pass

    class FenceOnceStore:
        supports_flat_allreduce = True
        elastic_abort = "generation"
        num_workers = 2

        def __init__(self):
            self.session = FakeSession()
            self.calls = 0

        def allreduce_flat(self, key, value):
            self.calls += 1
            if self.calls == 2:  # the 2nd bucket of the 1st attempt
                raise MembershipChanged("fenced mid-exchange", 2)
            from mxnet_tpu.ndarray.ndarray import _wrap
            return _wrap(value._data * 2.0)  # sum over world 2

    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    kv = FenceOnceStore()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.0}, kvstore=None,
                            update_on_kvstore=False)
    trainer._kvstore = kv
    trainer._update_on_kvstore = False
    trainer._kv_initialized = True
    trainer._elastic = kv.session
    config.set_flag("MXNET_GRAD_BUCKET_BYTES", 8)  # force 2 buckets
    try:
        for p in trainer._params:
            p.grad()._rebind(jnp.ones_like(p.grad()._data))
        trainer.step(1)
        assert kv.session.rebuilt == 1
        # weight AND bias grads are exactly 2x the originals — the
        # aborted first attempt left no partial rebinds behind
        for p in trainer._params:
            assert (p.grad().asnumpy() == 2.0).all(), p.name
        # bucket0 ok + bucket1 fence, then both retried = 4 calls
        assert kv.calls == 4
    finally:
        config.unset_flag("MXNET_GRAD_BUCKET_BYTES")
