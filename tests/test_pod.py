"""mxpod: multi-host process-group runtime (ISSUE 15).

Tier-1 fast cut — the protocol pieces, in-process and fake-clocked:
coordinator journal write/replay and the restart fence, PodGroup's
bounded-backoff/typed-CoordinatorLost transport, idempotent re-issue,
PodContext bootstrap + stale-identity shed, the host-scope watchdog
probe, pod topology in checkpoint manifests, the podlint contract,
and the kill9/pod.host fault-plan grammar.

The subprocess N-host drills (SIGKILL a host / corrupt a host / kill
the coordinator) are @slow; their protocol content is what the fast
tests above pin, and `tools/mxresil.py pod` drives them with gates. The 2-process socket-exchange smoke lives in
tests/test_dist_kvstore.py (tier-1).
"""
import json
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic.coordinator import ElasticCoordinator
from mxnet_tpu.elastic.membership import (MembershipChanged,
                                          MembershipTracker)
from mxnet_tpu.kvstore import KVStoreTimeoutError
from mxnet_tpu.pod import CoordinatorLost, PodContext, PodGroup


@pytest.fixture(autouse=True)
def _reset_pod_context():
    """A test that dies mid-bootstrap must not leave its PodContext as
    the process-wide active context (checkpoint topology reads it)."""
    yield
    from mxnet_tpu.pod import context as _ctx_mod
    _ctx_mod._ACTIVE = None


# ---------------------------------------------------------------------------
# membership restore + the coordinator journal
# ---------------------------------------------------------------------------

def test_tracker_restore_and_bump():
    tr = MembershipTracker(heartbeat_interval_s=10.0)
    view = tr.restore(7, ["w0", "w1"], {"w0": (0,), "w1": (1,)})
    assert view.generation == 7 and view.workers == ("w0", "w1")
    assert view.devices["w1"] == (1,)
    # restored members carry fresh beats: nobody is lost at t=0
    assert tr.check() == []
    v2 = tr.bump("restart")
    assert v2.generation == 8 and v2.workers == ("w0", "w1")
    # heartbeat under the restored identity works
    tr.heartbeat("w0")


def test_coordinator_journal_replay_and_restart_fence(tmp_path):
    jd = str(tmp_path / "journal")
    co = ElasticCoordinator(journal_dir=jd)
    co.register("w0", (0,))
    co.register("w1", (1,))
    gen = co.view().generation
    lines = [json.loads(ln) for ln in
             open(os.path.join(jd, "membership.jsonl"))]
    assert lines[-1]["generation"] == gen
    assert lines[-1]["workers"] == ["w0", "w1"]

    # a RESTARTED coordinator replays the newest entry and bumps once
    co2 = ElasticCoordinator(journal_dir=jd)
    assert co2.restored
    v = co2.view()
    assert v.workers == ("w0", "w1")
    assert v.generation == gen + 1
    # an exchange issued under the pre-crash generation fences TYPED —
    # the re-issued idempotent request of a reconnecting survivor
    with pytest.raises(MembershipChanged):
        co2.allreduce("w0", gen, 0, "g", onp.ones(2))
    # survivors re-enter through the ordinary protocol
    co2.heartbeat("w0")
    co2.heartbeat("w1")
    # the restart itself was journaled (reason recorded)
    lines = [json.loads(ln) for ln in
             open(os.path.join(jd, "membership.jsonl"))]
    assert lines[-1]["generation"] == gen + 1
    assert lines[-1]["reason"] == "restart"


def test_journal_tolerates_torn_tail(tmp_path):
    jd = str(tmp_path)
    co = ElasticCoordinator(journal_dir=jd)
    co.register("a", (0,))
    gen = co.view().generation
    path = os.path.join(jd, "membership.jsonl")
    with open(path, "a") as f:
        f.write('{"generation": 99, "workers": ["a", "b"')  # torn
    co2 = ElasticCoordinator(journal_dir=jd)
    assert co2.restored
    assert co2.view().workers == ("a",)
    assert co2.view().generation == gen + 1


def test_coordinator_allreduce_idempotent_reissue():
    """PodGroup re-issues a request after a transport failure; the
    round protocol makes the duplicate contribution a no-op per
    (generation, round, key, worker) — the sum counts each worker
    once."""
    co = ElasticCoordinator()
    co.register("a")
    co.register("b")
    gen = co.view().generation
    out = {}

    def contribute_a():
        # first attempt "lost its reply": contribute, then re-issue
        def run():
            out["a1"] = co.allreduce("a", gen, 0, "g",
                                     onp.full(2, 10.0))
        t = threading.Thread(target=run, daemon=True)
        t.start()
        time.sleep(0.05)
        out["a2"] = co.allreduce("a", gen, 0, "g", onp.full(2, 10.0))
        t.join(10)

    th = threading.Thread(target=contribute_a, daemon=True)
    th.start()
    time.sleep(0.1)
    out["b"] = co.allreduce("b", gen, 0, "g", onp.full(2, 1.0))
    th.join(10)
    assert (out["b"] == 11.0).all()
    assert (out["a1"] == 11.0).all() and (out["a2"] == 11.0).all()


# ---------------------------------------------------------------------------
# PodGroup: bounded backoff, typed CoordinatorLost
# ---------------------------------------------------------------------------

class _DownClient:
    def __init__(self, fail_n=10 ** 9):
        self.calls = 0
        self.fail_n = fail_n

    def request(self, cmd, key=None, payload=None):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise KVStoreTimeoutError("fake: server down")
        return {"ok": self.calls}

    def _reconnect(self):
        pass

    def close(self):
        pass


def test_pod_group_recovers_after_transport_blip():
    g = PodGroup(client=_DownClient(fail_n=3), grace_s=10.0)
    assert g._req("view") == {"ok": 4}
    assert g._client.calls == 4


def test_pod_group_raises_typed_coordinator_lost():
    g = PodGroup(client=_DownClient(), grace_s=0.5)
    t0 = time.monotonic()
    with pytest.raises(CoordinatorLost) as ei:
        g.heartbeat("w1")
    assert time.monotonic() - t0 >= 0.5
    assert "MXPOD_COORDINATOR_GRACE_S" in str(ei.value)
    # NOT retryable: blind retry is what just failed
    from mxnet_tpu.resil.policy import RetryableError
    assert not isinstance(ei.value, RetryableError)


# ---------------------------------------------------------------------------
# PodContext bootstrap
# ---------------------------------------------------------------------------

def _unset_pod_flags():
    for f in ("MXPOD_COORDINATOR", "MXPOD_RANK", "MXPOD_NPROCS",
              "MXPOD_HEARTBEAT_S", "MXPOD_JOURNAL_DIR"):
        config.unset_flag(f)
    config.unset_flag("MXELASTIC_HEARTBEAT_S")


def test_pod_context_resolution_and_heartbeat_mapping():
    try:
        config.set_flag("MXPOD_COORDINATOR", "10.0.0.1:7777")
        config.set_flag("MXPOD_RANK", 2)
        config.set_flag("MXPOD_NPROCS", 4)
        config.set_flag("MXPOD_HEARTBEAT_S", 0.25)
        ctx = PodContext(start_server=False)
        assert ctx.rank == 2 and ctx.nprocs == 4
        assert not ctx.is_coordinator_host
        assert ctx.coordinator == "10.0.0.1:7777"
        assert ctx.worker_id == "w2"
        # one flag tunes host-loss detection end to end
        assert float(config.get("MXELASTIC_HEARTBEAT_S")) == 0.25
        assert ctx.local_device_ids() == (2,)  # CPU: rank slot
        from mxnet_tpu.pod import active_context
        assert active_context() is ctx
        ctx.close()
        assert active_context() is None
        # the restart contract: MXPOD_JOIN=1 + plain PodContext() is a
        # rejoin (user code unchanged when the cluster manager
        # reschedules a host)
        os.environ["MXPOD_JOIN"] = "1"
        try:
            ctx2 = PodContext(start_server=False)
            assert ctx2.join is True
            ctx2.close()
        finally:
            os.environ.pop("MXPOD_JOIN", None)
    finally:
        _unset_pod_flags()


def test_pod_context_multiproc_requires_coordinator():
    try:
        config.set_flag("MXPOD_NPROCS", 3)
        config.set_flag("MXPOD_RANK", 1)
        env_kv = os.environ.pop("MX_KV_SERVER", None)
        try:
            with pytest.raises(MXNetError, match="MXPOD_COORDINATOR"):
                PodContext(start_server=False)
        finally:
            if env_kv is not None:
                os.environ["MX_KV_SERVER"] = env_kv
    finally:
        _unset_pod_flags()


def test_pod_context_single_process_loopback_and_topology(tmp_path):
    try:
        ctx = PodContext(rank=0, nprocs=1,
                         journal_dir=str(tmp_path / "j"))
        kv = ctx.kvstore()
        ctx.form_group(kv)
        assert kv.session.world == 1
        top = ctx.topology()
        assert top["n_hosts"] == 1 and top["ranks"] == ["w0"]
        assert top["coordinator"] == ctx.coordinator
        assert ctx.describe()["coordinator_host"] is True
        # the journal is armed on the control plane
        assert os.path.exists(os.path.join(str(tmp_path / "j"),
                                           "membership.jsonl"))
        ctx.close()
    finally:
        _unset_pod_flags()


def test_fresh_start_rotates_stale_journal(tmp_path):
    """A NEW job reusing MXPOD_JOURNAL_DIR must not replay the
    previous job's members as phantoms: a non-join coordinator host
    rotates the stale journal; a join=True restart replays it."""
    jd = str(tmp_path)
    co = ElasticCoordinator(journal_dir=jd)
    co.register("w0", (0,))
    co.register("w1", (1,))
    del co
    try:
        ctx = PodContext(rank=0, nprocs=1, journal_dir=jd)
        assert ctx.restored is False
        assert ctx._server._ensure_elastic().view().workers == ()
        assert os.path.exists(os.path.join(jd,
                                           "membership.jsonl.prev"))
        ctx.close()
    finally:
        _unset_pod_flags()


def test_host_gauges_retire_when_host_departs():
    from mxnet_tpu import telemetry
    from mxnet_tpu.resil.watchdog import host_liveness_probe
    co = ElasticCoordinator()
    co.register("w0", (0,))
    co.register("w1", (1,))
    probe = host_liveness_probe(co, dump=False)
    probe()
    assert "mxpod_host_beat_age_seconds_w1" in telemetry.snapshot()
    co.leave("w1")
    probe()
    # the departed host's gauge is retired, not frozen at its last
    # healthy-looking age
    assert "mxpod_host_beat_age_seconds_w1" not in \
        telemetry.snapshot()
    assert "mxpod_host_beat_age_seconds_w0" in telemetry.snapshot()


def test_rejoin_sheds_stale_identity_over_sockets(tmp_path):
    """A restarted host whose previous identity is still a member
    leaves it first (one immediate bump), then re-enters through the
    join state-sync — survivors never wait out the heartbeat budget
    for a ghost."""
    import socket as _socket
    from mxnet_tpu.elastic import RemoteGroup
    from mxnet_tpu.elastic.session import ElasticSession
    from mxnet_tpu.kvstore_server import KVServer
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = KVServer(f"127.0.0.1:{port}", num_workers=2)
    try:
        # the surviving leader, beating so admissions happen
        leader = ElasticSession(RemoteGroup(f"127.0.0.1:{port}"), "w0")
        # the STALE identity of the dead host, still a member
        RemoteGroup(f"127.0.0.1:{port}").register("w1", (1,))
        gen_stale = leader.refresh().generation
        assert "w1" in leader.view.workers
        stop = threading.Event()

        def beat():
            # the leader's step boundary: beat, publish join state,
            # and ABSORB bumps (meet the rebuild barrier) — what the
            # Trainer loop does in a real run
            while not stop.wait(0.02):
                if leader.heartbeat(0):
                    leader.rebuild()

        th = threading.Thread(target=beat, daemon=True)
        th.start()
        try:
            ctx = PodContext(coordinator=f"127.0.0.1:{port}", rank=1,
                             nprocs=2, join=True, start_server=False)
            kv = ctx.kvstore()
            assert kv.session.world == 2
            # shed (leave bump) + readmit (admit bump): >= 2 bumps
            assert kv.session.generation >= gen_stale + 2
            assert "w1" in kv.session.view.workers
            ctx.close()
        finally:
            stop.set()
            th.join(2)
            leader.group.close()
    finally:
        server.stop()
        _unset_pod_flags()


# ---------------------------------------------------------------------------
# host-scope watchdog probe
# ---------------------------------------------------------------------------

def test_host_liveness_probe_names_rank_and_generation():
    clk = {"t": 0.0}
    tr = MembershipTracker(heartbeat_interval_s=1.0, miss_limit=2,
                           clock=lambda: clk["t"])
    co = ElasticCoordinator(tracker=tr)
    co.register("w0", (0,))
    co.register("w1", (1,))
    gen = co.view().generation
    from mxnet_tpu.resil.watchdog import host_liveness_probe
    probe = host_liveness_probe(co, dump=False)
    assert probe() == []
    clk["t"] = 3.0
    tr.heartbeat("w0")  # only w0 beats; w1 goes silent past budget
    findings = probe()
    assert len(findings) == 1
    f = findings[0]
    assert f.check == "host_lost" and f.severity == "error"
    assert f.obj == "pod.host.w1"
    assert "rank 1" in f.message
    assert f"generation {gen}" in f.message
    # per-host beat-age gauges exported
    from mxnet_tpu import telemetry
    snap = telemetry.snapshot()
    assert snap.get("mxpod_host_beat_age_seconds_w1", 0) > 2.0
    assert snap.get("mxpod_host_beat_age_seconds_w0") == 0.0


def test_attach_watchdog_wires_host_probe_and_dump(tmp_path):
    from mxnet_tpu.resil import Watchdog
    clk = {"t": 0.0}
    tr = MembershipTracker(heartbeat_interval_s=1.0, miss_limit=2,
                           clock=lambda: clk["t"])
    co = ElasticCoordinator(tracker=tr)
    co.register("w0", (0,))
    co.register("w1", (1,))
    wd = Watchdog(stall_after_s=1e6, clock=lambda: clk["t"])
    co.attach_watchdog(wd)
    assert wd.check() == []
    clk["t"] = 5.0
    tr.heartbeat("w0")
    try:
        config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
        checks = {f.check for f in wd.check()}
        # both the verdict-action probe and the pod host-scope probe
        assert "worker_lost" in checks and "host_lost" in checks
        dumps = [p for p in os.listdir(str(tmp_path))
                 if p.startswith("mxtrace-flight-host_lost")]
        assert dumps, "host_lost verdict must freeze the recorder"
    finally:
        config.unset_flag("MXTRACE_DUMP_DIR")


# ---------------------------------------------------------------------------
# checkpoint: pod topology in the manifest
# ---------------------------------------------------------------------------

def test_checkpoint_pod_topology_and_cross_topology_restore(tmp_path):
    """Save with a 4-host group, restore into 2: the manifest records
    {n_hosts, ranks, coordinator} alongside {generation, world_size},
    and the cross-topology restore is counted."""
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.elastic.kvstore import ElasticKVStore
    from mxnet_tpu import telemetry

    co = ElasticCoordinator()
    kv = ElasticKVStore(group=co, worker_id="w0", devices=(0,))
    for r in (1, 2, 3):  # the other three "hosts"
        co.register(f"w{r}", (r,))
    kv.session.refresh()
    assert kv.session.world == 4

    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kv,
                            update_on_kvstore=False)
    if not trainer._kv_initialized:
        trainer._init_kvstore()  # binds the elastic session
    kv.session.refresh()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, trainer=trainer)
    man = mgr.manifest(3)
    assert man["elastic"]["world_size"] == 4
    pod = man["elastic"]["pod"]
    assert pod["n_hosts"] == 4
    assert pod["ranks"] == ["w0", "w1", "w2", "w3"]

    # the group shrinks to 2 hosts; restoring the 4-host snapshot
    # counts the cross-topology move
    co.leave("w3")
    co.leave("w2")
    kv.session.refresh()
    assert kv.session.world == 2
    before = telemetry.snapshot().get(
        "mxpod_cross_topology_restores_total", 0)
    mgr.restore(3, trainer=trainer)
    after = telemetry.snapshot().get(
        "mxpod_cross_topology_restores_total", 0)
    assert after == before + 1
    kv.close()


def test_cross_topology_restore_reinfers_shard_plan(tmp_path):
    """The ShardPlan batch axis re-infers against the devices present
    NOW when a checkpoint from a different host count restores."""
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.shard import ShardPlan

    class _View:
        workers = ("w0",)
        generation = 1

        def rank_of(self, w):
            return 0

    class _Ses:
        view = _View()
        generation = 1
        world = 1
        worker_id = "w0"
        samples_seen = 0.0

    class _Trainer:
        _params = []
        _updaters = []
        _elastic = _Ses()
        _shard_plan = ShardPlan(axes={"batch": -1})

    t = _Trainer()
    plan_before = t._shard_plan
    _CM = CheckpointManager
    _CM._install(
        t, {}, None, shard=None,
        elastic={"generation": 1, "world_size": 2,
                 "pod": {"n_hosts": 2, "ranks": ["w0", "w1"],
                         "coordinator": "10.0.0.1:1"}})
    assert t._shard_plan is not plan_before  # re-inferred instance
    assert t._shard_plan.batch_axis == plan_before.batch_axis


# ---------------------------------------------------------------------------
# podlint: the pod-scope membership contract
# ---------------------------------------------------------------------------

class _GoodPodStore:
    supports_flat_allreduce = True
    pod_scope = True
    elastic_abort = "generation"
    heartbeat_channel = "control-socket"

    def allreduce_flat(self, key, value):
        return self._reduce_round(key, value)


class _NoBeatStore:
    supports_flat_allreduce = True
    pod_scope = True
    elastic_abort = "generation"

    def allreduce_flat(self, key, value):
        return self._reduce_round(key, value)


class _UnfencedPodStore:
    supports_flat_allreduce = True
    pod_scope = True
    elastic_abort = "timeout"
    heartbeat_channel = "control-socket"

    def allreduce_flat(self, key, value):
        return value


class _DeclaredUnwiredStore:
    supports_flat_allreduce = True
    pod_scope = True
    elastic_abort = "generation"  # declared, never wired
    heartbeat_channel = "control-socket"

    def allreduce_flat(self, key, value):
        return value + value


def test_podlint_fixture_coverage_and_live_registry():
    from mxnet_tpu.passes.elasticlint import PodScopeAudit
    fx = PodScopeAudit().run([_GoodPodStore, _NoBeatStore,
                              _UnfencedPodStore,
                              _DeclaredUnwiredStore])
    got = {(f.obj, f.check) for f in fx}
    assert ("_NoBeatStore", "no-heartbeat-channel") in got
    assert ("_UnfencedPodStore", "pod-unfenced-exchange") in got
    assert ("_DeclaredUnwiredStore", "pod-unfenced-exchange") in got
    assert not [f for f in fx if f.obj == "_GoodPodStore"]
    # the live registry is clean of errors; the raw collective path
    # stays VISIBLE as info (not silently exempt)
    live = PodScopeAudit().run()
    assert not [f for f in live if f.severity == "error"], live
    assert any(f.check == "not-pod-scope" and f.obj == "KVStoreDist"
               for f in live)
    # ElasticKVStore declares both halves
    from mxnet_tpu.elastic.kvstore import ElasticKVStore
    assert ElasticKVStore.pod_scope is True
    assert ElasticKVStore.heartbeat_channel == "control-socket"


def test_podlint_registered_in_default_manager():
    from mxnet_tpu.passes import default_manager
    assert "podlint" in default_manager().names()


# ---------------------------------------------------------------------------
# fault plan: kill9 + pod.host sites
# ---------------------------------------------------------------------------

def test_faultplan_kill9_and_pod_site_grammar():
    from mxnet_tpu.resil.faultplan import parse_plan
    (c,) = parse_plan("pod.host.1:5=kill9")
    assert c.site == "pod.host.1" and c.action == "kill9"
    assert c.step == 5 and not c.step_from
    assert c.describe()["selector"] == "pod.host.1:5"
    assert c.describe()["action"] == "kill9"
    # the other pod-scope actions parse at the same site
    parse_plan("pod.host.0:3=preempt;pod.host.2=stall:50ms")
    with pytest.raises(MXNetError, match="kill9"):
        parse_plan("pod.host.1:5=explode")


def test_transport_socket_mode_off_single_process():
    from mxnet_tpu.pod import transport
    assert transport.socket_mode() is False


# ---------------------------------------------------------------------------
# the subprocess N-host drills (slow: real python+jax host processes)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pod_sigkill_host_drill_acceptance():
    """ISSUE 15 acceptance: SIGKILL one of 3 host processes (CPU);
    survivors absorb the bump with zero user code, exactly one
    program re-keys per new world size, training continues within
    MXELASTIC_LOSS_TOL, and the replacement host syncs live state
    from the group — no checkpoint file."""
    from mxnet_tpu.elastic.drill import run_pod_drill
    base = run_pod_drill(n_hosts=3, steps=20, batch=8, timeout_s=240.0)
    rep = run_pod_drill(n_hosts=3, steps=20, kill_step=6, kill_rank=1,
                        action="kill9", rejoin=True,
                        rejoin_after_steps=4, batch=8,
                        hb_interval=0.25, timeout_s=240.0)
    per = rep["per_worker"]
    assert per["w1"]["death"] == "killed" and per["w1"]["rc"] == -9
    assert per["w0"]["steps"] == 20 and per["w2"]["steps"] == 20
    assert rep["world_after_kill"] == 2
    assert rep["recovery_s"] is not None and rep["recovery_s"] < 30
    # re-key budget: 1 grad ever, 1 update per world size
    for wid in ("w0", "w2"):
        rk = rep["rekeys"][wid]
        assert rk["grad"] == 1 and rk["update"] == len(rk["worlds"])
    assert rep["recompiles_after_rebuild"] == 0
    # the replacement synced from the GROUP, mid-run
    assert rep["rejoin_synced_from_group"] is True
    assert per["w3+join"]["start_step"] > 0
    # loss trajectory within the declared tolerance of uninterrupted
    tol = float(config.get("MXELASTIC_LOSS_TOL"))
    delta = abs(rep["final_loss"] - base["final_loss"]) / \
        max(abs(base["final_loss"]), 1e-9)
    assert delta <= tol, (rep["final_loss"], base["final_loss"])
    assert rep["final_view"]["world_size"] == 3


@pytest.mark.slow
def test_pod_corrupt_host_detected_attributed_quarantined():
    """ISSUE 15 acceptance: an sdc-injected host process is caught by
    the CROSS-HOST fingerprint vote within one step, attributed by
    rank, and quarantined through a membership bump; survivors
    continue."""
    from mxnet_tpu.elastic.drill import run_pod_drill
    rep = run_pod_drill(n_hosts=3, steps=14, kill_step=6, kill_rank=1,
                        action="sdc", rejoin=False, batch=4, in_dim=8,
                        hidden=8, out_dim=2, hb_interval=0.25,
                        timeout_s=240.0)
    g = rep["guard"]
    assert g["detected_step"] is not None
    assert 0 <= g["detected_step"] - 6 <= 1
    assert g["suspects"] == ["w1"]
    assert g["quarantined"] == ["w1"]
    assert rep["per_worker"]["w1"]["death"] == "quarantined"
    assert rep["per_worker"]["w1"]["rc"] == 43
    assert rep["per_worker"]["w0"]["steps"] == 14
    assert rep["per_worker"]["w2"]["steps"] == 14
    assert rep["recompiles_after_rebuild"] == 0


@pytest.mark.slow
def test_pod_coordinator_restart_replays_journal_and_reforms():
    """ISSUE 15 acceptance: kill rank-0 (the coordinator host)
    mid-run; the restarted coordinator replays its generation journal
    and the group RE-FORMS — survivors ride the bounded-backoff
    reconnect into the ordinary rebuild (no CoordinatorLost, no
    wedge), and the restarted host rejoins from group state."""
    from mxnet_tpu.elastic.drill import run_pod_drill
    rep = run_pod_drill(n_hosts=3, steps=14, kill_step=5, kill_rank=0,
                        action="kill9", restart_coordinator=True,
                        batch=4, in_dim=8, hidden=8, out_dim=2,
                        hb_interval=0.25, timeout_s=240.0)
    cr = rep["coordinator_restart"]
    assert cr["journal_replayed"] is True
    assert cr["rejoined"] is True
    assert cr["survivor_coordinator_lost"] is False
    assert rep["per_worker"]["w1"]["steps"] == 14
    assert rep["per_worker"]["w2"]["steps"] == 14
    assert rep["per_worker"]["w0+join"]["start_step"] > 0
    assert rep["rejoin_synced_from_group"] is True
    assert rep["final_view"]["world_size"] == 3
    assert rep["recompiles_after_rebuild"] == 0
