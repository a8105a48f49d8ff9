"""mxobs unit + property tests (ISSUE 17): cross-host trace
propagation (wire contexts + derived pod.step identity), the exact
histogram merge behind the pod collector, coordinated dump-epoch
following, the coordinator's obs surface, obslint, and the mxprof
--dir stitcher. The 2-process end-to-end drill lives in
test_dist_kvstore.py (test_pod_obs_smoke_two_workers).
"""
import importlib.util
import json
import os
import random
import time

import pytest

from mxnet_tpu import config, trace
from mxnet_tpu.elastic.coordinator import ElasticCoordinator
from mxnet_tpu.obs import propagate as prop
from mxnet_tpu.obs.capture import DumpFollower
from mxnet_tpu.obs.collector import (MetricsCollector, fleet_probe,
                                     live_collectors)
from mxnet_tpu.passes.obslint import ObsLint, lint_collectors
from mxnet_tpu.telemetry import metrics as _metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_obs_test", os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _obs_env():
    trace.reset()
    config.set_flag("MXTRACE", True)
    config.set_flag("MXOBS", True)
    yield
    trace.reset()
    for f in ("MXTRACE", "MXOBS", "MXOBS_PUSH_INTERVAL_S",
              "MXOBS_EXPORT", "MXTRACE_DUMP_DIR", "MXTRACE_EXPORT"):
        config.unset_flag(f)


# ---------------------------------------------------------------------------
# histogram merge: exact on count/sum/min/max (the collector contract)
# ---------------------------------------------------------------------------

def test_histogram_merge_exact_property():
    """Property: for random streams split across random 'ranks', the
    merged histogram's count/sum/min/max equal the unsplit stream's —
    exactly for count/min/max, to float-sum reordering for sum."""
    for seed in range(8):
        rng = random.Random(seed)
        vals = [rng.uniform(-100, 100)
                for _ in range(rng.randrange(1, 400))]
        n_ranks = rng.randrange(1, 5)
        parts = [[] for _ in range(n_ranks)]
        for v in vals:
            parts[rng.randrange(n_ranks)].append(v)
        merged = _metrics.Histogram("t_merge")  # detached: no registry
        for part in parts:
            h = _metrics.Histogram("t_part")
            for v in part:
                h.observe(v)
            merged.merge(h, rng=rng)
        assert merged.count == len(vals), seed
        assert merged.sum == pytest.approx(sum(vals), rel=1e-9), seed
        v = merged.value()
        assert v["min"] == min(vals) and v["max"] == max(vals), seed
        # quantiles come from the merged reservoir: inside the range
        assert min(vals) <= v["p50"] <= max(vals), seed


def test_histogram_merge_accepts_state_dict_and_empty():
    h = _metrics.Histogram("t_state")
    h.observe(1.0)
    h.observe(3.0)
    other = _metrics.Histogram("t_state2")
    other.observe(2.0)
    h.merge(other.state())          # dict form (the wire form)
    assert h.count == 3 and h.sum == pytest.approx(6.0)
    h.merge({"count": 0})           # empty merge is a no-op
    assert h.count == 3
    assert _metrics.percentile_of([], 50) is None


def test_merge_reservoirs_cap_and_count_weighting():
    # under-cap: nothing dropped, order preserved
    assert _metrics.merge_reservoirs([1, 2], 2, [3], 1, 10) == [1, 2, 3]
    # one empty side passes through (tail-capped)
    assert _metrics.merge_reservoirs([], 0, list(range(20)), 20, 5) \
        == list(range(15, 20))
    # weighting: side A's 8 samples summarize 10_000 observations,
    # side B's 8 summarize 8 — A must dominate the merged reservoir
    wins = 0
    for seed in range(20):
        rng = random.Random(seed)
        out = _metrics.merge_reservoirs(
            [1.0] * 8, 10_000, [0.0] * 8, 8, 8, rng=rng)
        assert len(out) == 8
        if sum(out) >= 5:
            wins += 1
    assert wins >= 16, wins


# ---------------------------------------------------------------------------
# propagation: wire contexts + derived pod identity + zero-cost off
# ---------------------------------------------------------------------------

def test_wire_context_roundtrip_under_live_span():
    assert prop.wire_context() is None  # no ambient span
    with trace.span("rpc", "elastic") as sp:
        wire = prop.wire_context()
        assert wire == {"t": sp.trace_id, "s": sp.span_id}
    ctx = prop.bind(wire)
    assert ctx is not None and ctx.sampled
    assert ctx.trace_id == sp.trace_id
    assert ctx.span_id == sp.span_id
    # the bound context parents remote-side spans
    with trace.under(ctx):
        with trace.span("elastic.op", "elastic"):
            pass
    names = {s["name"]: s for s in trace.drain()}
    assert names["elastic.op"]["parent_id"] == sp.span_id
    assert names["elastic.op"]["trace_id"] == sp.trace_id


def test_bind_rejects_malformed_payloads():
    assert prop.bind(None) is None
    assert prop.bind("t:s") is None
    assert prop.bind({"t": "", "s": "x"}) is None
    assert prop.bind({"t": "x"}) is None


def test_unsampled_traces_stay_local():
    config.set_flag("MXTRACE_SAMPLE", 0.0)
    try:
        with trace.span("dropped", "app"):
            assert prop.wire_context() is None
    finally:
        config.unset_flag("MXTRACE_SAMPLE")


def test_obs_off_is_structurally_inert():
    config.set_flag("MXOBS", False)
    assert not prop.enabled()
    with trace.span("live", "app"):
        assert prop.wire_context() is None
    assert prop.bind({"t": "a", "s": "b"}) is None
    assert prop.pod_step_context("deadbeef", 1, 2) is None
    # and with obs on but tracing off, same answer
    config.set_flag("MXOBS", True)
    config.set_flag("MXTRACE", False)
    assert not prop.enabled()
    assert prop.pod_step_context("deadbeef", 1, 2) is None


def test_pod_step_context_is_a_pure_derivation():
    a = prop.pod_step_context("cafe01", 3, 17)
    b = prop.pod_step_context("cafe01", 3, 17)  # "another rank"
    assert a.trace_id == b.trace_id == "podcafe01g3s17"
    assert a.span_id == b.span_id == "podcafe01g3s17.root"
    assert a.sampled and b.sampled
    assert prop.pod_step_context("cafe01", 3, 18).trace_id != a.trace_id
    assert prop.pod_step_context(None, 3, 17) is None


def test_emit_pod_root_records_explicit_identity():
    t0 = time.perf_counter_ns()
    sp = prop.emit_pod_root("cafe02", 1, 5, t0, t0 + 1_000_000,
                            world=2)
    assert sp is not None
    spans = {s["span_id"]: s for s in trace.drain()}
    root = spans["podcafe02g1s5.root"]
    assert root["trace_id"] == "podcafe02g1s5"
    assert root["name"] == "pod.step" and not root.get("parent_id")
    assert root["attrs"]["world"] == 2
    assert root["dur_us"] == pytest.approx(1000.0)


# ---------------------------------------------------------------------------
# coordinated capture: the dump-epoch follower
# ---------------------------------------------------------------------------

def test_dump_follower_dumps_once_per_epoch(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    with trace.span("warm", "app"):
        pass
    f = DumpFollower()
    assert f.observe({}) is None
    assert f.observe({"dump_epoch": 0}) is None
    p = f.observe({"dump_epoch": 1, "dump_reason": "unit-a"})
    assert p and os.path.exists(p) and "-r0-" in os.path.basename(p)
    assert f.epoch == 1
    # same epoch re-observed: no second dump
    assert f.observe({"dump_epoch": 1, "dump_reason": "unit-a"}) is None
    # a NEW epoch with a new reason dumps again
    p2 = f.observe({"dump_epoch": 2, "dump_reason": "unit-b"})
    assert p2 and p2 != p
    doc = json.load(open(p2))
    assert doc["reason"] == "pod-dump-unit-b"
    assert doc["rank"] == 0


def test_dump_follower_inert_when_obs_off(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    config.set_flag("MXOBS", False)
    f = DumpFollower()
    assert f.observe({"dump_epoch": 5, "dump_reason": "x"}) is None
    assert os.listdir(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# the collector: exact merge, per-rank gauges, lifecycle
# ---------------------------------------------------------------------------

def _snap(hist_vals, counter_v):
    h = _metrics.Histogram("obs_t_h")  # detached builder
    for v in hist_vals:
        h.observe(v)
    return {"obs_t_h": {"kind": "histogram", **h.state()},
            "obs_t_c": {"kind": "counter", "value": counter_v}}


def test_collector_merged_counts_are_exact_sums():
    col = MetricsCollector("unit")
    try:
        col.push("wa", 0, _snap([1.0, 2.0], 2))
        col.push("wb", 1, _snap([3.0, 4.0, 5.0], 5))
        assert col.ranks() == [0, 1]
        doc = col.merged()
        assert doc["hosts"] == 2
        m = doc["merged"]["obs_t_h"]
        assert m["count"] == 5 and m["sum"] == pytest.approx(15.0)
        assert m["min"] == 1.0 and m["max"] == 5.0
        assert doc["merged"]["obs_t_c"] == 7
        assert doc["ranks"]["0"]["metrics"]["obs_t_h"]["count"] == 2
        assert doc["ranks"]["1"]["metrics"]["obs_t_h"]["count"] == 3
        assert doc["kinds"]["obs_t_h"] == "histogram"
        # per-rank freshness gauges registered + adopted
        live = _metrics.all_metrics()
        assert "mxobs_push_age_seconds_r0" in live
        assert "mxobs_push_age_seconds_r1" in live
        assert col in live_collectors()
        # a re-push updates in place (no second host entry)
        col.push("wa", 0, _snap([9.0], 1))
        assert col.merged()["hosts"] == 2
    finally:
        col.close()


def test_collector_retire_and_close_unregister_gauges():
    col = MetricsCollector("unit2")
    col.push("wa", 0, _snap([1.0], 1))
    col.push("wb", 1, _snap([2.0], 1))
    col.retire("wb")
    assert "mxobs_push_age_seconds_r1" not in _metrics.all_metrics()
    assert col.ranks() == [0]
    adopted = list(col.token.describe()["names"])
    col.close()
    assert col.closed
    assert col.token.describe()["closed"]
    for name in adopted:
        assert name not in _metrics.all_metrics(), name
    # close is idempotent, and a closed collector drops pushes
    col.close()
    col.push("wc", 2, _snap([1.0], 1))
    assert col.merged()["hosts"] == 0


def test_collector_export_jsonl_and_prometheus(tmp_path):
    col = MetricsCollector("unit3")
    try:
        col.push("wa", 0, _snap([1.0, 2.0], 4))
        path = os.path.join(str(tmp_path), "fleet.jsonl")
        assert col.export_jsonl(path)
        doc = json.loads(open(path).read().splitlines()[-1])
        assert doc["merged"]["obs_t_c"] == 4
        assert not col.export_jsonl("")  # off when no sink configured
        prom = col.to_prometheus()
        assert "obs_t_h_pod_count 2" in prom
        assert 'obs_t_c{rank="0"} 4' in prom
        assert "# TYPE obs_t_c_pod counter" in prom
    finally:
        col.close()


def test_fleet_probe_flags_stale_push():
    config.set_flag("MXOBS_PUSH_INTERVAL_S", 0.05)
    col = MetricsCollector("unit4")
    try:
        col.push("wa", 0, _snap([1.0], 1))
        probe = fleet_probe(col, stale_factor=3.0)
        assert probe() == []  # fresh
        with col._lock:
            col._hosts["wa"].mono -= 60.0  # age the snapshot
        out = probe()
        assert len(out) == 1
        f = out[0]
        assert f.check == "obs-push-stale" and f.severity == "warn"
        assert "r0" in f.obj
    finally:
        col.close()


# ---------------------------------------------------------------------------
# obslint: the collector-lifecycle audit
# ---------------------------------------------------------------------------

def test_obslint_bad_fixture_fires_every_check():
    rows = [
        {"name": "a", "closed": False, "owner_closed": True,
         "adopted": [], "ranks": []},
        {"name": "b", "closed": True, "owner_closed": False,
         "adopted": [], "ranks": []},
        {"name": "c", "closed": True, "owner_closed": True,
         "adopted": ["mxobs_pushes_total"], "ranks": []},
        {"name": "d", "closed": False, "owner_closed": False,
         "adopted": ["mxobs_push_age_seconds_r7"], "ranks": [0]},
    ]
    live = ["mxobs_pushes_total", "mxobs_push_age_seconds_r7"]
    checks = {f.check for f in
              ObsLint().run({"collectors": rows, "live": live})}
    assert checks == {"collector-no-owner",
                      "closed-collector-open-owner",
                      "collector-leaked-instruments",
                      "stale-rank-gauge"}


def test_obslint_clean_fixture_and_tracked_rank_quiet():
    rows = [{"name": "ok", "closed": False, "owner_closed": False,
             "adopted": ["mxobs_push_age_seconds_r0"], "ranks": [0]}]
    assert lint_collectors(rows, ["mxobs_push_age_seconds_r0"]) == []
    # an age gauge the collector did NOT adopt is someone else's
    rows = [{"name": "ok", "closed": False, "owner_closed": False,
             "adopted": [], "ranks": []}]
    assert lint_collectors(rows, ["mxobs_push_age_seconds_r3"]) == []


def test_obslint_live_path_clean_for_wellformed_collector():
    col = MetricsCollector("unit5")
    try:
        col.push("wa", 0, _snap([1.0], 1))
        mine = [f for f in ObsLint().run(None) if "unit5" in f.obj]
        assert mine == []
    finally:
        col.close()


# ---------------------------------------------------------------------------
# coordinator obs surface: uid flags, dump epochs, push/merge RPC ops
# ---------------------------------------------------------------------------

def test_coordinator_flags_carry_pod_uid_only_when_obs_on(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    co = ElasticCoordinator()
    co.register("w0", (0,))
    _, flags = co.heartbeat("w0")
    assert flags["pod_uid"] == co.uid
    assert len(co.uid) == 8
    assert "dump_epoch" not in flags  # no dump requested yet
    config.set_flag("MXOBS", False)
    _, flags = co.heartbeat("w0")
    assert "pod_uid" not in flags  # structurally absent when off
    assert co.request_dump("off") == 0  # and no epochs minted
    config.set_flag("MXOBS", True)

    ep = co.request_dump("unit-dump")
    assert ep == 1
    _, flags = co.heartbeat("w0")
    assert flags["dump_epoch"] == 1
    assert flags["dump_reason"] == "unit-dump"
    # same reason inside the coalesce window: same epoch
    assert co.request_dump("unit-dump") == 1
    # a different reason is a new incident
    assert co.request_dump("other-cause") == 2
    d = co.describe()["obs"]
    assert d["uid"] == co.uid and d["dump_epoch"] == 2


def test_coordinator_obs_push_merge_and_retire(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    co = ElasticCoordinator()
    co.register("w0", (0,))
    co.register("w1", (1,))
    co.obs_push("w0", snap=_snap([1.0], 1))  # rank derived from view
    co.obs_push("w1", snap=_snap([2.0, 3.0], 2))
    doc = co.obs_merged()
    assert doc["hosts"] == 2
    assert doc["merged"]["obs_t_h"]["count"] == 3
    ranks = {doc["ranks"][k]["worker"]: int(k) for k in doc["ranks"]}
    assert ranks == {"w0": 0, "w1": 1}
    # departure retires the host's snapshot + gauge
    co.leave("w1")
    assert co.obs_merged()["hosts"] == 1
    assert "mxobs_push_age_seconds_r1" not in _metrics.all_metrics()
    col = co.obs_collector(create=False)
    col.close()


def test_coordinator_obs_collector_not_created_when_off():
    config.set_flag("MXOBS", False)
    co = ElasticCoordinator()
    co.register("w0", (0,))
    assert co.obs_collector() is None
    assert co.obs_merged() is None


# ---------------------------------------------------------------------------
# mxprof --dir stitcher: rebase + rank tagging + dedup
# ---------------------------------------------------------------------------

def test_load_spans_dir_stitches_rebases_and_dedups(tmp_path):
    mxprof = _load_tool("mxprof")
    root = {"name": "pod.step", "subsystem": "pod",
            "trace_id": "podaag1s0", "span_id": "podaag1s0.root",
            "parent_id": None, "ts_us": 500.0, "dur_us": 1000.0,
            "wall": 100.0}
    child0 = {"name": "train.step", "subsystem": "train",
              "trace_id": "podaag1s0", "span_id": "s1",
              "parent_id": "podaag1s0.root", "ts_us": 510.0,
              "dur_us": 980.0, "wall": 100.00001}
    child1 = {"name": "train.step", "subsystem": "train",
              "trace_id": "podaag1s0", "span_id": "s2",
              "parent_id": "podaag1s0.root",
              "ts_us": 999_510.0,  # different monotonic origin
              "dur_us": 980.0, "wall": 100.00002}
    with open(os.path.join(str(tmp_path), "f-r0-a.jsonl"), "w") as f:
        for s in (root, child0):
            f.write(json.dumps(s) + "\n")
    with open(os.path.join(str(tmp_path), "f-r1-a.jsonl"), "w") as f:
        for s in (child1, root):  # root duplicated across files
            f.write(json.dumps(s) + "\n")
    spans = mxprof.load_spans_dir(str(tmp_path))
    assert len(spans) == 3  # dedup kept one root
    by_id = {s["span_id"]: s for s in spans}
    assert by_id["podaag1s0.root"]["attrs"]["rank"] == 0
    assert by_id["s2"]["attrs"]["rank"] == 1
    # rebased onto the wall clock: cross-rank order is real now
    assert by_id["s1"]["ts_us"] == pytest.approx(100.00001 * 1e6)
    assert by_id["s2"]["ts_us"] - by_id["s1"]["ts_us"] == \
        pytest.approx(10.0)
    # and the stitched tree is a single rooted, orphanless trace
    trees = mxprof._trace_trees(spans)
    tree = trees["podaag1s0"]
    assert not tree["orphans"] and len(tree["roots"]) == 1
    cov = mxprof._interval_coverage(tree["roots"][0], tree["spans"])
    assert cov == pytest.approx(0.99, abs=0.005)  # union [10,1000]us
