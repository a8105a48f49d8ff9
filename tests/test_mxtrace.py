"""mxtrace (ISSUE 13): correlated cross-subsystem tracing — span
model + contextvar/cross-thread propagation, JSONL/chrome export, the
crash flight recorder and its failure-site dumps, per-request phase
decomposition with outcome-tagged endpoint latency, the recompile
auditor's new kind/reason coverage, the metriclint owner-token audit,
and the mxprof trace analyzer (orphans, coverage, critical path).

The two acceptance drills: one loadgen request against a routed
serve3 engine and one elastic+guard training drill each produce a
SINGLE trace with >=90% wall coverage and zero orphan spans (verified
through the mxprof analyzer), and a forced breaker trip / guard
quarantine each leave a flight-recorder dump naming the failing site.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, nd, telemetry, trace
from mxnet_tpu.telemetry import metrics as _metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mxprof():
    spec = importlib.util.spec_from_file_location(
        "mxprof_under_test", os.path.join(ROOT, "tools", "mxprof.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_trace():
    trace.reset()
    yield
    trace.reset()
    for f in ("MXTRACE", "MXTRACE_SAMPLE", "MXTRACE_EXPORT",
              "MXTRACE_DUMP_DIR"):
        config.unset_flag(f)


def _coverage(root, spans):
    r0, r1 = root["ts_us"], root["ts_us"] + root["dur_us"]
    ivals = sorted(
        (max(r0, s["ts_us"]), min(r1, s["ts_us"] + s["dur_us"]))
        for s in spans if s is not root and s.get("dur_us") is not None)
    cov, end = 0.0, r0
    for a, b in ivals:
        a = max(a, end)
        if b > a:
            cov += b - a
            end = b
    return cov / (r1 - r0)


# ---------------------------------------------------------------------------
# span model units
# ---------------------------------------------------------------------------

def test_span_nesting_and_ids():
    with trace.span("root", "serve", model="m") as sp:
        tid = sp.trace_id
        assert tid and sp.parent_id is None
        with trace.span("child", "serve2") as c:
            assert c.trace_id == tid and c.parent_id == sp.span_id
    assert trace.current_context() is None
    spans = trace.drain()
    assert [s["name"] for s in spans] == ["root", "child"]
    assert spans[1]["parent_id"] == spans[0]["span_id"]
    assert spans[0]["attrs"]["model"] == "m"
    assert all(s["dur_us"] >= 0 for s in spans)


def test_span_error_status():
    with pytest.raises(ValueError):
        with trace.span("boom", "app"):
            raise ValueError("bad news")
    (s,) = trace.drain()
    assert s["status"] == "error"
    assert s["attrs"]["error"] == "ValueError"
    assert "bad news" in s["attrs"]["error_msg"]


def test_cross_thread_emit_and_under():
    with trace.span("root", "serve") as sp:
        ctx = trace.current_context()
    t0 = time.perf_counter_ns()
    e = trace.emit("phase", "serve2", t0, t0 + 2_000_000, parent=ctx,
                   attrs={"sid": 7})
    assert abs(e.duration_s - 0.002) < 1e-9
    with trace.under(ctx):
        with trace.span("live", "serve2"):
            pass
    spans = {s["name"]: s for s in trace.drain()}
    assert spans["phase"]["parent_id"] == sp.span_id
    assert spans["live"]["parent_id"] == sp.span_id
    assert spans["phase"]["trace_id"] == sp.trace_id
    # emit with no parent records nothing
    assert trace.emit("orphanless", "x", t0, t0 + 1, parent=None) is None


def test_sampling_and_disable():
    config.set_flag("MXTRACE_SAMPLE", 0.0)
    with trace.span("dropped", "app") as sp:
        assert sp.span_id == ""  # null span
        ctx = trace.current_context()
        assert ctx is not None and ctx.sampled is False
        with trace.span("child-of-dropped", "app"):
            pass  # inherits the drop
    assert trace.drain() == []
    config.unset_flag("MXTRACE_SAMPLE")
    config.set_flag("MXTRACE", False)
    with trace.span("off", "app"):
        assert trace.current_context() is None
    config.unset_flag("MXTRACE")
    assert trace.drain() == []


def test_export_jsonl_and_chrome_roundtrip(tmp_path):
    sink = str(tmp_path / "spans.jsonl")
    config.set_flag("MXTRACE_EXPORT", sink)
    with trace.span("outer", "train", step=3):
        with trace.span("inner", "elastic"):
            pass
    config.unset_flag("MXTRACE_EXPORT")
    trace.export.reset_sink()
    loaded = trace.load_spans(sink)
    assert [s["name"] for s in loaded] == ["inner", "outer"] or \
        [s["name"] for s in loaded] == ["outer", "inner"]
    chrome = str(tmp_path / "spans.json")
    trace.write_chrome(chrome, loaded)
    back = trace.load_spans(chrome)
    assert {s["name"] for s in back} == {"outer", "inner"}
    by_name = {s["name"]: s for s in back}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["attrs"]["step"] == 3


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_recorder_rings_bounded_and_dump(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    config.set_flag("MXTRACE_RECORDER_SPANS", 8)
    sink = str(tmp_path / "crash_spans.jsonl")
    config.set_flag("MXTRACE_EXPORT", sink)
    for i in range(30):
        with trace.span(f"s{i}", "serve2"):
            pass
    rec = trace.get_recorder()
    ring = rec.spans("serve2")
    assert len(ring) == 8  # bounded
    assert ring[-1]["name"] == "s29"
    path = trace.crash_dump("engine_crashed", site="lm/r0",
                            extra={"error": "boom"}, force=True)
    assert path and os.path.dirname(path) == str(tmp_path)
    doc = json.load(open(path))
    assert doc["reason"] == "engine_crashed"
    assert doc["site"] == "lm/r0"
    assert doc["extra"]["error"] == "boom"
    assert doc["events"][-1]["name"] == "engine_crashed"
    assert [s["name"] for s in doc["spans"]["serve2"]][-1] == "s29"
    assert "metrics" in doc and "recompiles" in doc
    assert rec.last_dump["reason"] == "engine_crashed"
    # the dump flushed the batched export sink: the spans preceding
    # the failure are on disk WITHOUT waiting for the 64-line cadence
    assert len(trace.load_spans(sink)) == 30
    config.unset_flag("MXTRACE_EXPORT")
    trace.export.reset_sink()
    config.unset_flag("MXTRACE_RECORDER_SPANS")


def test_dump_rate_limit_and_gating(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    p1 = trace.crash_dump("breaker_trip", site="a")
    p2 = trace.crash_dump("breaker_trip", site="b")  # rate-limited
    p3 = trace.crash_dump("breaker_trip", site="c", force=True)
    assert p1 and p3 and p2 is None
    config.set_flag("MXTRACE", False)
    assert trace.crash_dump("breaker_trip", force=True) is None
    config.unset_flag("MXTRACE")


def test_breaker_trip_dumps_flight_recorder(tmp_path):
    from mxnet_tpu.resil.policy import CircuitBreaker
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    with trace.span("serve.request", "serve"):
        pass  # something for the dump to show
    br = CircuitBreaker(name="lm/r1", failure_threshold=2,
                        cooldown_s=30.0)
    br.record_failure()
    assert trace.get_recorder().last_dump is None or \
        trace.get_recorder().last_dump["reason"] != "breaker_trip"
    br.record_failure()  # trips
    ld = trace.get_recorder().last_dump
    assert ld is not None and ld["reason"] == "breaker_trip"
    assert ld["site"] == "lm/r1"
    doc = json.load(open(ld["path"]))
    assert doc["extra"]["consecutive_failures"] == 2
    crash_events = [e for e in doc["events"]
                    if e["name"] == "breaker_trip"]
    assert crash_events and crash_events[-1]["attrs"]["site"] == "lm/r1"


def test_watchdog_stall_dumps_recorder(tmp_path):
    from mxnet_tpu.resil.watchdog import Watchdog
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    clock = [100.0]
    wd = Watchdog(stall_after_s=5.0, clock=lambda: clock[0])
    wd.beat(step_seconds=0.1)
    clock[0] += 60.0
    findings = wd.check()
    stall = [f for f in findings if f.check == "stall"]
    assert stall, findings
    ld = trace.get_recorder().last_dump
    assert ld is not None and ld["reason"] == "watchdog_stall"
    assert ld["path"] in stall[0].message


def test_sigterm_dump_in_subprocess(tmp_path):
    script = (
        "import os, signal, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"os.environ['MXTRACE_DUMP_DIR'] = {str(tmp_path)!r}\n"
        "from mxnet_tpu import trace\n"
        "assert trace.install_signal_handler()\n"
        "with trace.span('doomed', 'train'):\n"
        "    pass\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "print('UNREACHABLE')\n")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=240,
                          cwd=ROOT)
    assert "UNREACHABLE" not in proc.stdout
    assert proc.returncode != 0  # killed by the chained default
    dumps = [f for f in os.listdir(tmp_path) if "sigterm" in f]
    assert dumps, (proc.stdout, proc.stderr[-500:],
                   os.listdir(tmp_path))
    doc = json.load(open(os.path.join(tmp_path, dumps[0])))
    assert doc["reason"] == "sigterm"
    assert any(s["name"] == "doomed"
               for s in doc["spans"].get("train", []))


# ---------------------------------------------------------------------------
# serving hot path (acceptance: routed serve3, one trace, >=90%, no
# orphans, X-MXTrace-Id echoed, outcome-tagged latency)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve3_stack():
    from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
    from mxnet_tpu.serve.endpoint import ModelRegistry, ServingEndpoint
    from mxnet_tpu.serve2 import DecodeEngine
    from mxnet_tpu.serve2.router import Router
    params = init_pipeline_lm(0, vocab=32, d_model=16, n_layers=2,
                              n_heads=2, d_head=8, d_ff=32,
                              n_experts=2)
    router = Router("trace-test")

    def factory(version, replica):
        return DecodeEngine(
            params, page_size=4, num_pages=64, max_inflight=2,
            prefill_buckets=[8], max_new_default=16, max_seq_len=48,
            prefix_cache=True, name=f"tlm-v{version}-r{replica}")

    router.add_group("lm", factory, n_replicas=2)
    front = ModelRegistry()
    front.register("lm", router.frontend("lm"))
    ep = ServingEndpoint(front, port=0)
    ep.start()
    yield ep, router
    ep.stop()
    router.close()


def test_loadgen_request_single_trace_full_coverage(serve3_stack,
                                                    tmp_path):
    from mxnet_tpu.serve.loadgen import run_loadgen
    ep, router = serve3_stack
    url = ep.address + "/v1/models/lm:predict"
    sink = str(tmp_path / "serve_spans.jsonl")
    body = json.dumps({"inputs": [1, 2, 3, 4, 5]}).encode()

    def fire(payload):
        req = urllib.request.Request(
            url, data=payload,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            tids.append(resp.headers.get("X-MXTrace-Id"))
            return json.loads(resp.read())

    tids = []
    run_loadgen(fire, [body, body], concurrency=2)  # warm the stack
    tids.clear()
    config.set_flag("MXTRACE_EXPORT", sink)
    report = run_loadgen(fire, [body], concurrency=1)
    time.sleep(0.3)  # decode-phase emits land from the sched thread
    config.unset_flag("MXTRACE_EXPORT")
    trace.export.reset_sink()
    assert report["completed"] == 1 and not report["errors"]
    (tid,) = tids
    assert tid  # the endpoint echoed X-MXTrace-Id

    mxprof = _mxprof()
    spans = trace.load_spans(sink)
    mine = [s for s in spans if s["trace_id"] == tid]
    names = {s["name"] for s in mine}
    # the request decomposes across endpoint -> router -> scheduler ->
    # prefill/decode in ONE trace
    assert {"serve.request", "serve.route", "serve.attempt",
            "serve2.wait", "serve2.queue", "serve2.admit",
            "serve2.decode"} <= names, names
    assert names & {"serve2.prefill", "serve2.prefill_ext"}
    assert "serve2.prefix_lookup" in names  # serve3 leg traced too
    trees = mxprof._trace_trees(spans)
    tree = trees[tid]
    assert not tree["orphans"]
    (root,) = tree["roots"]
    assert root["name"] == "serve.request"
    cov = _coverage(root, tree["spans"])
    assert cov >= 0.9, (cov, sorted(names))
    # the analyzer agrees: no orphan/coverage findings for this trace
    findings = [f for f in mxprof.analyze_trace({tid: tree})
                if f.check in ("orphan-span", "trace-coverage-gap")]
    assert not findings, findings
    # per-phase histograms carry p50/p99 in the registry
    snap = telemetry.snapshot()
    for k in ("mxtrace_phase_queue_seconds",
              "mxtrace_phase_admission_seconds",
              "mxtrace_phase_prefill_seconds",
              "mxtrace_phase_decode_seconds"):
        assert snap[k]["count"] >= 1, k
        assert snap[k]["p50"] is not None and snap[k]["p99"] is not None


def test_endpoint_latency_tagged_by_outcome(serve3_stack):
    ep, _ = serve3_stack
    url = ep.address + "/v1/models/lm:predict"
    base = _metrics.histogram("mxserve_request_seconds").count
    ok_before = _metrics.histogram("mxserve_request_seconds_ok").count
    bad_before = _metrics.histogram(
        "mxserve_request_seconds_bad_request").count
    urllib.request.urlopen(urllib.request.Request(
        url, data=json.dumps({"inputs": [1, 2, 3]}).encode()))
    # error path: malformed body — 400s must land in the histograms
    # too (error storms move p99 instead of vanishing from it)
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(urllib.request.Request(
            url, data=b"this is not json"))
    assert ei.value.code == 400
    assert ei.value.headers.get("X-MXTrace-Id")  # traced even on 400
    assert _metrics.histogram("mxserve_request_seconds").count \
        == base + 2
    assert _metrics.histogram("mxserve_request_seconds_ok").count \
        == ok_before + 1
    assert _metrics.histogram(
        "mxserve_request_seconds_bad_request").count == bad_before + 1


def test_all_replicas_down_maps_to_unavailable_outcome(serve3_stack):
    from mxnet_tpu.serve.engine import InputSpec
    ep, router = serve3_stack

    class _Boom:
        name = "boom"
        warmed = True
        input_specs = [InputSpec((4,), "float32", name="x")]

        def predict(self, data, timeout_ms=None):
            raise RuntimeError("replica dead")

        def warmup(self, input_specs=None):
            return []

        def stats(self):
            return {"name": "boom"}

        def drain(self, timeout=None):
            return True

        def close(self):
            pass

        def queue_depth(self):
            return 0

    router.add_group("boom", lambda v, r: _Boom(), n_replicas=1,
                     warmup=False)
    ep.registry.register("boom", router.frontend("boom"))
    before = _metrics.histogram(
        "mxserve_request_seconds_unavailable").count
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(urllib.request.Request(
            ep.address + "/v1/models/boom:predict",
            data=json.dumps({"inputs": [1, 2, 3, 4]}).encode()))
    # a whole-group outage is a retryable 503 in the 'unavailable'
    # outcome histogram — NOT a client-tagged 400
    assert ei.value.code == 503
    assert _metrics.histogram(
        "mxserve_request_seconds_unavailable").count == before + 1


def test_engine_crash_leaves_dump_naming_site(tmp_path):
    from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
    from mxnet_tpu.serve2 import DecodeEngine
    from mxnet_tpu.serve2.scheduler import EngineCrashedError
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    params = init_pipeline_lm(0, vocab=32, d_model=16, n_layers=2,
                              n_heads=2, d_head=8, d_ff=32,
                              n_experts=2)
    engine = DecodeEngine(params, page_size=4, num_pages=16,
                          max_inflight=2, prefill_buckets=[8],
                          max_new_default=4, max_seq_len=16,
                          name="crash-me")
    engine.warmup()
    engine.lm.prefill = None  # scheduler thread dies on first admit
    h = engine.submit(onp.asarray([1, 2, 3], "int32"))
    assert h.wait(30.0)
    assert isinstance(h.error, EngineCrashedError)
    ld = trace.get_recorder().last_dump
    assert ld is not None and ld["reason"] == "engine_crashed"
    assert ld["site"] == "crash-me"
    doc = json.load(open(ld["path"]))
    assert "TypeError" in doc["extra"]["error"]
    engine.close()


# ---------------------------------------------------------------------------
# training hot path (acceptance: elastic+guard drill -> one trace per
# step keyed by (generation, step), quarantine dump names the worker)
# ---------------------------------------------------------------------------

def test_elastic_guard_drill_traces_and_quarantine_dump(tmp_path):
    from mxnet_tpu.elastic.drill import run_elastic_drill
    mxprof = _mxprof()
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    report = run_elastic_drill(
        n_workers=2, steps=8, kill_step=3, kill_rank=1, action="sdc",
        batch=4, hb_interval=0.1, timeout_s=180.0)
    assert report["guard"]["quarantined"] == ["w1"]

    # the quarantine froze a dump whose final spans name the vote/
    # re-execution at the failing worker — and (mxobs) the leader
    # boundary ALSO broadcast a coordinated pod dump for the incident
    assert trace.get_recorder().last_dump is not None
    dumps = sorted(os.listdir(str(tmp_path)))
    quarantine = [f for f in dumps if "-guard_quarantine-" in f]
    assert quarantine, dumps
    assert any("pod-dump-guard-quarantine" in f for f in dumps), dumps
    doc = json.load(open(os.path.join(str(tmp_path), quarantine[-1])))
    assert doc["reason"] == "guard_quarantine"
    assert doc["site"] == "w1"
    guard_spans = [s["name"] for s in doc["spans"].get("guard", [])]
    assert "guard.vote" in guard_spans or "guard.reexec" in guard_spans
    assert any(e["name"] == "guard_quarantine" for e in doc["events"])

    # per-step traces: pick a completed survivor step span set from
    # the recorder and check the tree through the mxprof analyzer
    spans = trace.get_recorder().spans()
    steps = [s for s in spans if s["name"] == "train.step"
             and s["attrs"].get("kind") == "ElasticStepFunction"]
    assert steps, "no elastic step roots recorded"
    # keyed by (generation, step)
    assert all("generation" in s["attrs"] and "step" in s["attrs"]
               for s in steps)
    trees = mxprof._trace_trees(spans)
    checked = 0
    for root in steps:
        tree = trees[root["trace_id"]]
        if len(tree["spans"]) < 3:
            continue  # ring-truncated step (children aged out)
        assert not tree["orphans"], tree["orphans"]
        names = {s["name"] for s in tree["spans"]}
        if root["status"] != "ok":
            # the quarantined worker's final step dies mid-vote: its
            # trace legitimately never reaches the exchange
            continue
        assert "step.grads" in names and "step.exchange" in names
        cov = _coverage(root, tree["spans"])
        if cov >= 0.9:
            checked += 1
    assert checked >= 1, "no fully-covered elastic step trace found"
    # guarded steps carry the vote under the same trace
    voted = [t for t in trees.values()
             if any(s["name"] == "guard.vote" for s in t["spans"])
             and any(s["name"] == "train.step" for s in t["spans"])]
    assert voted, "guard.vote never landed inside a train.step trace"


def test_plain_fused_step_trace():
    mx.random.seed(0)
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    fused = trainer.fuse_step(net, gluon.loss.L2Loss())
    rng = onp.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (4, 8)).astype("float32"))
    y = nd.array(onp.zeros((4, 4), "float32"))
    trace.drain()
    fused.step(x, y)
    spans = trace.drain()
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "train.step"
    names = {s["name"] for s in spans}
    assert {"step.compile", "step.dispatch",
            "step.writeback"} <= names
    ids = {s["span_id"] for s in spans}
    assert all(s["parent_id"] in ids for s in spans if s["parent_id"])
    # steady state: no compile span, same trace shape
    fused.step(x, y)
    names2 = {s["name"] for s in trace.drain()}
    assert "step.compile" not in names2
    assert "step.dispatch" in names2


# ---------------------------------------------------------------------------
# the fused step's spans on the profile's clock, its CPU time, and the
# scope names inside the step program (ISSUE 25)
# ---------------------------------------------------------------------------

STEP_TREE = {"train.step": None, "step.prep": "train.step",
             "step.prep.hyper": "step.prep",
             "step.prep.gather": "step.prep",
             "step.prep.rng": "step.prep",
             "step.dispatch": "train.step",
             "step.writeback": "train.step"}


def _dense_step():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, in_units=8, activation="relu"),
            gluon.nn.Dense(4, in_units=8))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    fused = trainer.fuse_step(net, gluon.loss.L2Loss())
    rng = onp.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (4, 8)).astype("float32"))
    y = nd.array(onp.zeros((4, 4), "float32"))
    return fused, x, y


def _bert_step():
    from mxnet_tpu import models
    mx.random.seed(0)
    net = models.BERTModel(vocab_size=32, units=16, num_layers=2,
                           num_heads=2, hidden_size=32, max_len=8,
                           dropout=0.1)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    fused = trainer.fuse_step(net,
                              gluon.loss.SoftmaxCrossEntropyLoss())
    x = nd.array(onp.ones((2, 8), "int32"))
    y = nd.array(onp.zeros((2, 8), "float32"))
    return fused, x, y


@pytest.fixture(scope="module")
def warm_step_spans():
    """The span dicts of one steady fused step (the second: no
    compile)."""
    fused, x, y = _dense_step()
    fused.step(x, y)
    trace.drain()
    fused.step(x, y)
    return trace.drain()


@pytest.mark.parametrize("name", sorted(STEP_TREE))
def test_fused_step_tree_node_parent_and_cpu(warm_step_spans, name):
    by_name = {s["name"]: s for s in warm_step_spans}
    assert set(by_name) == set(STEP_TREE)  # the names docs/tools read
    node = by_name[name]
    parent = STEP_TREE[name]
    assert node["parent_id"] == (by_name[parent]["span_id"]
                                 if parent else None)
    # the thread's CPU time is inside the wall interval; a kernel that
    # accounts it by scheduler ticks (the chip's machine: 10 ms) may
    # round it up by one
    cpu_ns = node["attrs"]["cpu_ns"]
    assert node["dur_us"] * 1e3 + 10e6 >= cpu_ns >= 0


def test_fused_step_prep_children_count_their_work(warm_step_spans):
    by_name = {s["name"]: s for s in warm_step_spans}
    # a rate and a weight decay for each of the 4 trainable leaves, in
    # one host array
    assert by_name["step.prep.hyper"]["attrs"]["leaves"] == 4
    assert by_name["step.prep.gather"]["attrs"]["leaves"] >= 4
    prep = by_name["step.prep"]
    inside = sum(by_name[n]["dur_us"] for n in STEP_TREE
                 if STEP_TREE[n] == "step.prep")
    assert inside <= prep["dur_us"]


def test_cpu_time_is_asked_for_and_inherited():
    with trace.span("plain", "app"):
        with trace.span("plain.child", "app"):
            pass
    with trace.span("timed", "app", cpu=True):
        with trace.span("timed.child", "app"):
            pass
    spans = {s["name"]: s for s in trace.drain()}
    assert "cpu_ns" not in spans["plain"]["attrs"]
    assert "cpu_ns" not in spans["plain.child"]["attrs"]
    assert spans["timed"]["attrs"]["cpu_ns"] >= \
        spans["timed.child"]["attrs"]["cpu_ns"] >= 0


def _host_events(logdir):
    """name -> [stats dict, ...] of the profile's /host:CPU plane."""
    import glob
    import jax
    (path,) = glob.glob(os.path.join(str(logdir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return found


@pytest.fixture(scope="module")
def profiled_step(tmp_path_factory):
    """(events of the profile's host plane, the program's spans) of two
    fused steps run under ``jax.profiler.trace``, with one retroactive
    ``emit`` among them."""
    import jax
    fused, x, y = _dense_step()
    fused.step(x, y)
    trace.drain()
    logdir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(logdir)):
        for _ in range(2):
            fused.step(x, y)._data.block_until_ready()
        with trace.span("outer", "app") as sp:
            t0 = time.perf_counter_ns()
            trace.emit("retroactive", "app", t0 - 1000, t0,
                       parent=sp.context())
    return _host_events(logdir), trace.drain()


@pytest.mark.parametrize("name", sorted(STEP_TREE))
def test_profile_host_plane_holds_the_programs_spans(profiled_step,
                                                     name):
    events, spans = profiled_step
    assert len(events[name]) == 2 == sum(s["name"] == name
                                         for s in spans)
    # on the profile's clock the children lie inside the step's root
    roots = events["train.step"]
    for start, dur, _ in events[name]:
        assert any(r0 <= start and start + dur <= r0 + rd
                   for r0, rd, _ in roots)


def test_profile_root_is_a_step_annotation_and_emit_stays_out(
        profiled_step):
    events, spans = profiled_step
    steps = sorted(st["step_num"] for _, _, st in events["train.step"])
    assert steps == sorted(s["attrs"]["step"] for s in spans
                           if s["name"] == "train.step")
    assert "outer" in events
    assert "retroactive" not in events
    assert any(s["name"] == "retroactive" for s in spans)


def test_mxtrace_off_leaves_no_annotation_and_no_span(tmp_path):
    import jax
    fused, x, y = _dense_step()
    fused.step(x, y)
    trace.drain()
    config.set_flag("MXTRACE", False)
    try:
        with jax.profiler.trace(str(tmp_path)):
            fused.step(x, y)._data.block_until_ready()
    finally:
        config.unset_flag("MXTRACE")
    assert trace.drain() == []
    assert not set(STEP_TREE) & set(_host_events(tmp_path))


@pytest.fixture(scope="module", params=["dense", "bert"])
def step_op_paths(request):
    """(which net, the ``op_name`` paths of the compiled step's HLO),
    with the MXNet profiler never started."""
    import re
    from mxnet_tpu import profiler
    assert not profiler.is_running()
    fused, x, y = {"dense": _dense_step, "bert": _bert_step}[
        request.param]()
    fused.step(x, y)
    text = fused.compiled(x, y).as_text()
    return request.param, set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("what", ["forward", "backward", "optimizer",
                                  "child_block", "op"])
def test_step_hlo_carries_scope_names_without_the_profiler(
        step_op_paths, what):
    net, paths = step_op_paths
    block, op = {"dense": ("1", "FullyConnected"),
                 "bert": ("attn", "LayerNorm")}[net]
    want = {
        "forward": lambda p: "/jvp(forward)/" in p,
        "backward": lambda p: "/transpose(jvp(forward))/" in p,
        "optimizer": lambda p: "/optimizer/" in p,
        "child_block": lambda p: f"/jvp(forward)/{block}/" in p
        or f"/jvp(forward)/layers/0/{block}/" in p,
        "op": lambda p: f"/{op}/" in p and "forward" in p,
    }[what]
    assert any(want(p) for p in paths), sorted(paths)[:20]


# ---------------------------------------------------------------------------
# recompile auditor kinds (satellite: fused_step / serving2 /
# plan-fingerprint keys each classify a forced miss with its shapes)
# ---------------------------------------------------------------------------

def _records_for(entry_prefix):
    return [r for r in telemetry.recompile_report()
            if r["entry"].startswith(entry_prefix)]


@pytest.mark.parametrize("kind", ["fused_step", "serving2",
                                  "plan_fingerprint"])
def test_recompile_auditor_kind_classifies_forced_miss(kind):
    telemetry.reset_recompiles()
    if kind == "fused_step":
        mx.random.seed(0)
        net = gluon.nn.Dense(3, in_units=6)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01})
        fused = trainer.fuse_step(net, gluon.loss.L2Loss())
        rng = onp.random.RandomState(0)
        for b in (4, 6):  # the classic loose-batch retrace
            fused.step(
                nd.array(rng.uniform(-1, 1, (b, 6)).astype("float32")),
                nd.array(onp.zeros((b, 3), "float32")))
        recs = _records_for("StepFunction:")
        assert [r["reason"] for r in recs] == ["first-compile",
                                               "shape-change"]
        assert all(r["kind"] == "fused_step" for r in recs)
        # the triggering shapes ride the record
        assert recs[1]["signature"]["inputs"][0]["shape"] == [6, 6]
    elif kind == "serving2":
        from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
        from mxnet_tpu.serve2 import DecodeEngine
        params = init_pipeline_lm(0, vocab=32, d_model=16, n_layers=2,
                                  n_heads=2, d_head=8, d_ff=32,
                                  n_experts=2)
        engine = DecodeEngine(params, page_size=4, num_pages=16,
                              max_inflight=2, prefill_buckets=[4, 8],
                              max_new_default=2, max_seq_len=16,
                              name="rk-serving2")
        try:  # unwarmed on purpose: every program is a forced miss
            engine.predict(onp.asarray([1, 2, 3], "int32"))
            engine.predict(onp.asarray([1, 2, 3, 4, 5], "int32"))
        finally:
            engine.close()
        recs = _records_for("PagedLM:rk-serving2")
        assert recs and all(r["kind"] == "serving2" for r in recs)
        prefills = [r for r in recs
                    if r["signature"].get("program") == "prefill"]
        assert [r["signature"]["inputs"][0]["shape"]
                for r in prefills] == [[4], [8]]
        assert prefills[0]["reason"] == "first-compile"
        assert prefills[1]["reason"] == "shape-change"
    else:  # plan-fingerprint keys (sharded step re-plan)
        from mxnet_tpu.shard import ShardPlan
        from mxnet_tpu.shard.stepfn import ShardedStepFunction

        def build(zero):
            mx.random.seed(0)
            net = gluon.nn.Dense(4, in_units=8)
            net.initialize()
            return ShardedStepFunction(
                net, gluon.loss.L2Loss(),
                shard_plan=ShardPlan(zero=zero), name="plankind")

        rng = onp.random.RandomState(0)
        x = nd.array(rng.uniform(-1, 1, (8, 8)).astype("float32"))
        y = nd.array(onp.zeros((8, 4), "float32"))
        build(True).step(x, y)
        build(False).step(x, y)  # same shapes, different plan
        recs = _records_for("StepFunction:plankind")
        assert [r["reason"] for r in recs] == ["first-compile",
                                               "key-change"]
        assert recs[0]["signature"]["plan"] != \
            recs[1]["signature"]["plan"]
        assert recs[0]["signature"]["inputs"] == \
            recs[1]["signature"]["inputs"]


# ---------------------------------------------------------------------------
# metriclint (satellite: closed owner with live gauges = the leak)
# ---------------------------------------------------------------------------

def test_metriclint_flags_closed_owner_live_gauge():
    from mxnet_tpu.passes.metriclint import MetricLint
    p = MetricLint()
    tok = _metrics.owner("Test:leaky")
    g = _metrics.gauge("mxtest_leak_gauge_tmp", "leak fixture")
    tok.adopt(g)
    assert not [f for f in p.run()
                if f.obj == "mxtest_leak_gauge_tmp"]  # open: clean
    tok.close()  # closed WITHOUT unregistering: the leak
    fired = [f for f in p.run()
             if f.check == "closed-owner-live-gauge"
             and f.obj == "mxtest_leak_gauge_tmp"]
    assert fired and fired[0].severity == "error"
    _metrics.unregister(g.name)  # retire properly -> clean again
    assert not [f for f in p.run()
                if f.obj == "mxtest_leak_gauge_tmp"]
    assert tok.leaked() == []


def test_metriclint_fixture_mode_and_registration():
    from mxnet_tpu.passes import default_manager
    from mxnet_tpu.passes.metriclint import MetricLint
    assert "metriclint" in default_manager().names()
    bad = {"owners": [{"owner": "<e>", "closed": True,
                       "names": ["g1", "g2"]},
                      {"owner": "<empty>", "closed": True,
                       "names": []}],
           "live": ["g1"]}
    findings = MetricLint().run(bad)
    checks = {f.check for f in findings}
    assert "closed-owner-live-gauge" in checks
    assert "owner-no-instruments" in checks
    leaked = [f for f in findings
              if f.check == "closed-owner-live-gauge"]
    assert [f.obj for f in leaked] == ["g1"]  # g2 is not live


def test_engine_and_router_retire_owned_gauges():
    from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
    from mxnet_tpu.passes.metriclint import MetricLint
    from mxnet_tpu.serve2 import DecodeEngine
    from mxnet_tpu.serve2.router import Router
    params = init_pipeline_lm(0, vocab=32, d_model=16, n_layers=2,
                              n_heads=2, d_head=8, d_ff=32,
                              n_experts=2)
    router = Router("owner-test")
    router.add_group(
        "olm", lambda v, r: DecodeEngine(
            params, page_size=4, num_pages=16, max_inflight=2,
            prefill_buckets=[8], max_new_default=2, max_seq_len=16,
            name=f"olm-v{v}-r{r}"),
        n_replicas=2, warmup=False)
    live = set(_metrics.all_metrics())
    assert any(n.startswith("mxserve2_replica_depth_olm") for n in live)
    router.close()
    errs = [f for f in MetricLint().run()
            if f.severity == "error" and "olm" in f.obj]
    assert not errs, errs
    live = set(_metrics.all_metrics())
    assert not any(n.startswith("mxserve2_replica_depth_olm")
                   for n in live)
    assert not any(n.startswith("mxserve2_inflight_seqs_olm")
                   for n in live)


# ---------------------------------------------------------------------------
# mxprof trace analyzer (bad-fixture coverage: the findings must fire)
# ---------------------------------------------------------------------------

def _mk_span(tid, sid, parent, name, sub, ts, dur):
    return {"trace_id": tid, "span_id": sid, "parent_id": parent,
            "name": name, "subsystem": sub, "ts_us": ts,
            "dur_us": dur, "thread": 1, "status": "ok", "attrs": {}}


def test_mxprof_trace_analyzer_fires_on_bad_fixtures(tmp_path):
    mxprof = _mxprof()
    spans = [
        # trace A: orphan (parent x99 missing)
        _mk_span("A", "a1", None, "root", "serve", 0.0, 1000.0),
        _mk_span("A", "a2", "x99", "lost", "serve2", 100.0, 100.0),
        # trace B: root with one tiny child -> coverage gap (the
        # unattributed hole must also clear the 1 ms absolute floor)
        _mk_span("B", "b1", None, "root", "train", 0.0, 5000.0),
        _mk_span("B", "b2", "b1", "sliver", "train", 0.0, 50.0),
        # trace C: clean, fully covered
        _mk_span("C", "c1", None, "root", "serve", 0.0, 1000.0),
        _mk_span("C", "c2", "c1", "body", "serve2", 10.0, 985.0),
    ]
    trees = mxprof._trace_trees(spans)
    findings = mxprof.analyze_trace(trees)
    by_check = {}
    for f in findings:
        by_check.setdefault(f.check, []).append(f.obj)
    assert any("A/" in o for o in by_check["orphan-span"])
    assert any("B/" in o for o in by_check["trace-coverage-gap"])
    assert not any("C/" in o for vals in by_check.values()
                   for o in vals)
    # CLI round-trip on a written file
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    rc = mxprof.main(["trace", path, "--json"])
    assert rc == 2  # orphan-span is error severity


def test_mxprof_trace_critical_path_and_gaps():
    mxprof = _mxprof()
    spans = [
        _mk_span("T", "t1", None, "serve.request", "serve", 0.0,
                 1000.0),
        _mk_span("T", "t2", "t1", "serve.route", "serve2", 20.0,
                 900.0),
        _mk_span("T", "t3", "t2", "serve2.admit", "serve2", 200.0,
                 700.0),
        _mk_span("T", "t4", "t1", "serve.respond", "serve", 940.0,
                 55.0),
    ]
    trees = mxprof._trace_trees(spans)
    tree = trees["T"]
    path = mxprof._critical_path(tree, tree["roots"][0])
    assert [s["name"] for s in path] == [
        "serve.request", "serve.route", "serve2.admit"]
    gaps = mxprof._subsystem_gaps(tree, tree["roots"][0])
    assert gaps and gaps[0]["from"] == "serve2.admit"
    assert gaps[0]["to"] == "serve.respond"


# ---------------------------------------------------------------------------
# CLI surfaces (slow: subprocess imports)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mxlint_metrics_cli():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
         "--metrics", "--json"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-2000:] + \
        proc.stderr[-500:]
    rep = json.loads(proc.stdout)
    assert rep["summary"]["error"] == 0
    assert any(s["pass"] == "metriclint" for s in rep["sections"])


@pytest.mark.slow
def test_mxprof_trace_cli_on_flight_dump(tmp_path):
    config.set_flag("MXTRACE_DUMP_DIR", str(tmp_path))
    with trace.span("serve.request", "serve"):
        with trace.span("serve.route", "serve2"):
            pass
    path = trace.crash_dump("breaker_trip", site="r9", force=True)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
         "trace", path, "--json"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode in (0, 2), proc.stderr[-500:]
    rep = json.loads(proc.stdout)
    assert rep["n_spans"] >= 2
    names = {t["root"] for t in rep["traces"]}
    assert "serve.request" in names
