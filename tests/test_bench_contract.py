"""Bench contract: bench.py emits one parseable JSON line with the
required keys, on the CPU only when MXTPU_BENCH_FORCE_CPU=1 says so."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_emits_parseable_json_line():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",  # the only way to the CPU
        "MXTPU_BENCH_BATCH": "4",
        "MXTPU_BENCH_STEPS": "2",
        "MXTPU_BENCH_AMP": "0",
        "MXTPU_BENCH_EAGER_STEPS": "1",  # keys present, minimal cost
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline", "fused_step",
                "fused_step_speedup", "recompiles_after_step2"):
        assert key in data, data
    assert data["metric"] == "resnet50_train_throughput"
    assert data["value"] is not None and data["value"] > 0, data
    assert data["platform"] == "cpu"
    # the fused-step steady-state contract: the signature cache closes
    # after warmup — zero recompiles across the timed steps
    assert data["fused_step"] is True
    assert data["recompiles_after_step2"] == 0, data


@pytest.mark.slow
def test_bench_graph_opt_emits_mxopt_speedup():
    """--graph-opt contract: one mxopt_speedup JSON line with the
    per-level series (step time, rewrites, census) for both bench
    models, and ZERO recompiles across the interleaved timed phase at
    every level."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_GRAPHOPT_STEPS": "3",
        "MXTPU_BENCH_GRAPHOPT_BATCH": "4",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--graph-opt"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxopt_speedup"
    assert data["value"] is not None and data["value"] > 0, data
    models = {s["model"]: s for s in data["series"]}
    assert set(models) == {"resnet", "lm"}
    for s in models.values():
        assert s["recompiles_after_warmup"] == 0, s
        by_level = {r["level"]: r for r in s["levels"]}
        assert set(by_level) == {0, 1, 2}
        assert by_level[0]["rewrites"] == 0
        assert by_level[2]["rewrites"] > 0
        assert all(r["step_s"] > 0 for r in s["levels"])
    assert models["resnet"]["levels"][2]["fused_census"].get(
        "conv_bn_relu", 0) >= 1
    assert models["lm"]["levels"][2]["fused_census"].get(
        "attention", 0) >= 1


@pytest.mark.slow
def test_bench_serving3_emits_mxserve3_speedup():
    """--serving3 contract: one mxserve3_speedup JSON line — the
    per-leg ablation matrix (prefix/spec/quant on/off) on templated +
    unique mixes, greedy parity on every exact config, zero request
    errors, zero after-warmup recompiles across every engine, the
    open-loop p50/p99 rows, and the >=1.8x int8 capacity-at-equal-
    bytes ratio. Reduced knobs keep this a contract check (shape +
    invariants); the acceptance-scale >=2x speedup comes from the
    default knobs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_SERVE3_REQUESTS": "6",
        "MXTPU_BENCH_SERVE3_MAX_NEW": "8",
        "MXTPU_BENCH_SERVE3_DMODEL": "32",
        "MXTPU_BENCH_SERVE3_LAYERS": "2",
        "MXTPU_BENCH_SERVE3_INFLIGHT": "4",
        "MXTPU_BENCH_SERVE3_PROMPT": "48",
        "MXTPU_BENCH_SERVE3_TEMPLATE": "32",
        "MXTPU_BENCH_SERVE3_SPEC_K": "2",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--serving3"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxserve3_speedup"
    assert data["errors"] == 0, data
    assert data["recompiles_after_warmup"] == 0, data
    assert data["parity_ok"] is True, data
    assert data["value"] is not None and data["value"] > 0, data
    assert data["quant_capacity_ratio"] >= 1.8, data
    cfgs = data["configs"]
    assert set(cfgs) == {"serve2_base", "prefix", "spec", "quant_int8",
                         "prefix_spec", "prefix_quant"}, cfgs.keys()
    for name, entry in cfgs.items():
        for mix in ("templated", "unique"):
            row = entry[mix]
            assert row["rps"] > 0, (name, mix, row)
            assert row["errors"] == 0, (name, mix, row)
            assert row["p99_ms"] >= row["p50_ms"] > 0, (name, mix, row)
        # every f32 config must be greedy-parity exact
        if entry["legs"]["kv"] == "f32":
            assert entry["parity"] is True, (name, entry)
    assert cfgs["prefix"]["templated"]["prefill_tokens_avoided"] > 0
    assert cfgs["prefix_spec"]["templated"]["acceptance_rate"] is not None
    for row in data["open_loop"].values():
        assert row["errors"] == 0 and row["p99_ms"] > 0, row


@pytest.mark.slow
def test_bench_pod_emits_mxpod_recovery():
    """--pod contract: one mxpod_recovery JSON line from the
    subprocess 3-phase drill (full pod -> SIGKILL one host -> warm
    rejoin) vs uninterrupted, with the acceptance gates pinned:
    recovery ratio >= 0.6, zero recompiles beyond the per-world
    update re-key, rejoin synced from the GROUP (no checkpoint file),
    loss delta inside MXELASTIC_LOSS_TOL."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_POD_HOSTS": "3",
        "MXTPU_BENCH_POD_STEPS": "14",
        "MXTPU_BENCH_POD_KILL_STEP": "5",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--pod"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxpod_recovery"
    for key in ("value", "unit", "recovery_s", "steps_lost",
                "world_after_kill", "rate_full_samples_per_s",
                "rate_shrunk_samples_per_s", "recompiles_after_rebuild",
                "rekeys", "final_loss", "baseline_loss",
                "loss_delta_rel", "loss_tol",
                "rejoin_synced_from_group", "recovered"):
        assert key in data, (key, data)
    assert data["value"] is not None and data["value"] >= 0.6, data
    assert data["recompiles_after_rebuild"] == 0, data
    assert data["rejoin_synced_from_group"] is True, data
    assert data["loss_delta_rel"] <= data["loss_tol"], data
    assert data["recovered"] is True, data
    # the re-key budget, per finishing host: one grad program ever,
    # one update program per world size it trained at
    for wid, rk in data["rekeys"].items():
        assert rk["grad"] == 1, (wid, data["rekeys"])
        assert rk["update"] == len(rk["worlds"]), (wid, data["rekeys"])


@pytest.mark.slow
def test_bench_fleet_emits_mxfleet_slo():
    """--fleet contract: one mxfleet_slo JSON line from the 3-leg
    disaggregated-serving loadgen (single-host router baseline, the
    2-decode + 1-prefill subprocess fleet, and the mid-load host-kill
    availability leg), with the zero-drop gate pinned: the SIGKILLed
    host must not drop a single accepted request."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_FLEET_REQUESTS": "12",
        "MXTPU_BENCH_FLEET_RATE_QPS": "2.0",
        "MXTPU_BENCH_FLEET_KILL_REQUESTS": "10",
        "MXTPU_BENCH_TIMEOUT": "900",
        "MXTPU_BENCH_STORE": "0",  # reduced knobs: numbers are not
        # comparable to the default-scale trajectory
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--fleet"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxfleet_slo"
    for key in ("value", "unit", "decode_hosts", "prefill_hosts",
                "offered_qps", "slo_ms", "single_qps", "single_p99_ms",
                "single_goodput_qps", "fleet_qps", "fleet_p99_ms",
                "fleet_goodput_qps", "fleet_prefix_hit_rate",
                "kill_requests", "kill_completed", "kill_dropped",
                "kill_fault_fired", "fleet_beats_single", "zero_drop"):
        assert key in data, (key, data)
    assert data["single_failures"] == 0, data
    assert data["fleet_failures"] == 0, data
    assert data["kill_fault_fired"] is True, data
    assert data["kill_dropped"] == 0, data
    assert data["zero_drop"] is True, data


@pytest.mark.slow
def test_bench_trace_overhead_emits_mxtrace_overhead():
    """--trace-overhead contract: one mxtrace_overhead JSON line with
    both phase overheads (traced vs untraced fused training with
    guard taps on + serve2 predicts), and ZERO recompiles with the
    MXTRACE flag flipping every call — tracing must never re-key a
    program. Reduced knobs keep this a contract check (shape +
    invariants); the acceptance-scale <2% overhead gate (trace_ok)
    comes from the default knobs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_TRACE_STEPS": "6",
        "MXTPU_BENCH_TRACE_REQUESTS": "6",
        "MXTPU_BENCH_TRACE_MAX_NEW": "8",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--trace-overhead"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxtrace_overhead"
    assert data["value"] is not None and data["value"] > 0, data
    assert data["recompiles_after_warmup"] == 0, data
    assert data["sample"] == 1.0
    for key in ("train_overhead_pct", "serve_overhead_pct",
                "train_untraced_step_s", "serve_untraced_req_s",
                "trace_ok"):
        assert key in data, data
    assert data["train_untraced_step_s"] > 0
    assert data["serve_untraced_req_s"] > 0
    assert data["recorder_subsystems"].get("train", 0) > 0
    assert data["recorder_subsystems"].get("serve2", 0) > 0


@pytest.mark.slow
def test_bench_san_overhead_emits_mxsan_overhead():
    """--san-overhead contract: one mxsan_overhead JSON line with the
    sanitized/plain soak ratio, the STRUCTURAL zero-cost proof
    (MXSAN=0 constructs the plain stdlib primitives — there is no
    wrapper to pay for), and evidence the sanitizer watched the run
    (lock-order edges recorded, zero cycles in serve2's own lock
    discipline). Reduced knobs keep this a contract check (shape +
    invariants); the acceptance-scale <5% gate (san_ok) comes from
    the default knobs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXSAN", None)  # construction-time flag: the bench owns it
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_SAN_PAIRS": "4",
        "MXTPU_BENCH_SAN_REQUESTS": "8",
        "MXTPU_BENCH_SAN_MAX_NEW": "8",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--san-overhead"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxsan_overhead"
    assert data["value"] is not None and data["value"] > 0, data
    # the zero-cost half of the contract is structural, so it holds
    # at ANY knob scale: MXSAN=0 must hand out plain primitives
    assert data["san_off_plain_locks"] is True, data
    # the sanitizer really watched the sanitized arm
    assert data["lock_order_edges"] >= 1, data
    assert data["lock_order_cycles"] == 0, data
    assert data["watched_locks"] >= 1, data
    for key in ("overhead_pct", "plain_round_s", "sanitized_round_s",
                "san_ok", "wave"):
        assert key in data, data
    assert data["plain_round_s"] > 0
    assert data["sanitized_round_s"] > 0


@pytest.mark.slow
def test_bench_obs_overhead_emits_mxobs_overhead(tmp_path):
    """--obs-overhead contract: one mxobs_overhead JSON line with the
    obs-on/obs-off fused-step ratio, the STRUCTURAL zero-cost proof
    (MXOBS=0 puts nothing on the wire: no pod uid on flags, no _trace
    field, no derived step context), zero recompiles with the flag
    flipping every block, and the pod uid absorbed from heartbeat
    flags while obs was on. Also pins satellite (f): the emitted line
    lands in the benchstore trajectory by default (MXOBS_BENCHSTORE
    redirects it; MXTPU_BENCH_STORE=0 is the escape hatch). Reduced
    knobs keep this a contract check; the acceptance-scale <2% gate
    (obs_ok) comes from the default knobs."""
    store = str(tmp_path / "store.jsonl")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_OBS_PAIRS": "3",
        "MXTPU_BENCH_OBS_HIDDEN": "32",
        "MXTPU_BENCH_TIMEOUT": "900",
        "MXOBS_BENCHSTORE": store,
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--obs-overhead"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxobs_overhead"
    assert data["value"] is not None and data["value"] > 0, data
    # the zero-cost half is structural, so it holds at ANY knob scale
    assert data["obs_off_structural"] is True, data
    assert data["pod_uid_absorbed"] is True, data
    assert data["recompiles_after_warmup"] == 0, data
    for key in ("obs_off_step_s", "obs_on_step_s", "overhead_pct",
                "obs_ok", "pairs"):
        assert key in data, data
    assert data["obs_off_step_s"] > 0 and data["obs_on_step_s"] > 0
    # satellite (f): the metric line was appended to the trajectory
    # store the moment _emit printed it
    with open(store) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    assert any(r["metric"] == "mxobs_overhead" and
               r["value"] == data["value"] for r in recs), recs


@pytest.mark.slow
def test_bench_store_escape_hatch_and_regress_roundtrip(tmp_path):
    """MXTPU_BENCH_STORE=0 keeps a bench run out of the trajectory
    store, and `mxprof regress` gates a store seeded with a 2x
    slowdown (exit 2) while staying green on an unchanged re-run —
    the CLI half of the benchstore acceptance drill."""
    store = str(tmp_path / "store.jsonl")
    base = dict(os.environ)
    base.pop("XLA_FLAGS", None)
    base["MXOBS_BENCHSTORE"] = store

    # escape hatch: _emit fires, nothing lands in the store
    env = dict(base, MXTPU_BENCH_STORE="0")
    code = ("import bench, sys; sys.path.insert(0, '.');"
            "bench._emit(1.5, unit='s', metric='esc_overhead')")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])[
        "metric"] == "esc_overhead"
    assert not os.path.exists(store)

    # default-on: three baseline appends + an unchanged newest
    for _ in range(4):
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT,
            capture_output=True, text=True, timeout=120, env=base)
        assert proc.returncode == 0, proc.stderr[-800:]
    assert os.path.exists(store)
    regress = [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
               "regress", "--store", store, "--json"]
    proc = subprocess.run(regress, capture_output=True, text=True,
                          timeout=120, env=base)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # seed a 2x slowdown on the lower-is-better metric: exit 2
    proc = subprocess.run(
        [sys.executable, "-c",
         code.replace("bench._emit(1.5", "bench._emit(3.0")],
        cwd=ROOT, capture_output=True, text=True, timeout=120, env=base)
    assert proc.returncode == 0, proc.stderr[-800:]
    proc = subprocess.run(regress, capture_output=True, text=True,
                          timeout=120, env=base)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert any(f["check"] == "perf-regression" and
               f["severity"] == "error" and "esc_overhead" in f["obj"]
               for f in rep["findings"]), rep


@pytest.mark.slow
def test_bench_serving2_emits_mxserve2_throughput():
    """--serving2 contract: one mxserve2_throughput JSON line — serve2
    requests/sec, the PR-3 single-engine baseline and the speedup, zero
    after-warmup recompiles across BOTH phases, zero request errors,
    and a rolling reload performed mid-load with zero dropped requests.
    Reduced knobs keep this a contract check (shape + invariants);
    the acceptance-scale speedup number comes from the default knobs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_SERVE2_LM_REQUESTS": "8",
        "MXTPU_BENCH_SERVE2_CNN_REQUESTS": "8",
        "MXTPU_BENCH_SERVE2_CONCURRENCY": "8",
        "MXTPU_BENCH_SERVE2_MAX_NEW": "48",
        "MXTPU_BENCH_SERVE2_DMODEL": "64",
        "MXTPU_BENCH_SERVE2_INFLIGHT": "8",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--serving2"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxserve2_throughput"
    assert data["value"] is not None and data["value"] > 0, data
    assert data["errors"] == 0 and data["baseline_errors"] == 0, data
    assert data["recompiles_after_warmup"] == 0, data
    assert data["speedup_vs_single_engine"] is not None \
        and data["speedup_vs_single_engine"] > 1.0, data
    assert data["reload_during_load"] is True, data
    assert data["reload_dropped"] == 0, data
    assert data["reload_new_version"] == 2, data
    assert data["open_errors"] == 0, data
    assert data["open_p99_ms"] >= data["open_p50_ms"] > 0, data


@pytest.mark.slow
def test_bench_pipe_emits_mxpipe_scaling():
    """--pipe contract: one mxpipe_scaling JSON line from the
    stage-scaling legs (1 and 2 stages with reduced knobs), with the
    acceptance gates pinned: pipelined final loss matches the 1-stage
    leg within PIPE_TOL_REL (bit-identical on CPU), zero post-warmup
    recompiles on every leg, and per-stage parameter bytes shrinking
    with the stage count (value = 1-stage / max-stage ratio > 1)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_PIPE_STAGES": "1,2",
        "MXTPU_BENCH_PIPE_STEPS": "4",
        "MXTPU_BENCH_PIPE_LAYERS": "4",
        "MXTPU_BENCH_PIPE_DMODEL": "16",
        "MXTPU_BENCH_PIPE_SEQ": "8",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--pipe"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxpipe_scaling"
    for key in ("value", "unit", "schedule", "legs", "final_losses",
                "parity_rel", "parity_tol", "parity_ok",
                "recompiles_after_warmup_zero"):
        assert key in data, (key, data)
    assert data["parity_ok"] is True, data
    assert data["parity_rel"] <= data["parity_tol"], data
    assert data["recompiles_after_warmup_zero"] is True, data
    assert data["value"] is not None and data["value"] > 1.0, data
    assert set(data["legs"]) == {"1", "2"}, data["legs"]
    for leg in data["legs"].values():
        assert leg["recompiles_after_warmup"] == 0, leg
        assert leg["step_time_s"] > 0, leg
        assert len(leg["stage_param_bytes"]) == leg["n_stage"], leg


@pytest.mark.slow
def test_bench_tune_emits_mxtune_search():
    """--tune contract: one mxtune_search JSON line; the auto-applied
    config must match the search best, reproduce with ZERO post-warmup
    recompiles, and the gate fields must be present. Reduced knobs
    keep this a contract check (shape + invariants); the
    acceptance-scale >=1.05x gate comes from the default knobs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "MXTPU_BENCH_FORCE_CPU": "1",
        "MXTPU_BENCH_STORE": "0",
        "MXTPU_BENCH_TUNE_BUDGET": "4",
        "MXTPU_BENCH_TUNE_STEPS": "3",
        "MXTPU_BENCH_TUNE_REQUESTS": "10",
        "MXTPU_BENCH_TIMEOUT": "900",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--tune"],
        capture_output=True, text=True, timeout=960, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, \
        f"no JSON line:\n{proc.stdout[-800:]}\n{proc.stderr[-400:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "mxtune_search"
    assert data["value"] is not None and data["value"] > 0, data
    # the apply path is the contract: what search found is what bind
    # got, it compiled warm, and the DB holds the trials
    assert data["auto_applied"] is True, data
    assert data["recompiles_after_apply"] == 0, data
    assert data["db_records"] >= 2, data
    assert "tune_ok" in data and "threshold" in data
    for leg in ("fuse_step", "serve2"):
        assert data[f"{leg}_baseline"] > 0, data
        assert data[f"{leg}_trials_measured"] >= 1, data
        assert data[f"{leg}_recompiles_after_apply"] == 0, data


@pytest.mark.slow
def test_benchstore_committed_store_schema_and_dedupe():
    """Every record in the committed perf-trajectory store must be
    schema-valid, and loading must be dedupe-idempotent (a
    double-ingested artifact never double-weights the median)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import benchstore
    path = os.path.join(ROOT, "tools", "benchstore.jsonl")
    recs = benchstore.load(path)
    assert recs, "committed store is empty"
    for r in recs:
        assert benchstore.validate(r) == [], \
            f"schema problems in committed store: " \
            f"{benchstore.validate(r)}\n{json.dumps(r)[:300]}"
    assert benchstore.dedupe(recs) == recs  # load() already deduped
    # dedupe actually drops an exact duplicate
    assert len(benchstore.dedupe(recs + [dict(recs[0])])) == len(recs)
    # validate() actually rejects the degenerate shapes
    assert benchstore.validate({"metric": "m"})  # missing fields
    assert benchstore.validate(
        dict(recs[0], value="fast"))  # wrong type
    assert benchstore.validate(
        dict(recs[0], value=float("nan")))  # non-finite
