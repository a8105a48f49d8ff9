#!/usr/bin/env python
"""Environment diagnosis (ref: tools/diagnose.py — dump platform,
package versions, hardware and environment variables for bug reports).
"""
import os
import platform
import subprocess
import sys


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    print("------------Pip Info-----------")
    try:
        import pip
        print("Version      :", pip.__version__)
    except ImportError:
        print("No corresponding pip install for current python.")


def check_mxnet():
    print("----------MXNet-TPU Info-----------")
    try:
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        import jax
        # a chip belongs to one process: a bug-report dump stays off
        # it unless asked (--tpu), so it can run beside a training job
        if "--tpu" not in sys.argv:
            jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        print("Version      :", mx.__version__)
        print("Directory    :", os.path.dirname(mx.__file__))
        from mxnet_tpu.runtime import Features
        feats = Features()
        enabled = [f for f in feats if feats.is_enabled(f)]
        print("Num features :", len(list(feats)))
        print("Enabled      :", ", ".join(sorted(enabled)[:12]), "...")
    except Exception as e:
        print("Import error :", e)


def check_hardware():
    print("----------Hardware Info----------")
    print("Machine      :", platform.machine())
    print("Processor    :", platform.processor() or "n/a")
    if sys.platform.startswith("linux"):
        try:
            out = subprocess.run(["lscpu"], capture_output=True,
                                 text=True, timeout=10).stdout
            for line in out.splitlines():
                if any(k in line for k in ("Model name", "CPU(s):",
                                           "Thread", "Socket")):
                    print(line.strip())
        except Exception:
            pass
    # in this process, on the platform check_mxnet settled: a child
    # could not open a chip this process holds
    try:
        import jax
        print("JAX devices  :", jax.devices())
    except Exception as e:
        print("JAX devices  : unavailable (%s)" % e)


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def check_environment():
    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "MXTPU_", "JAX_", "XLA_", "OMP_",
                         "KMP_", "DMLC_")):
            print(f"{k}=\"{v}\"")


def check_mxlint():
    """Static-analysis health: run the fast (no-probe) registry audit and
    report finding counts (tools/mxlint.py; see docs/passes.md)."""
    print("----------mxlint Status----------")
    import json
    mxlint = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mxlint.py")
    try:
        out = subprocess.run(
            [sys.executable, mxlint, "--ops", "--no-probe", "--json"],
            capture_output=True, text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("mxlint       : TIMED OUT")
        return
    if out.returncode not in (0, 2):
        print(f"mxlint       : failed (rc={out.returncode}): "
              f"{out.stderr.strip()[-200:]}")
        return
    try:
        summary = json.loads(out.stdout)["summary"]
    except (ValueError, KeyError) as e:
        print(f"mxlint       : unparseable output ({e})")
        return
    status = "clean" if out.returncode == 0 else "FINDINGS"
    print(f"mxlint       : {status} — {summary['error']} error(s), "
          f"{summary['warn']} warning(s), {summary['info']} note(s) "
          f"[static checks only; run `python tools/mxlint.py --all` "
          f"for the full audit]")


def check_telemetry():
    """Runtime observability health: profiler state, metrics snapshot,
    recompile count (mxnet_tpu/telemetry/; docs/observability.md)."""
    print("----------Telemetry----------")
    try:
        from mxnet_tpu import profiler, telemetry
    except Exception as e:
        print("telemetry    : unavailable (%s)" % e)
        return
    state = "running" if profiler.is_running() else "stopped"
    if profiler.is_paused():
        state += " (paused)"
    print("profiler     :", state)
    enabled = [d for d in ("symbolic", "imperative", "memory", "api")
               if profiler._domain_enabled(d)]
    print("domains      :", ", ".join(enabled) or "none")
    print("recompiles   :", telemetry.recompile_count())
    snap = telemetry.snapshot()
    print("metrics      :", len(snap), "instrument(s)")
    for k, v in sorted(snap.items())[:10]:
        print(f"  {k} = {v}")
    from mxnet_tpu.base import get_env
    sink = get_env("MXNET_METRICS_EXPORT", "")
    print("export sink  :", sink or "(off)")


def check_trace():
    """mxtrace health: flag values, the per-phase latency histograms,
    and the crash flight recorder's rings/dump state read DIRECTLY
    (mxnet_tpu/trace/; docs/observability.md)."""
    print("----------Tracing (mxtrace)----------")
    try:
        from mxnet_tpu import config, telemetry, trace
    except Exception as e:
        print("trace        : unavailable (%s)" % e)
        return
    on = config.get("MXTRACE")
    print("tracing      :", "ON" if on else "(off — set MXTRACE=1)")
    print("sampling     :", config.get("MXTRACE_SAMPLE"),
          "(fraction of root traces recorded)")
    sink = config.get("MXTRACE_EXPORT")
    print("export sink  :", sink or "(off — in-memory recorder only)")
    print("recorder     : %s span(s)/subsystem ring cap, dumps to %s"
          % (config.get("MXTRACE_RECORDER_SPANS"),
             config.get("MXTRACE_DUMP_DIR") or "<tempdir>/mxtrace"))
    rec = trace.get_recorder().describe()
    if rec["subsystems"]:
        print("rings        :",
              ", ".join(f"{s}={n}"
                        for s, n in rec["subsystems"].items()))
    else:
        print("rings        : empty (no traced work in this process)")
    if rec["last_dump"]:
        ld = rec["last_dump"]
        print(f"  LAST DUMP  : {ld['reason']}"
              + (f" (site {ld['site']})" if ld.get("site") else "")
              + f" -> {ld['path']}")
        print("    read it with: python tools/mxprof.py trace "
              f"{ld['path']}")
    # pod view: dump filenames are rank-tagged (-r<k>-), so the dump
    # DIRECTORY holds one timeline per rank after a coordinated
    # capture — show the newest per rank, not just this process's
    dump_dir = str(config.get("MXTRACE_DUMP_DIR") or "")
    per_rank = _newest_dumps_per_rank(dump_dir)
    if per_rank:
        print(f"  POD DUMPS  : {len(per_rank)} rank(s) in {dump_dir}")
        for rank in sorted(per_rank):
            print(f"    r{rank}: {os.path.basename(per_rank[rank])}")
        print("    stitch them with: python tools/mxprof.py trace "
              f"--dir {dump_dir}")


def _newest_dumps_per_rank(dump_dir):
    """Newest flight-dump file per rank in ``dump_dir`` ({rank:
    path}); filenames carry the rank as ``-r<k>-`` (trace.recorder)."""
    import re
    out = {}
    if not dump_dir or not os.path.isdir(dump_dir):
        return out
    try:
        names = os.listdir(dump_dir)
    except OSError:
        return out
    for fn in names:
        m = re.match(r"mxtrace-flight-.*-r(\d+)-p\d+-\d+\.json$", fn)
        if not m:
            continue
        rank = int(m.group(1))
        path = os.path.join(dump_dir, fn)
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            continue
        if rank not in out or mtime > out[rank][0]:
            out[rank] = (mtime, path)
    return {r: p for r, (t, p) in out.items()}
    snap = telemetry.snapshot()
    phases = {k: v for k, v in snap.items()
              if k.startswith("mxtrace_phase_")}
    for k, v in sorted(phases.items()):
        if isinstance(v, dict) and v.get("count"):
            print(f"  {k}: n={v['count']} p50={v.get('p50')} "
                  f"p99={v.get('p99')}")
    req = {k: v for k, v in snap.items()
           if k.startswith("mxserve_request_seconds")}
    for k, v in sorted(req.items()):
        if isinstance(v, dict) and v.get("count"):
            print(f"  {k}: n={v['count']} p99={v.get('p99')}")


def check_serving():
    """Serving-subsystem health: flag values, bucket-ladder program
    count, and the mxserve_* metrics (mxnet_tpu/serve/; docs/serving.md)."""
    print("----------Serving (mxserve)----------")
    try:
        from mxnet_tpu import config, serve, telemetry
    except Exception as e:
        print("serving      : unavailable (%s)" % e)
        return
    try:
        ladder = serve.default_ladder()
        print("buckets      :", ladder.spec())
    except Exception as e:
        print("buckets      : INVALID MXSERVE_BUCKETS (%s)" % e)
        ladder = None
    print("max linger   :", config.get("MXSERVE_MAX_LINGER_MS"), "ms")
    print("queue depth  :", config.get("MXSERVE_QUEUE_DEPTH"))
    max_batch = config.get("MXSERVE_MAX_BATCH")
    print("max batch    :", max_batch if max_batch
          else f"(top batch rung: {ladder.max_batch})" if ladder else "?")
    snap = telemetry.snapshot()
    served = {k: v for k, v in snap.items() if k.startswith("mxserve_")}
    if not served:
        print("metrics      : none (no engine has run in this process)")
        return
    for k, v in sorted(served.items()):
        print(f"  {k} = {v}")
    after = snap.get("mxserve_recompile_after_warmup_total", 0)
    if after:
        print(f"  WARNING: {after} recompile(s) after warmup — the "
              "bucket ladder does not close the jit cache")


def check_serving2():
    """Serving-v2 health: pool/scheduler flags and the mxserve2_*
    metrics (mxnet_tpu/serve2/; docs/serving.md v2 section)."""
    print("----------Serving v2 (mxserve2)----------")
    try:
        from mxnet_tpu import config, telemetry
    except Exception as e:
        print("serve2       : unavailable (%s)" % e)
        return
    page = config.get("MXSERVE2_PAGE_SIZE")
    pages = config.get("MXSERVE2_NUM_PAGES")
    print("kv pool      : %s pages x %s tokens (%s slots)"
          % (pages, page, pages * page))
    print("max inflight :", config.get("MXSERVE2_MAX_INFLIGHT"))
    print("decode steps :", config.get("MXSERVE2_DECODE_STEPS"),
          "(tokens per compiled dispatch)")
    print("prefill rungs:", config.get("MXSERVE2_PREFILL_BUCKETS"))
    print("replicas     :", config.get("MXSERVE2_REPLICAS"))
    print("reload drain :", config.get("MXSERVE2_RELOAD_DRAIN_TIMEOUT_S"),
          "s")
    print("prefix cache :", "on" if config.get("MXSERVE3_PREFIX_CACHE")
          else "off",
          "(cap %s pages)" % (config.get("MXSERVE3_PREFIX_CACHE_PAGES")
                              or "none"))
    print("spec tokens  :", config.get("MXSERVE3_SPEC_TOKENS"),
          "(draft proposals per tick; engines need draft_params)")
    print("kv dtype     :", config.get("MXSERVE3_KV_DTYPE"),
          "(page-pool storage; int8 ~4x positions per byte)")
    snap = telemetry.snapshot()
    served = {k: v for k, v in snap.items()
              if k.startswith(("mxserve2_", "mxserve3_"))}
    if not served:
        print("metrics      : none (no serve2 engine has run in this "
              "process)")
        return
    for k, v in sorted(served.items()):
        print(f"  {k} = {v}")
    after = snap.get("mxserve2_recompile_after_warmup_total", 0)
    if after:
        print(f"  WARNING: {after} decode/prefill compile(s) after "
              "warmup — some caller bypassed the rung ladder "
              "(run tools/mxlint.py --serve)")


def check_resilience():
    """Fault-tolerance health: active fault plan, retry/breaker/watchdog
    flags, breaker states, mxresil_* metrics, last emergency checkpoint
    (mxnet_tpu/resil/; docs/resilience.md)."""
    print("----------Resilience (mxresil)----------")
    try:
        from mxnet_tpu import config, telemetry
        from mxnet_tpu.resil import active_plan, guard, hooks
    except Exception as e:
        print("resilience   : unavailable (%s)" % e)
        return
    try:
        plan = active_plan()
        if plan is None:
            print("fault plan   : (off)")
        else:
            print(f"fault plan   : {plan.spec!r} "
                  f"({len(plan.clauses)} clause(s), seed {plan.seed})")
    except Exception as e:
        print("fault plan   : INVALID MXRESIL_FAULT_PLAN (%s)" % e)
    print("retry policy :", config.get("MXRESIL_RETRY_MAX"), "retries,",
          config.get("MXRESIL_RETRY_BASE_MS"), "->",
          config.get("MXRESIL_RETRY_MAX_MS"), "ms backoff")
    print("breaker      :", config.get("MXRESIL_BREAKER_FAILURES"),
          "failures trip;", config.get("MXRESIL_BREAKER_COOLDOWN_S"),
          "s cooldown")
    stall = config.get("MXRESIL_WATCHDOG_STALL_S")
    print("watchdog     :", f"{stall} s stall threshold" if stall
          else "auto stall threshold (10x step EWMA)")
    kv_ms = config.get("MXNET_KVSTORE_TIMEOUT_MS")
    print("kv timeout   :", f"{kv_ms} ms" if kv_ms
          else "(barrier-based default)")
    states = hooks.breaker_states()
    if states:
        for site, st in sorted(states.items()):
            print(f"  breaker {site}: {st['state']} "
                  f"({st['consecutive_failures']} consecutive failures)")
    else:
        print("breakers     : none created (no guarded site has run)")
    emergency = guard.last_emergency()
    print("emergency ckpt:", emergency or "(none this process)")
    snap = telemetry.snapshot()
    resil_metrics = {k: v for k, v in snap.items()
                     if k.startswith("mxresil_")}
    for k, v in sorted(resil_metrics.items()):
        print(f"  {k} = {v}")
    if not resil_metrics:
        print("metrics      : none (no resil hook has fired)")


def check_guard():
    """Integrity-layer health: MXGUARD flags, tap/vote/quarantine
    metrics, the last EWMA anomaly verdict and its replay window
    (mxnet_tpu/guard/; docs/resilience.md integrity section)."""
    print("----------Integrity (mxguard)----------")
    try:
        from mxnet_tpu import config, telemetry
        from mxnet_tpu.guard import anomaly
    except Exception as e:
        print("guard        : unavailable (%s)" % e)
        return
    on = config.get("MXGUARD")
    print("taps         :", "ON (fingerprints ride the fused step)"
          if on else "(off — set MXGUARD=1)")
    print("vote tol     :", config.get("MXGUARD_VOTE_TOL"),
          "(absmax factor over peer median)")
    print("anomaly      : %sx EWMA factor (report-only probe)"
          % config.get("MXGUARD_EWMA_FACTOR"))
    print("replay ring  : %s steps, known-good ckpt every %s"
          % (config.get("MXGUARD_RING"),
             config.get("MXGUARD_CKPT_EVERY")))
    snap = telemetry.snapshot()
    guard_metrics = {k: v for k, v in snap.items()
                     if k.startswith("mxguard_")}
    for k, v in sorted(guard_metrics.items()):
        print(f"  {k} = {v}")
    if not guard_metrics:
        print("metrics      : none (no guarded step has run)")
    last = anomaly.last_anomaly()
    print("last anomaly :", last or "(none this process)")
    if last:
        print("  -> replay window %s: python tools/mxresil.py replay "
              "--ring-dir <ring>" % (last.get("replay_window"),))
    if snap.get("mxresil_guard_unprotected"):
        print("  WARNING: a TrainGuard ran without checkpoint "
              "backing — a non-finite step was skipped with no "
              "rollback, or a preemption committed no emergency "
              "checkpoint (mxresil_guard_unprotected=1); attach a "
              "CheckpointManager + restore channel")
    quar = snap.get("mxguard_quarantines_total", 0)
    if quar:
        print(f"  NOTE: {quar} replica(s) quarantined for persistent "
              "corruption — triage the host before readmitting")


def check_elastic():
    """Elastic-membership health: MXELASTIC_* policy, the current
    generation/world gauges, rebuild/rejoin counters
    (mxnet_tpu/elastic/; docs/resilience.md elastic section)."""
    print("----------Elastic membership (mxelastic)----------")
    try:
        from mxnet_tpu import config, telemetry
    except Exception as e:
        print("elastic      : unavailable (%s)" % e)
        return
    hb = config.get("MXELASTIC_HEARTBEAT_S")
    miss = config.get("MXELASTIC_MISS_LIMIT")
    print("heartbeat    : every %ss, lost after %d misses (%.2fs)"
          % (hb, miss, float(hb) * int(miss)))
    print("min world    :", config.get("MXELASTIC_MIN_WORLD"),
          "(below this the group hard-fails)")
    print("lr scaling   :", "linear (base_lr x world/ref_world)"
          if config.get("MXELASTIC_LR_SCALE") else "off")
    print("loss tol     :", config.get("MXELASTIC_LOSS_TOL"),
          "(declared drill tolerance)")
    snap = telemetry.snapshot()
    elastic_metrics = {k: v for k, v in snap.items()
                       if k.startswith("mxelastic_")}
    if not elastic_metrics:
        print("metrics      : none (no elastic group in this process)")
        return
    for k, v in sorted(elastic_metrics.items()):
        print(f"  {k} = {v}")
    gen = snap.get("mxelastic_generation")
    world = snap.get("mxelastic_world_size")
    if gen is not None:
        print(f"group        : generation {gen}, world {world}")
    lost = snap.get("mxelastic_lost_workers_total", 0)
    rejoins = snap.get("mxelastic_rejoins_total", 0)
    if lost and not rejoins:
        print(f"  NOTE: {lost} worker(s) lost and none rejoined — "
              "running shrunk; restart the lost workers to rejoin "
              "from group state (docs/resilience.md runbook)")


def check_pod():
    """Multi-host pod runtime: MXPOD_* wiring, the live PodContext (if
    any), control-plane journal, host beat-age gauges and coordinator
    retry/lost counters (mxnet_tpu/pod/; docs/resilience.md multi-host
    section)."""
    print("----------Multi-host pod (mxpod)----------")
    try:
        from mxnet_tpu import config, telemetry
        from mxnet_tpu.pod import active_context
    except Exception as e:
        print("pod          : unavailable (%s)" % e)
        return
    coord = config.get("MXPOD_COORDINATOR") or \
        os.environ.get("MX_KV_SERVER") or "(none)"
    rank = int(config.get("MXPOD_RANK"))
    nprocs = int(config.get("MXPOD_NPROCS")) or \
        int(os.environ.get("MX_NUM_WORKERS", "1"))
    print("coordinator  :", coord)
    print("rank/nprocs  : %s / %d"
          % (rank if rank >= 0 else "(from launcher env)", nprocs))
    hb = float(config.get("MXPOD_HEARTBEAT_S"))
    print("heartbeat    :", ("%ss (overrides MXELASTIC_HEARTBEAT_S)"
                             % hb) if hb > 0
          else "MXELASTIC_HEARTBEAT_S=%s"
          % config.get("MXELASTIC_HEARTBEAT_S"))
    jdir = config.get("MXPOD_JOURNAL_DIR") or ""
    print("journal      :", jdir if jdir else
          "(none — a coordinator restart orphans the group; set "
          "MXPOD_JOURNAL_DIR)")
    print("grace        : %ss until CoordinatorLost"
          % config.get("MXPOD_COORDINATOR_GRACE_S"))
    ctx = active_context()
    if ctx is not None:
        d = ctx.describe()
        print("context      : rank %(rank)d/%(nprocs)d worker "
              "%(worker_id)s%(extra)s" % {
                  **d, "extra": (" [coordinator host]"
                                 if d["coordinator_host"] else "")
                  + (" [journal replayed]" if d["restored"] else "")})
        cp = d.get("control_plane")
        if cp:
            v = cp["view"]
            print("control plane: generation %s, world %s, members %s"
                  % (v["generation"], v["world_size"], v["workers"]))
            if cp.get("pending_joins"):
                print("  pending join(s):", cp["pending_joins"])
    else:
        print("context      : none (not a pod process)")
    snap = telemetry.snapshot()
    pod_metrics = {k: v for k, v in sorted(snap.items())
                   if k.startswith("mxpod_")}
    for k, v in pod_metrics.items():
        print(f"  {k} = {v}")
    lost = snap.get("mxpod_coordinator_lost_total", 0)
    if lost:
        print(f"  NOTE: {lost} waiter(s) raised CoordinatorLost — "
              "the control plane stayed down past the grace; check "
              "rank 0 and its journal (docs/resilience.md multi-host "
              "runbook)")


def check_pipe():
    """Pipeline-parallel config: MXPIPE_* policy (schedule, stage and
    microbatch counts, balance tolerance), the schedule's bubble math
    at the configured shape, and any live mxpipe compile counters
    (mxnet_tpu/pipe/; docs/pipeline.md)."""
    print("----------Pipeline parallelism (mxpipe)----------")
    try:
        from mxnet_tpu import config, telemetry
        from mxnet_tpu.pipe import build_schedule
    except Exception as e:
        print("pipe         : unavailable (%s)" % e)
        return
    kind = str(config.get("MXPIPE_SCHEDULE"))
    n_stage = int(config.get("MXPIPE_STAGES"))
    n_micro = int(config.get("MXPIPE_MICROBATCH"))
    print("schedule     :", kind)
    print("stages       :", n_stage if n_stage > 0 else
          "(auto — session world, or 1 without a session)")
    print("microbatches :", n_micro if n_micro > 0 else
          "(auto — one per stage)")
    print("balance tol  :", config.get("MXPIPE_BALANCE_TOL"),
          "(pipelint stage-imbalance threshold)")
    # bubble math at the configured (or representative) shape: the
    # schedule cost a user signs up for before any step runs
    S = n_stage if n_stage > 0 else 4
    M = n_micro if n_micro > 0 else S
    try:
        sched = build_schedule(kind, S, M)
        print("bubble       : %.3f at S=%d M=%d (%d ticks; raise the "
              "microbatch count to shrink it)"
              % (sched.bubble_fraction(), S, M, sched.n_ticks))
    except Exception as e:
        print("bubble       : schedule build failed (%s)" % e)
    snap = telemetry.snapshot()
    pipe_metrics = {k: v for k, v in sorted(snap.items())
                    if k.startswith("mxpipe_")}
    if not pipe_metrics:
        print("metrics      : none (no pipeline in this process)")
        return
    for k, v in pipe_metrics.items():
        print(f"  {k} = {v}")


def check_mxsan():
    """Concurrency sanitizer health: MXSAN flag state, which locks the
    runtime sanitizer is watching, the lock-order graph, any detected
    cycles or blocked-waiter events (mxnet_tpu/san/;
    docs/observability.md MXSAN runbook)."""
    print("----------Concurrency sanitizer (mxsan)----------")
    try:
        from mxnet_tpu import config
        from mxnet_tpu.san import runtime as san
    except Exception as e:
        print("mxsan        : unavailable (%s)" % e)
        return
    on = bool(config.get("MXSAN"))
    print("sanitizer    :", "ON" if on else
          "(off — set MXSAN=1 BEFORE import/construction; the flag "
          "is read when each lock is built)")
    print("block dump   : %sms until a waiter triggers a flight dump"
          % config.get("MXSAN_BLOCK_THRESHOLD_MS"))
    stats = san.lock_stats()
    if not stats:
        print("watched locks: none (nothing sanitized was built in "
              "this process)")
        return
    print("watched locks:", len(stats))
    for name, st in sorted(stats.items()):
        print(f"  {name} [{st['kind']}]: acq={st['acquisitions']} "
              f"cont={st['contentions']} "
              f"hold_max={st['hold_ms_max']}ms "
              f"wait_max={st['wait_ms_max']}ms")
    edges = san.order_graph()
    if edges:
        print("order graph  :", len(edges), "edge(s)")
        for e in edges[:12]:
            print(f"  {e['src']} -> {e['dst']} (x{e['count']}, "
                  f"{e['thread']})")
    cycles = san.cycle_findings()
    if cycles:
        print(f"  CYCLES      : {len(cycles)} lock-order cycle(s) — "
              "potential deadlock; both acquisition stacks are in "
              "san.report() and the flight recorder")
        for c in cycles[:4]:
            print("   ", " -> ".join(c["locks"]))
    blocked = san.blocked_events()
    if blocked:
        print(f"  BLOCKED     : {len(blocked)} waiter(s) past "
              "threshold; latest: %s waited %sms (holder at %s)"
              % (blocked[-1]["lock"], blocked[-1]["waited_ms"],
                 blocked[-1]["holder_site"]))


def check_obs():
    """Pod observability plane health: MXOBS flag state, the live pod
    collectors (hosts, pushes, owner tokens) and the
    trace-propagation gate (mxnet_tpu/obs/;
    docs/observability.md multi-host section)."""
    print("----------Pod observability (mxobs)----------")
    try:
        from mxnet_tpu import config
        from mxnet_tpu.obs import propagate as prop
        from mxnet_tpu.obs.collector import live_collectors
    except Exception as e:
        print("mxobs        : unavailable (%s)" % e)
        return
    on = bool(config.get("MXOBS"))
    print("obs plane    :", "ON" if on else "(off — set MXOBS=1)")
    print("propagation  :", "armed (spans ride the control plane)"
          if prop.enabled() else
          "(inert — needs MXOBS and MXTRACE both on)")
    print("push cadence :", config.get("MXOBS_PUSH_INTERVAL_S"),
          "s per host snapshot")
    sink = config.get("MXOBS_EXPORT")
    print("export sink  :", sink or "(off — query the collector "
                                    "via describe/obs_merged)")
    cols = live_collectors()
    if not cols:
        print("collectors   : none (not the rank-0 control-plane "
              "process, or no pod formed)")
    for col in cols:
        d = col.describe()
        hosts = d.get("hosts") or {}
        print(f"collector    : {d['name']!r} — {len(hosts)} host(s)"
              + (" CLOSED" if d.get("closed") else ""))
        for w, h in sorted(hosts.items()):
            print(f"  {w}: rank {h['rank']}, {h['pushes']} push(es)")


def check_fleet():
    """Disaggregated serving fleet health: MXFLEET_* policy knobs,
    and — when a coordinator address is in scope — the live fleet
    directory: per-worker role/depth/beat age, controller liveness,
    the last resize and the last autoscale decision
    (mxnet_tpu/fleet/; docs/fleet.md)."""
    print("----------Fleet serving (mxfleet)----------")
    try:
        from mxnet_tpu import config
    except Exception as e:
        print("mxfleet      : unavailable (%s)" % e)
        return
    print("affinity     :", "ON (first %d page keys)"
          % int(config.get("MXFLEET_AFFINITY_PAGES"))
          if bool(config.get("MXFLEET_AFFINITY"))
          else "(off — shallowest-queue only)")
    print("spill factor :", config.get("MXFLEET_SPILL_FACTOR"),
          "(x shallowest depth before affinity yields)")
    print("disagg       :", "ON (prefill pushed over pagewire, "
          "chunk %d pages)"
          % int(config.get("MXFLEET_PAGEWIRE_CHUNK_PAGES"))
          if bool(config.get("MXFLEET_PREFILL_DISAGG"))
          else "(off — every host prefills locally)")
    slo = float(config.get("MXFLEET_SLO_P99_MS"))
    print("autoscale    :", "SLO p99 %gms, cooldown %gs"
          % (slo, float(config.get("MXFLEET_AUTOSCALE_WINDOW_S")))
          if slo > 0 else
          "(observability-only — set MXFLEET_SLO_P99_MS)")
    coord = os.environ.get("MXFLEET_COORDINATOR") or \
        config.get("MXPOD_COORDINATOR") or \
        os.environ.get("MX_KV_SERVER")
    if not coord:
        print("directory    : (no coordinator address — set "
              "MXFLEET_COORDINATOR to inspect a live fleet)")
        return
    try:
        from mxnet_tpu.pod.group import PodGroup
        g = PodGroup(coord, grace_s=3.0)
        try:
            view = g.fleet_view()
        finally:
            g.close()
    except Exception as e:
        print(f"directory    : unreachable at {coord} ({e})")
        return
    workers = view.get("workers") or {}
    beat = float(config.get("MXFLEET_HEARTBEAT_S"))
    print(f"directory    : {coord} — {len(workers)} worker(s)")
    for wid, ent in sorted(workers.items()):
        age = float(ent.get("age_s", 0.0))
        stale = " STALE" if age > 3 * beat else ""
        print("  %s: %s @ %s, depth %s, beat %.1fs ago%s"
              % (wid, ent.get("role"), ent.get("address"),
                 ent.get("meta", {}).get("depth", "?"), age, stale))
    notes = view.get("notes") or {}
    ctl = notes.get("controller")
    if ctl:
        import time as _t
        print("controller   : %d decode / %d prefill proxied, "
              "noted %.1fs ago"
              % (ctl.get("decode", 0), ctl.get("prefill", 0),
                 max(0.0, _t.time() - float(ctl.get("ts", 0.0)))))
    else:
        print("controller   : no liveness note (no controller "
              "attached, or it never completed a sync)")
    rs = notes.get("last_resize")
    if rs:
        print("last resize  : -> %s replica(s)" % rs.get("target"))
    sc = notes.get("autoscale")
    if sc:
        print("autoscale    : %s (%s)"
              % (sc.get("decision"), sc.get("reason")))


def check_tune():
    """Autotuner state: MXTUNE_* flag resolution, the tuning DB's
    summary (records, keys, objectives), and what bind-time auto-apply
    last did in THIS process with its provenance (mxnet_tpu/tune/;
    docs/tuning.md runbook)."""
    print("----------Autotuning (mxtune)----------")
    try:
        from mxnet_tpu import config, tune
    except Exception as e:
        print("mxtune       : unavailable (%s)" % e)
        return
    auto = bool(config.get("MXTUNE_AUTO"))
    print("auto-apply   :", "ON (binds consult the DB)" if auto
          else "(off — binding is bit-identical to untuned)")
    print("objective    :", config.get("MXTUNE_OBJECTIVE"),
          "(auto = per bind kind)" if
          str(config.get("MXTUNE_OBJECTIVE")) == "auto" else "")
    print("budget       :", int(config.get("MXTUNE_BUDGET")),
          "trial(s) default for search")
    try:
        db = tune.TuneDB()
        d = db.describe()
        if d["records"]:
            print("db           : %s — %d record(s), %d key(s), "
                  "objectives %s"
                  % (d["path"], d["records"], d["keys"],
                     d["objectives"]))
        else:
            print("db           : %s — empty (run `python tools/"
                  "mxtune.py search` to populate)" % d["path"])
    except Exception as e:
        print("db           : unreadable (%s)" % e)
    try:
        space = tune.default_space()
        print("knob space   : %d knob(s) over %s, fingerprint %s"
              % (len(space), space.subsystems(),
                 space.fingerprint()))
    except Exception as e:
        print("knob space   : unavailable (%s)" % e)
    applied = tune.last_applied()
    if not applied:
        print("last applied : nothing this process"
              + ("" if auto else " (MXTUNE_AUTO is off)"))
    for bind, info in sorted(applied.items()):
        prov = info.get("provenance") or {}
        print("last applied : bind=%s %s (measured %s=%s, source %s, "
              "trial %s)"
              % (bind, info.get("config"), info.get("objective"),
                 info.get("value"), prov.get("source"),
                 prov.get("trial")))


def main():
    check_python()
    check_pip()
    check_os()
    check_hardware()
    check_environment()
    check_mxnet()
    check_telemetry()
    check_trace()
    check_serving()
    check_serving2()
    check_resilience()
    check_elastic()
    check_pod()
    check_pipe()
    check_guard()
    check_mxsan()
    check_obs()
    check_fleet()
    check_tune()
    check_mxlint()


if __name__ == "__main__":
    main()
