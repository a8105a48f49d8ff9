#!/usr/bin/env python
"""mxprof: summarize a telemetry dump (chrome-trace JSON or metrics
JSON-lines) from the command line.

The reading half of mxnet_tpu/telemetry/: the profiler writes a
chrome-trace dump whose events carry MXNet op names (tracing pillar),
recompile instants with triggering shapes (recompile auditor), and
memory counter samples; this tool renders the three reports the dump
encodes:

  python tools/mxprof.py summarize profile.json            # all three
  python tools/mxprof.py summarize profile.json --top 10   # top-K cap
  python tools/mxprof.py summarize profile.json --json     # machine-
                                                           # readable
  python tools/mxprof.py summarize metrics.jsonl           # metrics
                                                           # sink lines
  python tools/mxprof.py step metrics.jsonl                # fused-step
                                                           # report

--json emits the shared findings schema (mxnet_tpu.passes
findings_report — same shape as mxlint/check_tpu_consistency/
flakiness_checker --json): pathological patterns (recompile loops,
monotone memory growth) surface as findings; the tables ride in the
report's extra sections.

Exit codes: 0 clean, 2 findings at error severity, 1 usage error.
"""
import argparse
import json
import os
import re
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# a loose-shape entry that recompiles this often is a retrace loop
RECOMPILE_LOOP_THRESHOLD = 4


# ---------------------------------------------------------------------------
# chrome-trace analysis
# ---------------------------------------------------------------------------

def self_times(events):
    """Per-name {count, total_us, self_us} from ph=X duration events.

    Self time = duration minus the duration of events nested inside it
    (same pid/tid, contained interval) — the chrome-trace flame-graph
    convention, so an op that re-enters the nd layer doesn't double-
    count its children.
    """
    stats = defaultdict(lambda: {"count": 0, "total_us": 0.0,
                                 "self_us": 0.0})
    by_track = defaultdict(list)
    for e in events:
        if e.get("ph") == "X" and "dur" in e:
            by_track[(e.get("pid"), e.get("tid"))].append(e)
    for track in by_track.values():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        open_evs = []  # stack of (end_ts, event) currently containing us
        for e in track:
            ts, dur = e["ts"], e["dur"]
            while open_evs and open_evs[-1][0] <= ts:
                open_evs.pop()
            if open_evs:  # direct parent absorbs this child's duration
                parent = open_evs[-1][1]
                parent["child_us"] = parent.get("child_us", 0.0) + dur
            open_evs.append((ts + dur, e))
            s = stats[e["name"]]
            s["count"] += 1
            s["total_us"] += dur
        for e in track:
            stats[e["name"]]["self_us"] += \
                e["dur"] - e.pop("child_us", 0.0)
    return dict(stats)


def top_ops_table(stats, top):
    rows = sorted(stats.items(), key=lambda kv: -kv[1]["self_us"])
    if top and top > 0:
        rows = rows[:top]
    lines = [f"{'Op':<40}{'Count':>8}{'Self (ms)':>12}{'Total (ms)':>12}"
             f"{'Avg (ms)':>12}",
             "-" * 84]
    for name, s in rows:
        lines.append(
            f"{name[:39]:<40}{s['count']:>8}{s['self_us'] / 1e3:>12.4f}"
            f"{s['total_us'] / 1e3:>12.4f}"
            f"{s['total_us'] / s['count'] / 1e3:>12.4f}")
    return "\n".join(lines)


def recompile_records(events):
    out = []
    for e in events:
        if e.get("cat") == "recompile" or \
                str(e.get("name", "")).startswith("recompile:"):
            args = e.get("args", {})
            out.append({
                "entry": str(e.get("name", ""))[len("recompile:"):],
                "reason": args.get("reason", "?"),
                "kind": args.get("kind", "?"),
                "inputs": args.get("inputs", []),
                "training": args.get("training"),
                "ts": e.get("ts"),
            })
    return out


def recompile_table(records):
    lines = [f"{'Entry':<44}{'Reason':<18}{'Triggering shapes'}",
             "-" * 96]
    for r in records:
        shapes = ",".join("x".join(map(str, i.get("shape", [])))
                          or "scalar" for i in r["inputs"]) or "-"
        lines.append(f"{r['entry'][:43]:<44}{r['reason']:<18}{shapes}")
    by_entry = defaultdict(int)
    for r in records:
        by_entry[r["entry"]] += 1
    lines.append("")
    lines.append(f"total recompiles: {len(records)} across "
                 f"{len(by_entry)} entr(ies)")
    return "\n".join(lines)


def memory_timeline(events):
    samples = [(e["ts"], e.get("args", {}))
               for e in events if e.get("ph") == "C"
               and e.get("cat") == "memory"]
    samples.sort()
    return samples


def memory_table(samples):
    if not samples:
        return "no memory counter samples in this dump"
    vals = [a.get("live_bytes", 0) for _, a in samples]
    lines = [f"samples: {len(samples)}  "
             f"first: {vals[0]}  peak: {max(vals)}  last: {vals[-1]} "
             f"(live bytes)"]
    span = samples[-1][0] - samples[0][0]
    width = 50
    peak = max(vals) or 1
    for ts, a in samples[:200]:
        bar = "#" * max(1, int(width * a.get("live_bytes", 0) / peak))
        rel = (ts - samples[0][0]) / 1e3
        lines.append(f"  +{rel:>10.1f} ms  {a.get('live_bytes', 0):>14}  "
                     f"{bar}")
    if len(samples) > 200:
        lines.append(f"  ... {len(samples) - 200} more samples")
    if span <= 0 and len(samples) > 1:
        lines.append("  (all samples share one timestamp)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# metrics JSON-lines analysis
# ---------------------------------------------------------------------------

def summarize_metrics_lines(lines):
    """Fold a MXNET_METRICS_EXPORT stream: last snapshot + line count."""
    last = None
    n = 0
    for ln in lines:
        ln = ln.strip()
        if not ln:
            continue
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metrics" in rec:
            last = rec
            n += 1
    return {"n_snapshots": n, "last": last}


# ---------------------------------------------------------------------------
# fused-step report (mxnet_tpu/step/ — ISSUE 5)
# ---------------------------------------------------------------------------

# a fused step that misses its signature cache this often is retracing
FUSED_RETRACE_THRESHOLD = 4


def _hist_row(name, h):
    if not isinstance(h, dict) or not h.get("count"):
        return f"  {name:<34} (no samples)"
    return (f"  {name:<34} n={h['count']:<6} avg={h['avg'] * 1e3:9.3f} ms"
            f"  p50={(h.get('p50') or 0) * 1e3:9.3f} ms"
            f"  max={h['max'] * 1e3:9.3f} ms")


def step_report(metrics):
    """Render the fused-step section of one metrics snapshot: cache
    hits/misses, time-per-phase breakdown, gradient-bucket shape, and
    the persistent-compile-cache counters."""
    g = metrics.get
    hits = g("fused_step_cache_hits_total", 0)
    misses = g("fused_step_cache_misses_total", 0)
    lines = ["-- fused step (mxstep)"]
    if not (hits or misses):
        lines.append("  no fused-step activity in this snapshot "
                     "(StepFunction never ran)")
    else:
        total = hits + misses
        lines.append(f"  signature cache: {hits} hit(s), {misses} "
                     f"miss(es) ({100.0 * hits / total:.1f}% hit rate)")
        lines.append("  time per phase:")
        for name in ("step_program_trace_seconds",
                     "step_program_lower_seconds",
                     "step_program_compile_seconds",
                     "fused_step_host_seconds",
                     "fused_step_dispatch_seconds",
                     "fused_step_writeback_seconds",
                     "trainer_step_seconds"):
            lines.append(_hist_row(name, g(name)))
    buckets = g("grad_bucket_count")
    if buckets:
        bb = g("grad_bucket_bytes", {})
        lines.append(f"  gradient exchange: {int(buckets)} bucket(s)"
                     + (f", bytes avg={bb.get('avg', 0):.0f} "
                        f"max={bb.get('max', 0):.0f}"
                        if isinstance(bb, dict) and bb.get("count")
                        else ""))
    cc_h = g("jax_compile_cache_hits_total", 0)
    cc_m = g("jax_compile_cache_misses_total", 0)
    if cc_h or cc_m:
        lines.append(f"  persistent compile cache: {cc_h} hit(s), "
                     f"{cc_m} miss(es)")
    return "\n".join(lines)


def analyze_step(metrics):
    """Fused-step pathology scan → Finding list (shared schema)."""
    from mxnet_tpu.passes import Finding
    findings = []
    hits = metrics.get("fused_step_cache_hits_total", 0)
    misses = metrics.get("fused_step_cache_misses_total", 0)
    if misses >= FUSED_RETRACE_THRESHOLD and misses > hits:
        findings.append(Finding(
            "mxprof", "fused-step-retrace", "StepFunction", "error",
            f"{misses} fused-step cache misses vs {hits} hits — the "
            "step signature changes almost every call (loose batch "
            "shape or flapping dtype); pad or bucket the inputs or "
            "every step pays a full XLA compile"))
    disp = metrics.get("fused_step_dispatch_seconds")
    host = metrics.get("fused_step_host_seconds")
    if isinstance(disp, dict) and isinstance(host, dict) \
            and disp.get("count") and host.get("count") \
            and host.get("avg", 0) > 4 * disp.get("avg", 1e-12):
        findings.append(Finding(
            "mxprof", "host-bound-step", "StepFunction", "warn",
            f"host prep averages {host['avg'] * 1e3:.2f} ms vs "
            f"{disp['avg'] * 1e3:.2f} ms dispatch — per-step python "
            "overhead (hyper scalars/gather) dominates; suspect tiny "
            "model or excessive parameter count"))
    return findings


def step_cmd(path, as_json):
    with open(path) as f:
        report = summarize_metrics_lines(f)
    last = report.get("last") or {}
    metrics = last.get("metrics", {})
    findings = analyze_step(metrics)
    if as_json:
        from mxnet_tpu.passes import findings_report
        keys = [k for k in metrics
                if k.startswith(("fused_step_", "grad_bucket_",
                                 "jax_compile_cache_", "trainer_step"))]
        print(findings_report(
            "mxprof", findings,
            extra={"file": path, "n_snapshots": report["n_snapshots"],
                   "step_metrics": {k: metrics[k] for k in keys}},
            as_json=True))
    else:
        print(f"== mxprof step: {path} "
              f"({report['n_snapshots']} snapshot(s))")
        print(step_report(metrics))
        for fi in findings:
            print(f"  {fi!r}")
    from mxnet_tpu.passes import severity_counts
    return 2 if severity_counts(findings)["error"] else 0


# ---------------------------------------------------------------------------
# graph-optimizer report (mxnet_tpu/opt/ — ISSUE 7)
# ---------------------------------------------------------------------------

_OPT_PASSES = ("fold", "cse", "elide", "layout", "fuse", "dce")


def opt_metrics(metrics):
    """Extract the graph-optimizer slice of one metrics snapshot."""
    out = {
        "graphs": metrics.get("graph_opt_graphs_total", 0),
        "rewrites": metrics.get("graph_opt_rewrites_total", 0),
        "reverts": metrics.get("graph_opt_reverts_total", 0),
        "verify_failures": metrics.get(
            "graph_opt_verify_failures_total", 0),
        "passes": {}, "fused": {},
    }
    for p in _OPT_PASSES:
        n = metrics.get(f"graph_opt_{p}_rewrites_total", 0)
        t = metrics.get(f"graph_opt_{p}_seconds")
        out["passes"][p] = {
            "rewrites": n,
            "seconds": t if isinstance(t, dict) else None}
    for k, v in metrics.items():
        if k.startswith("graph_opt_fused_") and k.endswith("_total"):
            out["fused"][k[len("graph_opt_fused_"):-len("_total")]] = v
    return out


def opt_report(om):
    """Render the optimizer section: per-pass rewrite counters, the
    fused-group census, and time-in-pass."""
    lines = ["-- graph optimizer (mxopt)"]
    if not om["graphs"]:
        lines.append("  no optimizer activity in this snapshot "
                     "(MXNET_GRAPH_OPT=0 or no symbol binds)")
        return "\n".join(lines)
    lines.append(f"  graphs optimized: {om['graphs']}, total rewrites: "
                 f"{om['rewrites']}, reverts: {om['reverts']}, "
                 f"verify failures: {om['verify_failures']}")
    lines.append("  per-pass rewrites / time-in-pass:")
    for p in _OPT_PASSES:
        row = om["passes"][p]
        t = row["seconds"]
        tavg = (f"avg={t['avg'] * 1e3:8.3f} ms  "
                f"max={t['max'] * 1e3:8.3f} ms"
                if isinstance(t, dict) and t.get("count") else
                "(no timing samples)")
        lines.append(f"  {p:<8} rewrites={row['rewrites']:<6} {tavg}")
    if om["fused"]:
        lines.append("  fused-group census (pattern -> groups):")
        for pat, n in sorted(om["fused"].items()):
            lines.append(f"    {pat:<20} {n}")
    return "\n".join(lines)


def analyze_opt(om):
    """Optimizer pathology scan → Finding list (shared schema)."""
    from mxnet_tpu.passes import Finding
    findings = []
    if om["reverts"]:
        findings.append(Finding(
            "mxprof", "opt-reverts", "optimize_symbol", "warn",
            f"{om['reverts']} graph(s) reverted to unoptimized (io-"
            "contract or parity failure) — the optimizer paid its "
            "cost and delivered nothing; check bind logs/findings"))
    if om["verify_failures"]:
        findings.append(Finding(
            "mxprof", "opt-verify-failed", "optimize_symbol", "error",
            f"{om['verify_failures']} bind-time parity check(s) "
            "failed — a rewrite pass produced different numbers; "
            "file it, and run mxlint --opt to reproduce"))
    if om["graphs"] and not om["rewrites"]:
        findings.append(Finding(
            "mxprof", "opt-no-rewrites", "optimize_symbol", "info",
            f"{om['graphs']} graph(s) went through the pipeline with "
            "zero rewrites — nothing matched; see the \"why didn't my "
            "graph fuse\" cookbook in docs/graph_opt.md"))
    return findings


def opt_cmd(path, as_json):
    with open(path) as f:
        report = summarize_metrics_lines(f)
    last = report.get("last") or {}
    om = opt_metrics(last.get("metrics", {}))
    findings = analyze_opt(om)
    if as_json:
        from mxnet_tpu.passes import findings_report
        print(findings_report(
            "mxprof", findings,
            extra={"file": path, "n_snapshots": report["n_snapshots"],
                   "opt_metrics": om},
            as_json=True))
    else:
        print(f"== mxprof opt: {path} "
              f"({report['n_snapshots']} snapshot(s))")
        print(opt_report(om))
        for fi in findings:
            print(f"  {fi!r}")
    from mxnet_tpu.passes import severity_counts
    return 2 if severity_counts(findings)["error"] else 0


# ---------------------------------------------------------------------------
# sharded-training report (mxnet_tpu/shard/ — ISSUE 6)
# ---------------------------------------------------------------------------

def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} GiB"


def shard_metrics(metrics):
    """Pull the mxshard gauge family out of one metrics snapshot."""
    devices = int(metrics.get("shard_mesh_devices", 0) or 0)
    out = {"devices": devices, "per_device_live": {
        int(k[len("memory_live_bytes_dev"):]): v
        for k, v in metrics.items()
        if k.startswith("memory_live_bytes_dev")}}
    for kind in ("params", "opt_state"):
        total = metrics.get(f"shard_{kind}_bytes_total")
        per = metrics.get(f"shard_{kind}_bytes_per_replica")
        out[kind] = {"total": total, "per_replica": per,
                     "replicated_fraction": (
                         round(per * devices / total, 4)
                         if total and per and devices else None)}
    return out


def shard_table(sm):
    """Render bytes-per-replica for params vs optimizer state — the
    quantity ZeRO sharding exists to shrink (1.0x replicated fraction
    = perfectly sharded; Nx = fully replicated on an N-device mesh)."""
    if not sm["devices"]:
        return ("  no sharded-step activity in this snapshot "
                "(ShardedStepFunction never installed)")
    lines = [f"  mesh devices: {sm['devices']}"]
    for kind, label in (("params", "parameters"),
                        ("opt_state", "optimizer state")):
        k = sm[kind]
        if not k["total"]:
            lines.append(f"  {label:<16} (no accounting)")
            continue
        frac = k["replicated_fraction"]
        lines.append(
            f"  {label:<16} total {_fmt_bytes(k['total']):>10}   "
            f"per-replica {_fmt_bytes(k['per_replica']):>10}   "
            f"replicated-fraction {frac}x"
            + (" (fully sharded)" if frac and frac <= 1.05 else
               " (fully replicated)" if frac
               and frac >= 0.95 * sm["devices"] else ""))
    if sm["per_device_live"]:
        vals = sm["per_device_live"]
        lines.append("  per-device live bytes:")
        for dev_id in sorted(vals):
            lines.append(f"    dev{dev_id:<3} "
                         f"{_fmt_bytes(vals[dev_id])}")
    return "\n".join(lines)


def analyze_shard(sm):
    """Sharding pathology scan → Finding list (shared schema)."""
    from mxnet_tpu.passes import Finding
    findings = []
    devices = sm["devices"]
    frac = sm["opt_state"]["replicated_fraction"]
    if devices > 1 and frac is not None and frac >= 0.95 * devices:
        findings.append(Finding(
            "mxprof", "shard-no-memory-win", "opt_state", "warn",
            f"optimizer state is effectively fully replicated "
            f"(replicated-fraction {frac}x on a {devices}-device "
            "mesh) — ZeRO sharding is off or every state dim 0 "
            "fails the divisibility rule; per-replica memory will "
            "not scale 1/N"))
    per_dev = sm["per_device_live"]
    if len(per_dev) > 1:
        vals = sorted(per_dev.values())
        if vals[0] and vals[-1] / max(vals[0], 1) > 1.5:
            findings.append(Finding(
                "mxprof", "shard-imbalance", "live_bytes", "warn",
                f"per-device live bytes are imbalanced "
                f"(min {vals[0]}, max {vals[-1]}): one replica is "
                "holding >1.5x another's memory — check param_specs "
                "divisibility or stray unsharded buffers"))
    return findings


def shard_cmd(path, as_json):
    with open(path) as f:
        report = summarize_metrics_lines(f)
    last = report.get("last") or {}
    metrics = last.get("metrics", {})
    sm = shard_metrics(metrics)
    findings = analyze_shard(sm)
    if as_json:
        from mxnet_tpu.passes import findings_report
        print(findings_report(
            "mxprof", findings,
            extra={"file": path, "n_snapshots": report["n_snapshots"],
                   "shard_metrics": sm},
            as_json=True))
    else:
        print(f"== mxprof shard: {path} "
              f"({report['n_snapshots']} snapshot(s))")
        print("-- sharded training (mxshard)")
        print(shard_table(sm))
        for fi in findings:
            print(f"  {fi!r}")
    from mxnet_tpu.passes import severity_counts
    return 2 if severity_counts(findings)["error"] else 0


# ---------------------------------------------------------------------------
# mxtrace report (mxnet_tpu/trace/ — ISSUE 13)
# ---------------------------------------------------------------------------

# a root whose descendants cover less than this fraction of its wall
# time has an attribution hole — somewhere the trace lost a phase
TRACE_COVERAGE_THRESHOLD = 0.9
# ...but only when the hole is big enough to act on: a sub-ms step's
# inter-span Python (key building, branches) is below tracing
# granularity and not a lost phase
TRACE_COVERAGE_MIN_GAP_US = 1000.0
# cross-subsystem gaps larger than this fraction of the root are
# called out in the gap table
TRACE_GAP_FRACTION = 0.05


def _trace_trees(spans):
    """Group spans by trace_id: {tid: {"spans", "by_id", "roots",
    "orphans"}}."""
    traces = {}
    for s in spans:
        traces.setdefault(s["trace_id"], []).append(s)
    out = {}
    for tid, ss in traces.items():
        by_id = {s["span_id"]: s for s in ss}
        roots = [s for s in ss if not s.get("parent_id")]
        orphans = [s for s in ss
                   if s.get("parent_id")
                   and s["parent_id"] not in by_id]
        out[tid] = {"spans": ss, "by_id": by_id, "roots": roots,
                    "orphans": orphans}
    return out


def _interval_coverage(root, spans):
    """Fraction of the root's interval covered by the union of the
    OTHER spans' intervals (clipped to the root)."""
    r0 = root["ts_us"]
    r1 = r0 + (root["dur_us"] or 0.0)
    if r1 <= r0:
        return None
    ivals = []
    for s in spans:
        if s is root or s.get("dur_us") is None:
            continue
        a = max(r0, s["ts_us"])
        b = min(r1, s["ts_us"] + s["dur_us"])
        if b > a:
            ivals.append((a, b))
    ivals.sort()
    covered, end = 0.0, r0
    for a, b in ivals:
        a = max(a, end)
        if b > a:
            covered += b - a
            end = b
    return covered / (r1 - r0)


def _critical_path(tree, root):
    """Longest-duration child chain from the root — the trace's
    critical path, flame-graph style."""
    children = defaultdict(list)
    for s in tree["spans"]:
        pid = s.get("parent_id")
        if pid:
            children[pid].append(s)
    path = [root]
    cur = root
    while True:
        kids = [k for k in children.get(cur["span_id"], ())
                if k.get("dur_us") is not None]
        if not kids:
            return path
        cur = max(kids, key=lambda s: s["dur_us"])
        path.append(cur)


def _subsystem_gaps(tree, root):
    """Gaps between consecutive descendant spans where the subsystem
    changes — the cross-subsystem handoff cost (e.g. endpoint ->
    scheduler thread wakeup)."""
    spans = sorted((s for s in tree["spans"]
                    if s is not root and s.get("dur_us") is not None),
                   key=lambda s: s["ts_us"])
    gaps = []
    for a, b in zip(spans, spans[1:]):
        gap = b["ts_us"] - (a["ts_us"] + a["dur_us"])
        if gap > 0 and a["subsystem"] != b["subsystem"]:
            gaps.append({"from": a["name"], "from_sub": a["subsystem"],
                         "to": b["name"], "to_sub": b["subsystem"],
                         "gap_us": round(gap, 3)})
    return sorted(gaps, key=lambda g: -g["gap_us"])


def trace_self_times(spans):
    """Per-name self-time stats over span dicts (chrome-event shape
    reuse: ts/dur in us, nesting by parent chain per trace)."""
    stats = defaultdict(lambda: {"count": 0, "total_us": 0.0,
                                 "self_us": 0.0})
    child_of = defaultdict(float)  # span_id -> summed child duration
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        pid = s.get("parent_id")
        if pid in by_id and s.get("dur_us") is not None:
            child_of[pid] += s["dur_us"]
    for s in spans:
        if s.get("dur_us") is None:
            continue
        st = stats[s["name"]]
        st["count"] += 1
        st["total_us"] += s["dur_us"]
        st["self_us"] += max(0.0, s["dur_us"]
                             - child_of.get(s["span_id"], 0.0))
    return dict(stats)


def analyze_trace(trees, min_coverage=TRACE_COVERAGE_THRESHOLD):
    """Trace pathology scan → Finding list (shared schema):
    orphan-span (error — a span's parent is missing from its trace)
    and trace-coverage-gap (warn — a root's descendants cover less
    than ``min_coverage`` of its wall time)."""
    from mxnet_tpu.passes import Finding
    findings = []
    for tid, tree in sorted(trees.items()):
        if tree["orphans"] and not tree["roots"]:
            # the whole ancestry is absent: a flight-recorder ring
            # truncated the trace, or the work was still IN FLIGHT
            # when the dump froze (its root span had not closed yet).
            # Expected in dumps — note it, don't fail on it.
            findings.append(Finding(
                "mxprof", "truncated-trace", tid, "info",
                f"{len(tree['orphans'])} span(s) reference parents "
                "outside the file and the trace has no root — "
                "ring-truncated or dumped mid-flight"))
            continue
        for s in tree["orphans"]:
            findings.append(Finding(
                "mxprof", "orphan-span",
                f"{tid}/{s['name']}", "error",
                f"span {s['span_id']} ({s['name']}) references parent "
                f"{s['parent_id']} which is not in trace {tid} — the "
                "trace tree is broken (a span was dropped or a "
                "context leaked across traces)"))
        for root in tree["roots"]:
            # only roots with recorded children are judged: a lone
            # root (a dispatch tick, a one-span trace) has no
            # decomposition to be incomplete
            kids = [s for s in tree["spans"] if s is not root]
            if not kids:
                continue
            cov = _interval_coverage(root, tree["spans"])
            if cov is None or cov >= min_coverage:
                continue
            gap_us = (1.0 - cov) * (root["dur_us"] or 0.0)
            if gap_us < TRACE_COVERAGE_MIN_GAP_US:
                continue  # sub-granularity hole (see the constant)
            findings.append(Finding(
                "mxprof", "trace-coverage-gap",
                f"{tid}/{root['name']}", "warn",
                f"descendant spans cover {cov * 100:.1f}% of the "
                f"root's {root['dur_us'] / 1e3:.3f} ms "
                f"({gap_us / 1e3:.3f} ms unattributed; threshold "
                f"{min_coverage * 100:.0f}%) — a phase of this "
                "request/step is untraced"))
    return findings


def trace_report(trees, top):
    """Render: per-trace summary, critical path of the longest trace,
    top-K span self-time, largest cross-subsystem gaps."""
    lines = []
    all_spans = [s for t in trees.values() for s in t["spans"]]
    lines.append(f"-- traces: {len(trees)}, spans: {len(all_spans)}")
    rooted = [(t, r) for t in trees.values() for r in t["roots"]
              if r.get("dur_us") is not None
              and len(t["spans"]) > 1]
    rooted.sort(key=lambda tr: -tr[1]["dur_us"])
    for t, root in rooted[:max(3, top or 3)]:
        cov = _interval_coverage(root, t["spans"])
        lines.append(
            f"  {root['trace_id']}  {root['name']:<18} "
            f"{root['dur_us'] / 1e3:9.3f} ms  "
            f"{len(t['spans'])} span(s)  coverage "
            f"{cov * 100:.1f}%" if cov is not None else
            f"  {root['trace_id']}  {root['name']}")
    if rooted:
        t, root = rooted[0]
        lines.append("-- critical path (longest trace)")
        for s in _critical_path(t, root):
            lines.append(f"  {s['name']:<26} [{s['subsystem']:<8}] "
                         f"{(s['dur_us'] or 0) / 1e3:9.3f} ms")
        gaps = _subsystem_gaps(t, root)
        big = [g for g in gaps
               if g["gap_us"] >= TRACE_GAP_FRACTION
               * (root["dur_us"] or 1.0)]
        if big:
            lines.append("-- largest cross-subsystem gaps")
            for g in big[:5]:
                lines.append(
                    f"  {g['from']} [{g['from_sub']}] -> {g['to']} "
                    f"[{g['to_sub']}]: {g['gap_us'] / 1e3:.3f} ms")
    stats = trace_self_times(all_spans)
    lines.append(f"-- top span self-time (top {top or 'all'})")
    lines.append(top_ops_table(stats, top))
    return "\n".join(lines)


def load_spans_dir(dirpath):
    """Stitch a DIRECTORY of per-rank span files (a coordinated
    flight-dump directory, or each rank's MXTRACE_EXPORT) into one
    span list. Two repairs make cross-host trees analyzable:

    - **clock rebase** — ``ts_us`` is per-process monotonic (origins
      differ per host); every span carrying a ``wall`` anchor is
      rebased to ``wall * 1e6`` so spans from different ranks align on
      the epoch clock while intra-process deltas survive exactly;
    - **rank tagging + dedup** — the rank parsed from the ``-r<k>-``
      filename tag lands in ``attrs.rank``, and a span dumped by two
      files (a leader's export AND its flight dump) is kept once.
    """
    spans, seen = [], set()
    for fn in sorted(os.listdir(dirpath)):
        if not fn.endswith((".json", ".jsonl")):
            continue
        try:
            from mxnet_tpu.trace import load_spans
            file_spans = load_spans(os.path.join(dirpath, fn))
        except (OSError, ValueError):
            continue
        m = re.search(r"-r(\d+)-", fn)
        rank = int(m.group(1)) if m else None
        for s in file_spans:
            key = (s.get("trace_id"), s.get("span_id"))
            if key in seen:
                continue
            seen.add(key)
            s = dict(s)
            w = s.get("wall")
            if isinstance(w, (int, float)) and w > 0:
                s["ts_us"] = float(w) * 1e6
            if rank is not None:
                attrs = dict(s.get("attrs") or {})
                attrs.setdefault("rank", rank)
                s["attrs"] = attrs
            spans.append(s)
    return sorted(spans, key=lambda d: d["ts_us"])


def trace_cmd(path, top, as_json, min_coverage):
    from mxnet_tpu.trace import load_spans
    if os.path.isdir(path):
        spans = load_spans_dir(path)
    else:
        spans = load_spans(path)
    trees = _trace_trees(spans)
    findings = analyze_trace(trees, min_coverage)
    if as_json:
        from mxnet_tpu.passes import findings_report
        traces_out = []
        for tid, t in sorted(trees.items()):
            for root in t["roots"]:
                cov = _interval_coverage(root, t["spans"]) \
                    if len(t["spans"]) > 1 else None
                traces_out.append({
                    "trace_id": tid, "root": root["name"],
                    "dur_us": root.get("dur_us"),
                    "n_spans": len(t["spans"]),
                    "coverage": round(cov, 4)
                    if cov is not None else None,
                    "orphans": len(t["orphans"]),
                    "critical_path": [
                        {"name": s["name"], "sub": s["subsystem"],
                         "dur_us": s.get("dur_us")}
                        for s in _critical_path(t, root)],
                    "gaps": _subsystem_gaps(t, root)[:5],
                })
        stats = trace_self_times(spans)
        rows = sorted(stats.items(), key=lambda kv: -kv[1]["self_us"])
        if top and top > 0:
            rows = rows[:top]
        print(findings_report(
            "mxprof", findings,
            extra={"file": path, "n_spans": len(spans),
                   "n_traces": len(trees), "traces": traces_out,
                   "top_spans": [{"name": n, **s} for n, s in rows]},
            as_json=True))
    else:
        print(f"== mxprof trace: {path} ({len(spans)} span(s), "
              f"{len(trees)} trace(s))")
        print(trace_report(trees, top))
        for fi in findings:
            print(f"  {fi!r}")
    from mxnet_tpu.passes import severity_counts
    return 2 if severity_counts(findings)["error"] else 0


# ---------------------------------------------------------------------------
# findings (shared schema with mxlint)
# ---------------------------------------------------------------------------

def analyze(stats, recompiles, mem_samples):
    """Pathology scan → passes.Finding list (the shared schema)."""
    from mxnet_tpu.passes import Finding
    findings = []
    by_entry = defaultdict(list)
    for r in recompiles:
        by_entry[r["entry"]].append(r)
    for entry, recs in by_entry.items():
        shape_changes = [r for r in recs if r["reason"] == "shape-change"]
        if len(shape_changes) >= RECOMPILE_LOOP_THRESHOLD:
            shapes = [",".join("x".join(map(str, i.get("shape", [])))
                               for i in r["inputs"])
                      for r in shape_changes[:4]]
            findings.append(Finding(
                "mxprof", "recompile-loop", entry, "error",
                f"{len(shape_changes)} shape-triggered recompiles "
                f"(shapes: {shapes}); pad or bucket the loose dimension "
                f"or this entry compiles every step"))
        dtype_changes = [r for r in recs if r["reason"] == "dtype-change"]
        if len(dtype_changes) >= 2:
            findings.append(Finding(
                "mxprof", "dtype-flapping", entry, "warn",
                f"{len(dtype_changes)} dtype-triggered recompiles — an "
                f"amp boundary is casting inconsistently"))
    if len(mem_samples) >= 4:
        vals = [a.get("live_bytes", 0) for _, a in mem_samples]
        if all(b > a for a, b in zip(vals, vals[1:])):
            findings.append(Finding(
                "mxprof", "memory-growth", "live_bytes", "warn",
                f"live bytes grew monotonically across all "
                f"{len(vals)} samples ({vals[0]} -> {vals[-1]}); "
                f"check for arrays retained across steps"))
    return findings


def summarize(path, top, as_json):
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head != "{":
            report = {"file": path, "kind": "metrics",
                      **summarize_metrics_lines(f)}
            _emit_metrics(report, as_json)
            return 0
        first_line = f.readline()
        try:
            doc = json.loads(first_line)
            # a single-line file may be a metrics snapshot line
            if isinstance(doc, dict) and "metrics" in doc \
                    and "traceEvents" not in doc:
                f.seek(0)
                report = {"file": path, "kind": "metrics",
                          **summarize_metrics_lines(f)}
                _emit_metrics(report, as_json)
                return 0
        except ValueError:
            pass
        f.seek(0)
        doc = json.load(f)
    events = doc.get("traceEvents", [])
    stats = self_times(events)
    recompiles = recompile_records(events)
    mem = memory_timeline(events)
    findings = analyze(stats, recompiles, mem)

    if as_json:
        from mxnet_tpu.passes import findings_report, severity_counts
        rows = sorted(stats.items(), key=lambda kv: -kv[1]["self_us"])
        if top and top > 0:
            rows = rows[:top]
        print(findings_report(
            "mxprof", findings,
            extra={"file": path,
                   "top_ops": [{"name": n, **s} for n, s in rows],
                   "recompiles": recompiles,
                   "memory_samples": [
                       {"ts": ts, **args} for ts, args in mem]},
            as_json=True))
    else:
        print(f"== mxprof summarize: {path} ({len(events)} events)")
        print()
        print(f"-- top ops by self time (top {top or 'all'})")
        print(top_ops_table(stats, top))
        print()
        print("-- recompile report")
        print(recompile_table(recompiles))
        print()
        print("-- memory timeline")
        print(memory_table(mem))
        if findings:
            print()
            print("-- findings")
            for fi in findings:
                print(f"  {fi!r}")
    from mxnet_tpu.passes import severity_counts
    return 2 if severity_counts(findings)["error"] else 0


def _emit_metrics(report, as_json):
    if as_json:
        from mxnet_tpu.passes import findings_report
        print(findings_report("mxprof", [], extra=report, as_json=True))
        return
    print(f"== mxprof summarize: {report['file']} "
          f"(metrics stream, {report['n_snapshots']} snapshot(s))")
    last = report.get("last")
    if last:
        print("-- last snapshot")
        for k, v in sorted(last.get("metrics", {}).items()):
            print(f"  {k} = {v}")


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="mxprof", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd")
    ps = sub.add_parser("summarize",
                        help="render top-K ops / recompiles / memory "
                             "from a dump")
    ps.add_argument("dump", help="chrome-trace JSON (profiler.dump) or "
                                 "metrics JSON-lines "
                                 "(MXNET_METRICS_EXPORT)")
    ps.add_argument("--top", type=int, default=None,
                    help="rows in the op table (default: "
                         "MXNET_PROFILER_TOPK, 0 = all)")
    ps.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the shared machine-readable findings "
                         "report")
    pstep = sub.add_parser(
        "step",
        help="fused-step report from a metrics JSON-lines dump: cache "
             "hits/misses, time-per-phase breakdown, bucket sizes")
    pstep.add_argument("dump", help="metrics JSON-lines file "
                                    "(MXNET_METRICS_EXPORT)")
    pstep.add_argument("--json", action="store_true", dest="as_json",
                       help="emit the shared machine-readable findings "
                            "report")
    pshard = sub.add_parser(
        "shard",
        help="sharded-training report from a metrics JSON-lines dump: "
             "bytes-per-replica for params vs optimizer state, "
             "per-device live bytes, sharding pathologies")
    pshard.add_argument("dump", help="metrics JSON-lines file "
                                     "(MXNET_METRICS_EXPORT)")
    pshard.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the shared machine-readable "
                             "findings report")
    popt = sub.add_parser(
        "opt",
        help="graph-optimizer report from a metrics JSON-lines dump: "
             "per-pass rewrite counters, fused-group census "
             "(pattern -> count), time-in-pass")
    popt.add_argument("dump", help="metrics JSON-lines file "
                                   "(MXNET_METRICS_EXPORT)")
    popt.add_argument("--json", action="store_true", dest="as_json",
                      help="emit the shared machine-readable findings "
                           "report")
    ptrace = sub.add_parser(
        "trace",
        help="mxtrace report from a span file (MXTRACE_EXPORT "
             "JSON-lines, a write_chrome document, or a flight-"
             "recorder dump): per-trace critical path, top-K span "
             "self-time, cross-subsystem gaps, orphan/coverage "
             "findings")
    ptrace.add_argument("dump", help="span JSON-lines / chrome trace "
                                     "/ flight-recorder dump file")
    ptrace.add_argument("--top", type=int, default=None,
                        help="rows in the span self-time table "
                             "(default: MXNET_PROFILER_TOPK, 0 = all)")
    ptrace.add_argument("--min-coverage", type=float,
                        default=TRACE_COVERAGE_THRESHOLD,
                        help="coverage fraction below which a root "
                             "gets a trace-coverage-gap finding "
                             "(default 0.9)")
    ptrace.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the shared machine-readable "
                             "findings report")
    ptrace.add_argument("--dir", action="store_true", dest="as_dir",
                        help="treat DUMP as a directory of per-rank "
                             "span files (a coordinated flight-dump "
                             "dir): rebase each span onto the epoch "
                             "clock and stitch one cross-host report "
                             "(auto-detected for directory paths)")
    args = p.parse_args(argv)
    if args.cmd not in ("summarize", "step", "shard", "opt", "trace"):
        p.error("nothing to do: use the summarize, step, shard, opt "
                "or trace subcommand")
    try:
        if args.cmd == "step":
            return step_cmd(args.dump, args.as_json)
        if args.cmd == "shard":
            return shard_cmd(args.dump, args.as_json)
        if args.cmd == "opt":
            return opt_cmd(args.dump, args.as_json)
        if args.cmd == "trace":
            top = args.top
            if top is None:
                from mxnet_tpu.base import get_env
                top = int(get_env("MXNET_PROFILER_TOPK", 0))
            return trace_cmd(args.dump, top, args.as_json,
                             args.min_coverage)
        top = args.top
        if top is None:
            from mxnet_tpu.base import get_env
            top = int(get_env("MXNET_PROFILER_TOPK", 0))
        return summarize(args.dump, top, args.as_json)
    except OSError as e:
        print(f"mxprof: cannot read {args.dump}: {e}", file=sys.stderr)
        return 1
    except ValueError as e:
        print(f"mxprof: {args.dump} is not valid JSON: {e}",
              file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
