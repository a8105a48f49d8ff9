"""Typed runtime configuration / env-flag system.

The reference exposes ~83 ``MXNET_*`` environment variables read ad hoc
via ``dmlc::GetEnv`` at use sites (ref: docs/faq/env_var.md;
src/engine/threaded_engine_perdevice.cc:84 etc.). Here the flag system
is one typed registry: every flag has a declared type, default, doc
string, and a TPU status — ``active`` flags change behavior in this
framework and are read (through :func:`get`) at a real use site;
``accepted`` flags are recognized for workflow compatibility but are
no-ops on TPU (their job belongs to XLA/PJRT), and reading them warns
once when they are set to a non-default value so users know the knob
has no effect.

Resolution order: :func:`set_flag` runtime override > environment >
declared default.
"""
from __future__ import annotations

import os
import threading
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

__all__ = ["Flag", "register_flag", "get", "set_flag", "unset_flag",
           "describe", "flags"]


def _parse_bool(v: str) -> bool:
    return v.lower() in ("1", "true", "yes", "on")


@dataclass
class Flag:
    name: str
    type: type
    default: Any
    doc: str
    active: bool = True           # False: accepted-but-inert on TPU
    tpu_note: str = ""            # why inert / how reinterpreted
    choices: Optional[tuple] = None
    _warned: bool = field(default=False, repr=False)

    def parse(self, raw: str) -> Any:
        if self.type is bool:
            return _parse_bool(raw)
        return self.type(raw)


_FLAGS: Dict[str, Flag] = {}
_OVERRIDES: Dict[str, Any] = {}
_LOCK = threading.Lock()
_GEN = 0  # bumped on every runtime override; hot paths cache against it


def generation() -> int:
    """Monotone counter for flag-cache invalidation (engine.is_sync)."""
    return _GEN


def register_flag(name: str, type: type, default: Any, doc: str,
                  active: bool = True, tpu_note: str = "",
                  choices: Optional[tuple] = None) -> Flag:
    f = Flag(name, type, default, doc, active, tpu_note, choices)
    _FLAGS[name] = f
    return f


def get(name: str, default: Any = None, dtype: Optional[type] = None) -> Any:
    """Resolve a flag: runtime override > env > declared default.

    Unregistered names fall back to a raw env read with ``default``,
    coerced to ``dtype`` (or the default's type) — the dmlc::GetEnv
    escape hatch. For registered names the registry's type/default are
    canonical and ``default``/``dtype`` are ignored."""
    # lock-free read path: dict reads are atomic in CPython, and this is
    # called from the per-op eager dispatch (engine.is_sync)
    f = _FLAGS.get(name)
    if name in _OVERRIDES:
        val = _OVERRIDES.get(name, default)
        if f is not None and not f.active and val != f.default \
                and not f._warned:
            f._warned = True
            warnings.warn(
                f"{name}={val} has no effect on the TPU backend"
                + (f" ({f.tpu_note})" if f.tpu_note else ""),
                stacklevel=2)
        return val
    if f is None:
        raw = os.environ.get(name)
        if raw is None:
            return default
        ty = dtype or (type(default) if default is not None else None)
        if ty is bool or isinstance(default, bool):
            return _parse_bool(raw)
        if ty is not None:
            try:
                return ty(raw)
            except (TypeError, ValueError):
                return raw
        return raw
    raw = os.environ.get(name)
    val = f.default if raw is None else f.parse(raw)
    if not f.active and val != f.default and not f._warned:
        f._warned = True
        warnings.warn(
            f"{name}={val} has no effect on the TPU backend"
            + (f" ({f.tpu_note})" if f.tpu_note else ""), stacklevel=2)
    if f.choices and val not in f.choices:
        raise ValueError(f"{name}={val!r} not in {f.choices}")
    return val


def set_flag(name: str, value: Any) -> None:
    """Runtime override (highest precedence)."""
    global _GEN
    f = _FLAGS.get(name)
    if f is not None:
        if f.type is bool and isinstance(value, str):
            value = _parse_bool(value)
        elif not isinstance(value, f.type):
            value = f.type(value)
        if f.choices and value not in f.choices:
            raise ValueError(f"{name}={value!r} not in {f.choices}")
    with _LOCK:
        _OVERRIDES[name] = value
        _GEN += 1


def unset_flag(name: str) -> None:
    global _GEN
    with _LOCK:
        _OVERRIDES.pop(name, None)
        _GEN += 1


def flags() -> Dict[str, Flag]:
    return dict(_FLAGS)


def flag_rows():
    """One (name, type_name, default_repr, status, doc) tuple per flag —
    the single rendering source for describe() and docs generation
    (tools/gen_env_docs.py). Machine-dependent defaults (home-relative
    paths) are normalized so generated docs are portable."""
    home = os.path.expanduser("~")
    rows = []
    for name in sorted(_FLAGS):
        f = _FLAGS[name]
        status = "active" if f.active else "accepted (no-op on TPU)"
        default = repr(f.default)
        if isinstance(f.default, str) and f.default.startswith(home):
            default = repr("~" + f.default[len(home):])
        doc = " ".join(f.doc.split())
        if f.tpu_note:
            doc += f" TPU: {' '.join(f.tpu_note.split())}"
        rows.append((name, f.type.__name__, default, status, doc))
    return rows


def describe() -> str:
    """Human-readable flag table (the env_var.md analog)."""
    lines = []
    for name, tname, default, status, doc in flag_rows():
        lines.append(f"{name} = {get(name)!r}  [{tname}, "
                     f"default {default}, {status}]")
        lines.append(f"    {doc}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Active flags — each is read via config.get() at the cited use site.
# ---------------------------------------------------------------------------

register_flag(
    "MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
    "Execution engine. NaiveEngine = fully synchronous dispatch for "
    "debugging (ref: src/engine/engine.cc:32-56).",
    choices=("ThreadedEnginePerDevice", "ThreadedEnginePooled",
             "NaiveEngine"))
register_flag(
    "MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
    "Bulk (segment) execution of the training graph "
    "(ref: env_var.md:120). TPU: whole-graph jit when on; per-op "
    "dispatch hints when off.")
register_flag(
    "MXNET_EXEC_BULK_EXEC_INFERENCE", bool, True,
    "Bulk execution of inference graphs (ref: env_var.md:123).")
register_flag(
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", int, 15,
    "Max nodes per bulked segment (ref: env_var.md:129). TPU: advisory "
    "segment size for the engine facade's bulk scope.")
register_flag(
    "MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
    "Arrays above this element count are sharded across kvstore "
    "servers / collective chunks (ref: kvstore_dist.h EncodeDefaultKey).")
register_flag(
    "MXNET_UPDATE_ON_KVSTORE", bool, True,
    "Run the optimizer inside the kvstore (server-side update) when the "
    "kvstore supports it (ref: python/mxnet/model.py _create_kvstore).")
register_flag(
    "MXNET_HOME", str, os.path.join(os.path.expanduser("~"), ".mxnet_tpu"),
    "Data/model cache root (ref: env_var.md MXNET_HOME).")
register_flag(
    "MXNET_GLUON_REPO", str,
    "https://apache-mxnet.s3-accelerate.dualstack.amazonaws.com/",
    "Base URL for gluon model-zoo downloads (ref: env_var.md).",
    active=False,
    tpu_note="no network egress in this build; weights load from local "
             "files")
register_flag(
    "MXNET_USE_SIGNAL_HANDLER", bool, True,
    "Install the SIGSEGV/SIGABRT backtrace handler at import "
    "(ref: src/initialize.cc:62).")
register_flag(
    "MXNET_SAFE_ACCUMULATION", bool, False,
    "Accumulate reductions/softmax in fp32 even for fp16/bf16 inputs "
    "(ref: env_var.md MXNET_SAFE_ACCUMULATION).")
register_flag(
    "MXNET_ENFORCE_DETERMINISM", bool, False,
    "Refuse/avoid non-deterministic kernels. TPU: forces synchronous "
    "NaiveEngine-style dispatch ordering in the engine facade.")
register_flag(
    "MXNET_BACKWARD_DO_MIRROR", bool, False,
    "Trade compute for memory in backward (ref: env_var.md:187, "
    "src/nnvm/gradient.cc mirror). TPU: wraps the forward in "
    "jax.checkpoint (rematerialization) when building grad programs.")
register_flag(
    "MXNET_SUBGRAPH_BACKEND", str, "",
    "Partition graphs with the named subgraph property before "
    "compilation (ref: env_var.md:319 MXNET_SUBGRAPH_BACKEND). "
    "TPU: applies mxnet_tpu.subgraph.build_subgraph in Symbol.bind.")
register_flag(
    "MXNET_STORAGE_FALLBACK_LOG_VERBOSE", bool, True,
    "Warn when a sparse op falls back to the dense implementation "
    "(ref: env_var.md:30).")
register_flag(
    "MXNET_OPTIMIZER_AGGREGATION_SIZE", int, 4,
    "Max tensors fused per multi-tensor optimizer update "
    "(ref: env_var.md MXNET_OPTIMIZER_AGGREGATION_SIZE).")
register_flag(
    "MXNET_GRAD_BUCKET_BYTES", int, 4 << 20,
    "Byte cap per flat gradient-exchange bucket (step.buckets."
    "GradientBuckets, used by gluon Trainer._allreduce_grads): "
    "gradients of like dtype are coalesced into buckets up to this "
    "size so the kvstore data plane does O(buckets) transfers instead "
    "of O(params). Larger buckets amortize transport latency; smaller "
    "ones overlap exchange with the backward earlier "
    "(docs/performance.md).")
register_flag(
    "MXNET_COMPILE_CACHE_DIR", str, "",
    "Directory for JAX's persistent XLA compilation cache "
    "(step.cache.enable_compile_cache, applied at import): compiled "
    "programs — including the fused train step — are written to disk "
    "so warmup survives process restarts. Hits/misses are logged to "
    "the telemetry registry (jax_compile_cache_{hits,misses}_total). "
    "Yields to JAX_COMPILATION_CACHE_DIR: when that is set the cache "
    "stays where jax put it and this flag is ignored. Empty = cache "
    "off.")
register_flag(
    "MXNET_EAGER_SYNC", bool, False,
    "Block on device completion after EVERY eager op dispatch "
    "(ndarray.invoke). Default off: PJRT pipelines eager chains "
    "asynchronously. Forced on while the profiler's imperative domain "
    "is recording (accurate per-op timings) and under NaiveEngine / "
    "MXNET_ENFORCE_DETERMINISM.")
register_flag(
    "MXNET_MP_WORKER_NTHREADS", int, 4,
    "Per-worker decode thread cap in multiprocess DataLoader workers "
    "(ref: env_var.md:60).")
register_flag(
    "MXNET_CPU_WORKER_NTHREADS", int, 1,
    "Host-side worker threads for the native IO pipeline "
    "(ref: env_var.md:25). TPU: thread count of the native RecordIO "
    "batch server.")
register_flag(
    "MXNET_PROFILER_AUTOSTART", bool, False,
    "Start the profiler at import (ref: env_var.md MXNET_PROFILER_"
    "AUTOSTART).")
register_flag(
    "MXNET_PROFILER_MODE", int, 0,
    "Default profiler mode bitmask (ref: env_var.md).")
register_flag(
    "MXNET_PROFILER_TOPK", int, 0,
    "Row cap for the profiler's aggregate statistics table and the "
    "tools/mxprof.py default top-K; 0 = unlimited (profiler."
    "get_summary / mxprof summarize).")
register_flag(
    "MXNET_METRICS_EXPORT", str, "",
    "Path of the JSON-lines metrics sink; when set, gluon Trainer.step "
    "appends one metrics snapshot line per step "
    "(telemetry.record_step). Empty = export off.")
register_flag(
    "MXNET_TELEMETRY_MEMORY_INTERVAL", float, 0.0,
    "Minimum seconds between automatic memory samples at step "
    "boundaries (telemetry.memory.maybe_sample — the jax.live_arrays "
    "census walks every buffer). 0 = sample every step while the "
    "profiler's memory domain is on or a metrics sink is configured.")
register_flag(
    "MXNET_USE_INT64_TENSOR_SIZE", bool, False,
    "Enable tensors with more than 2^31 elements / int64 indexing "
    "(ref: the INT64_TENSOR_SIZE build flag, env_var.md). Read at "
    "import: turns on jax x64 mode, which also widens python-float "
    "weak types — opt-in, like the reference's off-by-default build.")
register_flag(
    "MXNET_USE_OPERATOR_TUNING", str, "1",
    "Measure-and-cache selection between equivalent op implementations "
    "(conv NCHW vs NHWC layout, RNN scan vs unrolled, int8 vs f32 "
    "dispatch; operator_tune.autotune — attention is not among them: "
    "kernel or dense is a rule on the call's shape, ops.pallas_kernels."
    "flash_attention_available — "
    "the TPU reinterpretation of the reference's OMP tuning, "
    "operator_tune.h:165). 0/false/off = always take the default "
    "candidate; any other value (1, float32, ... — the reference's "
    "multi-valued forms) enables tuning.")
register_flag(
    "MXNET_OPTUNE_CHOICE_<NAME>", str, "",
    "Wildcard override: pin a tuned choice by candidate label, "
    "trumping measurement and cache — e.g. "
    "MXNET_OPTUNE_CHOICE_CONV_LAYOUT=nchw keeps every convolution in "
    "the direct layout (operator_tune.choose). An unknown label "
    "raises, listing the candidates. No attention site reads one: "
    "MXNET_OPTUNE_CHOICE_ATTENTION is gone with the measurement.")
register_flag(
    "MXNET_GRAPH_OPT", int, 0,
    "Graph-optimizer level for Symbol binds (mxnet_tpu/opt/, "
    "docs/graph_opt.md). 0 = off; 1 = semantics-preserving cleanups "
    "(constant folding, CSE, identity elision, dead-node sweep — "
    "bitwise parity class); 2 = level 1 plus fusion-group "
    "partitioning (conv+bn+relu, matmul+act, elementwise chains, "
    "attention) and NHWC layout selection for TPU/XLA:CPU "
    "(tolerance-tagged parity). Applies at Executor bind, symbol-mode "
    "StepFunction compile, and serve AOT warmup.", choices=(0, 1, 2))
register_flag(
    "MXNET_GRAPH_OPT_VERIFY", bool, False,
    "Bind-time parity gate for the graph optimizer: run the optimized "
    "graph against the unoptimized one on the executor's live buffers "
    "under the pipeline's declared tolerance class, and REVERT to the "
    "unoptimized graph on any mismatch (graph_opt_verify_failures_"
    "total counts reverts). Costs one extra forward per bind; "
    "mxlint --opt turns it on for its self-check.")
register_flag(
    "MXNET_GRAPH_OPT_PALLAS", bool, True,
    "Allow Pallas kernel lowerings for fused patterns (_fused_"
    "attention's kernel where flash_attention_available takes the "
    "shape, the fused optimizer+cast mp_sgd step). "
    "Only takes effect on a TPU backend; everywhere else — and when "
    "set to 0 — the automatic XLA fallback composition runs "
    "(bitwise-identical to the unfused graph).")
register_flag(
    "MXSERVE_BUCKETS", str, "1,2,4,8,16,32",
    "Shape-bucket ladder for the serving subsystem (serve.buckets."
    "default_ladder): batch rungs as a comma list, or named axes as "
    "'batch:1,2,4,8;seq:16,32,64' where axis<k> addresses BATCHED-"
    "array axis k, i.e. item axis k-1 (seq = axis1). Requests are "
    "padded up to the next rung so the serving jit cache CLOSES after "
    "warmup (docs/serving.md).")
register_flag(
    "MXSERVE_MAX_LINGER_MS", float, 2.0,
    "Max milliseconds the serving batcher waits for co-batchable "
    "requests before dispatching a partial batch (serve.batcher) — "
    "the cap on batching-added latency; keep ~ one device step time.")
register_flag(
    "MXSERVE_QUEUE_DEPTH", int, 256,
    "Bounded serving-queue capacity (serve.batcher). A submit against "
    "a full queue is rejected immediately with QueueFullError "
    "(HTTP 429 at the endpoint) — load-shed backpressure, never "
    "unbounded blocking.")
register_flag(
    "MXSERVE_MAX_BATCH", int, 0,
    "Row cap per serving dispatch (serve.batcher). 0 (default) = the "
    "ladder's top batch rung.")
register_flag(
    "MXSERVE2_PAGE_SIZE", int, 16,
    "KV-cache page width in tokens for the continuous-batching serving "
    "tier (serve2.kvcache.PagedKVCache): each page is a fixed-size "
    "block of the pooled K/V memory, so admit/finish/preempt never "
    "change a compiled decode program's shapes. Smaller pages waste "
    "less memory on short tails but lengthen the paged-attention scan "
    "(docs/serving.md v2 tuning guide).")
register_flag(
    "MXSERVE2_NUM_PAGES", int, 256,
    "Total pages in the serve2 KV pool (page 0 is reserved as the null "
    "page). Together with MXSERVE2_PAGE_SIZE this fixes the pool's "
    "device footprint at engine construction; running out under load "
    "triggers recompute preemption of the youngest sequence, counted "
    "in mxserve2_preemptions_total.")
register_flag(
    "MXSERVE2_MAX_INFLIGHT", int, 8,
    "Max sequences decoded concurrently by one serve2 DecodeEngine. "
    "The decode bucket ladder is the powers of two up to this cap; "
    "each rung is ONE compiled decode step program, AOT-warmed so the "
    "jit cache closes (zero steady-state recompiles, servelint-"
    "checked).")
register_flag(
    "MXSERVE2_REPLICAS", int, 2,
    "Default replica count per model group in the serve2 Router "
    "(serve2.router): requests spread over N engine replicas with "
    "queue-depth + circuit-breaker aware routing; a tripped replica "
    "is routed around (graceful degradation) until its breaker "
    "half-opens.")
register_flag(
    "MXSERVE2_RELOAD_DRAIN_TIMEOUT_S", float, 30.0,
    "Per-replica drain budget during a rolling model reload "
    "(Router.rolling_reload): the NEW engine is warmed before the "
    "swap, then the old engine gets this many seconds to finish "
    "in-flight work before it is closed; requests still queued after "
    "the budget count as dropped in the reload report (test-enforced "
    "to be zero).")
register_flag(
    "MXSERVE2_DECODE_STEPS", int, 4,
    "Decode iterations folded into ONE compiled serve2 dispatch "
    "(n-step scheduling). The K steps run entirely in-device, so the "
    "pool copy-on-update forced where buffer donation is unavailable "
    "(XLA:CPU) is paid once per K tokens; scheduling granularity "
    "(admit/preempt/finish) coarsens to K tokens. 1 = strict "
    "iteration-level scheduling.")
register_flag(
    "MXSERVE2_PREFILL_BUCKETS", str, "16,32,64",
    "Prompt-length rungs for the serve2 prefill program (comma list). "
    "Prompts are padded up to the next rung so prefill compiles once "
    "per rung — same closed-jit-cache contract as MXSERVE_BUCKETS; "
    "prompts longer than the top rung are rejected at submit.")
register_flag(
    "MXSERVE3_PREFIX_CACHE", bool, False,
    "Prefix caching for serve2 DecodeEngines (serve3 leg a): FULL "
    "pages of each prompt are content-hashed (chain hash over the "
    "whole prefix) so identical prompt prefixes across requests map "
    "to the same refcounted physical pages — prefill runs only over "
    "the uncovered suffix, multiplying effective cache capacity under "
    "templated traffic. Shared pages are read-only; in-place writes "
    "copy-on-write (mxserve3_cow_copies_*). Exact: greedy outputs are "
    "unchanged (the cached K/V is the prefill's own). Off by default "
    "so a flags-off engine is bit-for-bit the PR-8 engine (finished "
    "sequences' pages linger refcounted in the cache when on).")
register_flag(
    "MXSERVE3_PREFIX_CACHE_PAGES", int, 0,
    "Cap on pages the serve2 prefix cache may pin (0 = no explicit "
    "cap; pool pressure still evicts LRU cache pages before the "
    "scheduler resorts to preemption). Tune below the pool size when "
    "templated traffic would otherwise crowd out decode growth.")
register_flag(
    "MXSERVE3_SPEC_TOKENS", int, 0,
    "Draft tokens proposed per speculative-decoding tick (serve3 leg "
    "b) when a DecodeEngine is built with draft_params. Each tick the "
    "draft proposes K tokens in one small dispatch and the target "
    "verifies all K+1 candidates in ONE batched forward; greedy "
    "acceptance is exact (token-for-token the target's own "
    "trajectory), so throughput scales with the draft's acceptance "
    "rate (mxserve3_accept_rate_*). 0 = speculative decoding off.")
register_flag(
    "MXSERVE3_KV_DTYPE", str, "f32",
    "Storage dtype of the serve2 KV page pools (serve3 leg c): 'f32' "
    "(exact), 'bf16' (half the pool bytes, quant_bf16 tolerance "
    "class), or 'int8' (quarter the pool bytes + per-slot f32 dequant "
    "scales, quantize-on-append, quant_int8 class) — int8 roughly "
    "quadruples in-flight sequences per pool byte. Dequantization "
    "happens inside the paged-attention gather.",
    choices=("f32", "bf16", "int8"))
register_flag(
    "MXRESIL_FAULT_PLAN", str, "",
    "Deterministic fault-injection plan (resil.faultplan), e.g. "
    "'step:40=preempt;kvstore.push@3=raise;io=stall:200ms' — "
    "semicolon-separated site[@K|%P|:STEP]=action[:arg] clauses "
    "evaluated at the wired injection sites (kvstore.push/pull, io, "
    "serve.submit, checkpoint.write/restore, step). Empty = injection "
    "off (the hooks are no-ops). See docs/resilience.md.")
register_flag(
    "MXRESIL_SEED", int, 0,
    "Seed for probabilistic fault-plan clauses (site%P): a fixed seed "
    "reproduces the same fault sequence bit-for-bit "
    "(resil.faultplan.Clause).")
register_flag(
    "MXRESIL_RETRY_MAX", int, 3,
    "Max retries per call for the site retry policies "
    "(resil.policy.RetryPolicy) wrapping kvstore push/pull and "
    "checkpoint I/O; only typed RetryableErrors are retried.")
register_flag(
    "MXRESIL_RETRY_BASE_MS", float, 10.0,
    "First-retry backoff in milliseconds; subsequent retries double "
    "it with jitter (resil.policy.BackoffSchedule).")
register_flag(
    "MXRESIL_RETRY_MAX_MS", float, 2000.0,
    "Backoff ceiling in milliseconds (resil.policy.BackoffSchedule).")
register_flag(
    "MXRESIL_BREAKER_FAILURES", int, 5,
    "Consecutive failures that trip a site circuit breaker to OPEN "
    "(fail-fast degraded mode; resil.policy.CircuitBreaker).")
register_flag(
    "MXRESIL_BREAKER_COOLDOWN_S", float, 30.0,
    "Seconds an open circuit breaker waits before admitting one "
    "half-open probe (resil.policy.CircuitBreaker).")
register_flag(
    "MXSHARD_AUTO", bool, False,
    "Shard every gluon Trainer.fuse_step over the local devices when "
    "more than one is present (shard.ShardPlan.from_env over "
    "MXSHARD_AXES/MXSHARD_ZERO): the fused train step compiles with "
    "NamedSharding annotations over a named mesh instead of running "
    "single-device. Explicit shard_plan= arguments always win. See "
    "docs/sharding.md.")
register_flag(
    "MXSHARD_AXES", str, "batch:-1",
    "Mesh axes for MXSHARD_AUTO / ShardPlan.from_env, as "
    "'name:size[,name:size...]' with at most one -1 (inferred from "
    "the device count) — e.g. 'batch:-1' (pure data parallel) or "
    "'batch:4,model:2' (DP x TP composition). The 'batch' axis (or "
    "the first axis named) is the data-parallel axis.")
register_flag(
    "MXSHARD_ZERO", bool, True,
    "ZeRO-style sharding of optimizer state (and thereby the fused "
    "weight-update computation) along the batch axis "
    "(shard.ShardPlan.state_spec): per-replica optimizer memory "
    "scales ~1/N with data-parallel replicas. Off = optimizer state "
    "mirrors its weight's (usually replicated) sharding.")
register_flag(
    "MXELASTIC_HEARTBEAT_S", float, 2.0,
    "Elastic-membership heartbeat interval in seconds (elastic."
    "MembershipTracker): workers beat at every step boundary and "
    "inside every blocked protocol wait; a worker silent for "
    "MXELASTIC_HEARTBEAT_S x MXELASTIC_MISS_LIMIT seconds is declared "
    "lost and the membership generation bumps, fencing in-flight "
    "exchanges with the typed MembershipChanged "
    "(docs/resilience.md elastic section).")
register_flag(
    "MXELASTIC_MISS_LIMIT", int, 3,
    "Missed-heartbeat budget before a worker-lost verdict (elastic."
    "MembershipTracker.check): lost_after = MXELASTIC_HEARTBEAT_S x "
    "this. Lower = faster recovery after a hard kill, higher = more "
    "tolerance for GC pauses / slow steps.")
register_flag(
    "MXELASTIC_MIN_WORLD", int, 1,
    "Smallest world size elastic training may shrink to before the "
    "group HARD-FAILS (elastic.MembershipTracker): below this, every "
    "elastic operation raises GroupFailed so the cluster manager "
    "restarts the job from checkpoint instead of limping on too few "
    "workers.")
register_flag(
    "MXELASTIC_LR_SCALE", bool, True,
    "Linear-scaling rule across membership changes (gluon Trainer."
    "_on_membership_change): after a generation bump the learning "
    "rate is set to base_lr x world/ref_world so per-sample update "
    "magnitude tracks the shrunken/grown global batch. Schedulers are "
    "instead driven through the session's virtual update counter "
    "(samples-based step accounting). Off = LR untouched.")
register_flag(
    "MXELASTIC_LOSS_TOL", float, 0.15,
    "Declared relative tolerance for the elastic loss-trajectory "
    "contract: the final loss of a kill/rejoin drill must match the "
    "uninterrupted run within this fraction (tools/mxresil.py "
    "elastic). The rescaled-batch/LR accounting exists to keep runs "
    "inside it.")
register_flag(
    "MXPIPE_SCHEDULE", str, "1f1b",
    "Microbatch schedule for pipelined training (mxnet_tpu/pipe/"
    "schedule.py, docs/pipeline.md): '1f1b' (non-interleaved one-"
    "forward-one-backward — same tick count and bubble as GPipe but "
    "peak in-flight activations bounded at min(M, S-s) per stage) or "
    "'gpipe' (all forwards then all backwards; peak in-flight = M "
    "everywhere). Both are explicit dependency-validated tick "
    "programs; bubble fraction is (S-1)/(M+S-1) for either.",
    choices=("1f1b", "gpipe"))
register_flag(
    "MXPIPE_MICROBATCH", int, 0,
    "Microbatch count M for the pipeline schedule "
    "(pipe.PipeStepFunction). 0 = auto: M = n_stage, the smallest M "
    "that keeps every stage busy in steady state; raise it to shrink "
    "the bubble fraction (S-1)/(M+S-1) at the cost of more ticks. "
    "The global batch must divide by M — pipelint flags violations "
    "as errors before the runner raises.")
register_flag(
    "MXPIPE_STAGES", int, 0,
    "Pipeline stage count S. 0 = auto: one stage per host in the "
    "elastic/pod membership view (a lost host is a lost stage), or 1 "
    "outside a session. The LM's layer count must divide by S; "
    "checkpoints save the DENSE layout, so the same checkpoint "
    "restores into any valid S (docs/pipeline.md re-stage section).")
register_flag(
    "MXPIPE_BALANCE_TOL", float, 0.25,
    "Stage-balance threshold for passes/pipelint.py: a stage whose "
    "param bytes deviate from the per-stage mean by more than this "
    "fraction draws a warn (the pipeline clocks at the SLOWEST "
    "stage, so imbalance is pure bubble). First/last stages "
    "legitimately carry embed/head extras; size the tolerance to "
    "what your vocab adds.")
register_flag(
    "MXGUARD", bool, False,
    "Silent-corruption integrity taps (mxnet_tpu/guard/, docs/"
    "resilience.md integrity section): per-gradient fingerprints "
    "(checksum, absmax, non-finite count) ride as extra outputs of "
    "the fused train step, cross-replica voting fences a corrupt "
    "replica BEFORE its gradients enter the allreduce, and the EWMA "
    "anomaly probe feeds the watchdog. Part of the fused-step "
    "signature-cache key: flipping it re-keys once, steady state "
    "stays at zero recompiles; taps-on training is bitwise-identical "
    "in weights to taps-off (test-enforced).")
register_flag(
    "MXGUARD_VOTE_TOL", float, 1000.0,
    "Cross-replica vote threshold (guard.fingerprint.vote): a "
    "gradient fingerprint's absmax beyond this factor over the OTHER "
    "replicas' median votes the replica suspect. Legitimate "
    "per-worker batch spread is single-digit; an exponent bit flip "
    "is ~1e30x — the default leaves orders of magnitude of margin "
    "both ways.")
register_flag(
    "MXGUARD_EWMA_FACTOR", float, 100.0,
    "Anomaly factor for the report-only EWMA loss/grad-norm probe "
    "(guard.anomaly.GuardProbe, registered on the resil watchdog): a "
    "step whose loss or gradient absmax exceeds this factor over its "
    "EWMA emits an integrity-anomaly finding naming the replay "
    "window for tools/mxresil.py replay.")
register_flag(
    "MXGUARD_RING", int, 256,
    "Capacity (steps) of the deterministic-replay record ring "
    "(guard.replay.ReplayRecorder): per step one small record of "
    "batch crc32 digests, the raw RNG key, hyper scalars, the loss "
    "digest and the fingerprint matrix — what `tools/mxresil.py "
    "replay` re-executes bitwise to bisect the first corrupted step.")
register_flag(
    "MXGUARD_CKPT_EVERY", int, 25,
    "Known-good checkpoint-ring cadence (steps) of the replay "
    "recorder: a ring checkpoint commits only while no guard verdict "
    "has flagged the run (a snapshot taken after corruption entered "
    "the weights must never become a recovery point — the ring "
    "freezes once tainted).")
register_flag(
    "MXGUARD_STRICT", bool, False,
    "Hard-fail the ONE-PROGRAM fused step on non-finite gradient "
    "fingerprints (GuardCorruption). Off by default: the fused "
    "program has already applied the update when the taps surface, "
    "so there is nothing to retry — the split-phase elastic step "
    "instead classifies by re-execution and retries/quarantines "
    "regardless of this flag.")
register_flag(
    "MXPOD_COORDINATOR", str, "",
    "host:port of the pod control plane (pod.PodContext): rank 0 "
    "binds a kvstore server carrying the elastic membership "
    "coordinator there; every rank's ElasticKVStore reaches it over "
    "the framed-pickle socket transport. Empty = fall back to the "
    "MX_KV_SERVER env exported by tools/launch.py (single process "
    "without either: a loopback server on a free port).")
register_flag(
    "MXPOD_RANK", int, -1,
    "This process's pod rank (pod.PodContext). -1 = fall back to the "
    "launcher env (MX_WORKER_ID / OMPI_COMM_WORLD_RANK / ... via "
    "base.worker_rank). Rank 0 is the coordinator host: it binds "
    "MXPOD_COORDINATOR and owns the membership verdicts.")
register_flag(
    "MXPOD_NPROCS", int, 0,
    "Number of host processes in the pod (pod.PodContext). 0 = fall "
    "back to MX_NUM_WORKERS from the launcher. Group formation waits "
    "for this many registrations before the first exchange.")
register_flag(
    "MXPOD_HEARTBEAT_S", float, 0.0,
    "Pod host-heartbeat interval in seconds: PodContext maps it onto "
    "MXELASTIC_HEARTBEAT_S for both the rank-0 verdict policy and "
    "the worker-side pump, so one flag tunes host-loss detection "
    "end to end. 0 = keep MXELASTIC_HEARTBEAT_S as configured.")
register_flag(
    "MXPOD_JOURNAL_DIR", str, "",
    "Directory of the coordinator's control-plane journal (elastic."
    "ElasticCoordinator): the leader appends one JSON line per "
    "generation bump (generation, workers, devices), and a RESTARTED "
    "rank-0 replays the newest entry to re-form the group — members "
    "restored, generation bumped once more so every survivor fences "
    "with the usual MembershipChanged instead of orphaning "
    "(docs/resilience.md multi-host section). Empty = no journal "
    "(a coordinator restart orphans the group).")
register_flag(
    "MXPOD_COORDINATOR_GRACE_S", float, 30.0,
    "How long a worker's PodGroup keeps retrying the control-plane "
    "socket (bounded jittered backoff, resil.policy.RetryPolicy) "
    "after transport failures before raising the typed "
    "CoordinatorLost. Long enough to cover a coordinator restart + "
    "journal replay; waiters never wedge silently either way.")
register_flag(
    "MXTRACE", bool, True,
    "Correlated cross-subsystem tracing (mxnet_tpu/trace/, docs/"
    "observability.md): spans with trace_id/span_id/parent thread the "
    "serving path (endpoint -> router -> scheduler -> prefill/decode/"
    "verify) and the training path (step -> exchange -> guard vote -> "
    "elastic rebuild), feed the per-phase latency histograms "
    "(mxtrace_phase_*_seconds) and the crash flight recorder. On by "
    "default: a span is two monotonic clock reads, a deque append and "
    "one check whether a profile is being taken; on one TPU v5e its "
    "cost in a fused train step was not resolvable (step_ms +0.2% and "
    "-0.9% with MXTRACE on against off, inside a run-to-run spread of "
    "0.6-2.6%: PERF.md section 5); "
    "tracing never touches jit cache keys, so it can never recompile.")
register_flag(
    "MXTRACE_SAMPLE", float, 1.0,
    "Fraction of ROOT traces recorded (trace.span): the decision is "
    "made once where a trace starts (endpoint request, train step) "
    "and inherited by every child span, so a dropped trace pays "
    "~nothing. 1.0 = record everything (default); lower it on "
    "high-QPS serving to bound export volume.")
register_flag(
    "MXTRACE_EXPORT", str, "",
    "Path of the span JSON-lines sink (trace.export): every finished "
    "sampled span appends one line. Read it with `tools/mxprof.py "
    "trace <file>` or convert with trace.write_chrome. Empty = "
    "export off (spans still reach the in-memory flight recorder).")
register_flag(
    "MXTRACE_BUFFER_SPANS", int, 4096,
    "Per-thread finished-span buffer capacity (trace.span.drain "
    "collects + clears them). Oldest spans drop first; the flight "
    "recorder keeps its own per-subsystem rings.")
register_flag(
    "MXTRACE_RECORDER_SPANS", int, 256,
    "Spans retained per subsystem in the crash flight recorder "
    "(trace.recorder): the last-N window a dump freezes on breaker "
    "trip / engine crash / GroupFailed / guard quarantine / watchdog "
    "stall / SIGTERM.")
register_flag(
    "MXTRACE_DUMP_DIR", str, "",
    "Directory for flight-recorder dump files (mxtrace-flight-"
    "<reason>-<ts>.json). Empty = <tempdir>/mxtrace. Dumps are "
    "rate-limited per reason (5 s) so failure storms stay readable.")
register_flag(
    "MXOBS", bool, True,
    "Pod-scale observability plane (mxnet_tpu/obs/, docs/"
    "observability.md multi-host section): control-plane messages "
    "carry the caller's mxtrace context so one train step / rebuild / "
    "guard vote is ONE trace id across every rank, each host's "
    "heartbeat pump pushes a mergeable metrics snapshot to the rank-0 "
    "collector, and a rank-0 dump trigger broadcasts a coordinated "
    "flight-recorder capture over the heartbeat channel. Same "
    "discipline as MXTRACE: structurally zero-cost when off (one "
    "generation-keyed flag-cache read on the hot path, no wire "
    "fields, no collector state; tests/test_obs.py), its cost when on "
    "not measured on a chip, never touches jit cache keys.")
register_flag(
    "MXOBS_PUSH_INTERVAL_S", float, 2.0,
    "Seconds between a host's metrics-snapshot pushes to the rank-0 "
    "collector (obs.collector, ridden by the elastic heartbeat pump "
    "— no extra thread, no extra connection). Counters/histograms "
    "merge exactly on the collector (count/sum exact, reservoir "
    "merge weighted); lower it in drills that assert on freshness.")
register_flag(
    "MXOBS_EXPORT", str, "",
    "Path of the rank-0 POD-MERGED snapshot JSON-lines sink: the "
    "collector appends one line per export tick with the fleet-"
    "merged metrics plus per-rank sections. Empty = export off "
    "(merged snapshots still queryable via obs_merged / "
    "tools/diagnose.py).")
register_flag(
    "MXFLEET_HEARTBEAT_S", float, 1.0,
    "Seconds between a fleet engine worker's directory heartbeats to "
    "the coordinator (fleet.worker.EngineHost). The FleetController "
    "treats a worker whose last beat is older than 3x this as dead "
    "and rebuilds the replica group without it; the Router breaker "
    "already sheds it in the meantime.")
register_flag(
    "MXFLEET_AFFINITY", bool, True,
    "Prefix-affinity routing (fleet.routing): hash the first "
    "MXFLEET_AFFINITY_PAGES serve2.prefix.page_keys of each prompt "
    "and prefer the rendezvous-chosen decode worker, so templated "
    "prompts land where their KV pages already live. Off = pure "
    "shallowest-queue across hosts. Only consulted inside fleet/ — "
    "single-host Router behavior is untouched either way.")
register_flag(
    "MXFLEET_AFFINITY_PAGES", int, 4,
    "How many leading page-chain hashes feed the affinity key. "
    "Small = template-level affinity (shared system prompts "
    "colocate); large = whole-prompt affinity (less sharing, better "
    "isolation).")
register_flag(
    "MXFLEET_SPILL_FACTOR", float, 2.0,
    "Affinity spill threshold: the preferred worker is used only "
    "while its queue depth <= this factor x the shallowest worker's "
    "depth (+1). Above it the request spills to shallowest-queue — "
    "cache locality must never buy a convoy. 0 = never spill "
    "(strict affinity).")
register_flag(
    "MXFLEET_PREFILL_DISAGG", bool, True,
    "Prefill/decode disaggregation (fleet.controller): prompts go to "
    "a dedicated prefill worker first, which streams the finished KV "
    "pages to the chosen decode worker over the pagewire before the "
    "decode request lands (CPU host-transfer path; device-to-device "
    "is stubbed pending TPU DMA). Requires at least one registered "
    "prefill-role worker, else requests fall back to direct decode "
    "(the decode worker prefills locally, exactly the single-host "
    "path).")
register_flag(
    "MXFLEET_PAGEWIRE_CHUNK_PAGES", int, 8,
    "Pages per pagewire transfer chunk (fleet.pagewire): the "
    "fixed-shape export/import jit programs move this many KV pages "
    "per dispatch (warmed by DecodeEngine warmup alongside the "
    "decode rungs, so streaming never recompiles). Larger = fewer "
    "dispatches, more padding on the tail chunk.")
register_flag(
    "MXFLEET_AUTOSCALE_WINDOW_S", float, 30.0,
    "Autoscaler observation window (fleet.autoscale.AutoScaler): "
    "grow/shrink decisions read the decode-phase p99 from the merged "
    "obs snapshots over this window, with the same span as cooldown "
    "between actuations (rolling_reload resizes are not free). "
    "0 = autoscaler disabled.")
register_flag(
    "MXFLEET_SLO_P99_MS", float, 0.0,
    "Decode p99 SLO target in milliseconds for the autoscaler: "
    "sustained p99 above it grows the group by one replica, p99 "
    "under half of it (with idle queues) shrinks by one. 0 = no SLO "
    "-> autoscaler holds (observability-only).")
register_flag(
    "MXRESIL_WATCHDOG_STALL_S", float, 0.0,
    "Heartbeat age that counts as a stall (resil.watchdog.Watchdog). "
    "0 = auto: 10x the step-time EWMA (min 1 s; 30 s before any step "
    "has been observed).")
register_flag(
    "MXSAN", bool, False,
    "Runtime lock-order sanitizer (mxnet_tpu/san/, docs/observability"
    ".md MXSAN runbook): the hot subsystems' locks (serve2, pod, "
    "elastic, trace, telemetry) are constructed through san.make_lock/"
    "make_rlock/make_condition — with MXSAN=1 they come back "
    "instrumented, recording the per-thread acquisition-order graph "
    "(cycles = potential deadlocks, reported with BOTH acquisition "
    "stacks), per-lock hold/wait/contention stats (san.export_to_"
    "registry publishes mxsan_lock_* instruments), and a flight-"
    "recorder dump when a waiter blocks past MXSAN_BLOCK_THRESHOLD_MS."
    " Off (default) = the factories return plain threading primitives:"
    " zero wrappers, zero overhead, no recompiles (tests/test_mxsan.py"
    " holds the identity). Read at LOCK CONSTRUCTION time — set "
    "it before building engines/groups (module-level locks capture it "
    "at import).")
register_flag(
    "MXSAN_BLOCK_THRESHOLD_MS", float, 1000.0,
    "MXSAN=1 only: a sanitized-lock waiter blocked longer than this "
    "triggers ONE mxsan-blocked-waiter flight-recorder dump naming "
    "the lock, the holder's acquisition site and the waiter's stack — "
    "then keeps waiting (the sanitizer reports wedges, it never "
    "changes blocking semantics). 0 disables the threshold.")
register_flag(
    "MXNET_KVSTORE_TIMEOUT_MS", float, 0.0,
    "Per-request timeout for kvstore data-plane push/pull over the "
    "dist_async transport: exceeding it raises the typed "
    "KVStoreTimeoutError (retryable by resil policies) instead of "
    "hanging. 0 (default) = fall back to the barrier-timeout-based "
    "socket deadline. An active resil deadline_scope caps it further.")
register_flag(
    "MXNET_KVSTORE_BARRIER_TIMEOUT", float, 300.0,
    "Seconds a worker waits at a dist barrier before declaring the "
    "job failed (failure detection, SURVEY.md §5.3; the reference's "
    "ps-lite van timeouts play this role).")
register_flag(
    "MXTUNE_AUTO", bool, False,
    "Auto-apply tuned configs on bind (mxnet_tpu/tune/, docs/tuning"
    ".md): Trainer.fuse_step, ServingEngine and DecodeEngine consult "
    "the tuning DB at bind time and apply the best measured config "
    "whose key matches this process exactly (model signature, device "
    "kind, mesh shape, knob-space fingerprint) — logging what was "
    "applied with its measured value and provenance. ANY mismatch or "
    "validation failure falls back to defaults (loudly logged, never "
    "raised into the bind). Off (default) = binding is bit-identical "
    "to a build without mxtune (test-enforced).")
register_flag(
    "MXTUNE_DB_DIR", str, "",
    "Tuning-DB directory (tune_db.jsonl lives here). Empty (default) "
    "= ~/.mxnet_tpu/tune. Point search and serving at the same dir "
    "to share tuned configs; the DB is append-crash-safe and "
    "self-compacting (docs/tuning.md, DB format section).")
register_flag(
    "MXTUNE_BUDGET", int, 16,
    "Default measurement budget (trials) for tune.run_search and "
    "`python tools/mxtune.py search` when no "
    "explicit budget is passed. Trial 0 always measures the DEFAULTS "
    "config, so the best entry is never worse than stock; the "
    "learned cost model starts pruning once ~len(space)+2 legal "
    "measurements exist (docs/tuning.md, budget guidance).")
register_flag(
    "MXTUNE_OBJECTIVE", str, "auto",
    "Objective auto-apply optimizes for, from tune.OBJECTIVES "
    "(fused_step_time_s, serve2_open_qps_slo, serve_open_qps_slo). "
    "'auto' (default) = per bind kind: fuse_step->fused_step_time_s, "
    "DecodeEngine->serve2_open_qps_slo, ServingEngine->"
    "serve_open_qps_slo.")
register_flag(
    "MXNET_TEST_SEED", int, -1,
    "Fixed seed for the test harness; -1 = random per test "
    "(ref: tests/python/unittest/common.py).")
register_flag(
    "MXNET_MODULE_SEED", int, -1,
    "Fixed module-level test seed; -1 = random "
    "(ref: tests/python/unittest/common.py:189).")

# ---------------------------------------------------------------------------
# Accepted-but-inert flags (XLA/PJRT owns the job). Setting them warns.
# ---------------------------------------------------------------------------

for _name, _type, _default, _doc, _note in [
    ("MXNET_GPU_MEM_POOL_TYPE", str, "Naive",
     "GPU memory pool selector (ref: storage.cc:103).",
     "PJRT owns device memory pooling"),
    ("MXNET_GPU_MEM_POOL_RESERVE", int, 5,
     "Percent of GPU memory held back from the pool.",
     "PJRT owns device memory pooling"),
    ("MXNET_EXEC_ENABLE_INPLACE", bool, True,
     "Allow in-place buffer sharing in the memory planner.",
     "XLA's buffer assignment handles aliasing/donation"),
    ("MXNET_EXEC_NUM_TEMP", int, 1,
     "Number of temp-space resources per device.",
     "XLA allocates scratch internally"),
    ("MXNET_CPU_PRIORITY_NTHREADS", int, 4,
     "Priority-queue worker threads of the CPU engine.",
     "PJRT schedules host work"),
    ("MXNET_GPU_WORKER_NTHREADS", int, 2,
     "Per-GPU engine worker threads.",
     "PJRT streams replace engine worker pools"),
    ("MXNET_OMP_MAX_THREADS", int, 0,
     "OpenMP thread cap for CPU kernels.",
     "XLA:CPU threadpool is sized by jax"),
    ("MXNET_CUDNN_AUTOTUNE_DEFAULT", int, 1,
     "cuDNN conv algo autotuning.",
     "XLA autotunes convolutions during compilation"),
    ("MXNET_CUDA_ALLOW_TENSOR_CORE", bool, True,
     "Allow TensorCore math.",
     "use jax.default_matmul_precision / bf16 policies"),
    ("MXNET_ENABLE_OPERATOR_TUNING", int, 1,
     "Enable/disable operator tuning.",
     "superseded by MXNET_USE_OPERATOR_TUNING (active)"),
    ("MXNET_KVSTORE_USETREE", bool, False,
     "Topology-aware tree reduction (ref: comm_tree.h).",
     "ICI collectives are already topology-optimal"),
    ("MXNET_KVSTORE_REDUCTION_NTHREADS", int, 4,
     "CPU threads for kvstore reduction.",
     "psum runs on-device over ICI"),
    ("MXNET_ENABLE_GPU_P2P", bool, True,
     "Peer-to-peer GPU copies in device comm.",
     "ICI replaces P2P copies"),
    ("MXNET_MKLDNN_ENABLED", bool, True,
     "MKL-DNN CPU kernels.", "XLA:CPU generates its own kernels"),
]:
    register_flag(_name, _type, _default, _doc, active=False,
                  tpu_note=_note)
