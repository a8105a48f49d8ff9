"""mxnet_tpu.serve2: multi-replica routed serving with continuous
batching and a paged KV-cache (ISSUE 8).

PR 3's :mod:`~mxnet_tpu.serve` is the request/response vertical: one
engine, one model instance, whole-request batching. This package is the
production tier above and beside it:

- :mod:`~mxnet_tpu.serve2.kvcache` — fixed-size KV pages, per-sequence
  block tables, a host-side allocator (page 0 reserved as the null
  page); admit/finish/preempt are host-side bookkeeping only, so
  compiled shapes never change;
- :mod:`~mxnet_tpu.serve2.decode` — :class:`PagedLM`: the in-repo
  ``pipeline_lm`` decoder stack compiled into ONE prefill program per
  prompt rung and ONE decode-step program per batch rung, attention via
  :func:`~mxnet_tpu.parallel.paged_attention.paged_attention`
  (ring-attention-style online softmax over the page axis), page pools
  donated to XLA;
- :mod:`~mxnet_tpu.serve2.scheduler` — :class:`DecodeEngine`:
  iteration-level continuous batching (admit prefills, step ALL
  in-flight sequences per tick, recompute-preempt on pool exhaustion)
  behind the same ``predict`` duck type as ``ServingEngine``;
- :mod:`~mxnet_tpu.serve2.router` — :class:`Router`: N replicas per
  model group, queue-depth + circuit-breaker aware routing
  (resil-backed graceful degradation), and zero-downtime rolling model
  reload with version pinning in the
  :class:`~mxnet_tpu.serve.endpoint.ModelRegistry`.

serve3 (ISSUE 12) adds three independently-gated legs on this
substrate: **prefix caching** (:mod:`~mxnet_tpu.serve2.prefix` —
content-hashed refcounted pages shared across requests, copy-on-write
on shared writes), **speculative decoding** (a small draft model
proposes K tokens, :meth:`PagedLM.verify` checks them in ONE batched
target forward with exact greedy acceptance), and **quantized KV
pages** (``kv_dtype="int8"/"bf16"`` pools with per-slot dequant
scales). ``MXSERVE3_*`` flags gate each leg.

Non-autoregressive (CNN) models keep serving through
:class:`~mxnet_tpu.serve.engine.ServingEngine`; the router mixes both
behind one front door. ``tools/mxserve.py route|reload|loadgen --qps``
are the CLIs;
``passes/servelint.py`` lints the closed-cache/donation contract;
docs/serving.md has the v2 architecture and runbook.
"""
from .kvcache import (BlockTable, PageAllocator,  # noqa: F401
                      PagePoolExhausted, pages_needed)
from .prefix import PrefixCache, page_keys  # noqa: F401
from .decode import KV_DTYPES, PagedLM, decode_rungs_for  # noqa: F401
from .scheduler import (DecodeEngine, EngineCrashedError,  # noqa: F401
                        GenerationHandle)
from .router import (AllReplicasUnavailable, RoutedModel,  # noqa: F401
                     Router)

__all__ = [
    "BlockTable", "PageAllocator", "PagePoolExhausted", "pages_needed",
    "PrefixCache", "page_keys", "KV_DTYPES",
    "PagedLM", "decode_rungs_for", "DecodeEngine", "EngineCrashedError",
    "GenerationHandle",
    "Router", "RoutedModel", "AllReplicasUnavailable",
]
