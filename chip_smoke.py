#!/usr/bin/env python
"""chip_smoke.py: the system's two main paths, once, on one TPU chip.

The quickest proof that the program still starts on the chip. In ONE
process (a chip belongs to one process at a time), through the entry
points a user calls, at the full width of models the repo ships and
with random weights made from ``--seed``:

- ``resnet50``: ``gluon.model_zoo.vision.resnet50_v1(classes=1000)`` at
  224x224, batch 256, bf16 parameters with f32 BatchNorm statistics,
  SGD+momentum through ``gluon.Trainer.fuse_step``;
- ``bert``: ``models.BERTModel()`` at its defaults (12 x 768, 12 heads,
  ffn 3072, vocab 30522), T=512, batch 16, Adam, through ``fuse_step``,
  with the Pallas flash kernel asserted from the compiled step's HLO;
- ``serve2``: ``serve2.DecodeEngine`` over a 12-layer d_model-768
  ``PagedLM`` with 2048 f32 pages (2.25 GiB of K+V), warmed over its
  rungs, answering eight requests of mixed prompt length.

Each training phase takes two warm-up steps and five more on a fixed
batch and checks: finite, falling loss; every parameter and
optimizer-state leaf on the TPU; no recompile after warm-up; and
agreement of the fused step with an eager record/backward/step from the
same initial state on a leading slice of the batch (the eager loop
keeps every activation, so the whole batch does not fit beside it). The
serving phase checks one request's prefill logits and all its greedy
tokens against ``pipeline_lm.dense_lm_logits``.

It prints one JSON line per phase and exits non-zero as soon as one
fails. The last line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it.

Without ``--tiny`` there is no size at which this carries on without a
TPU: it exits 2 at once. ``--tiny`` is the rehearsal of the control flow
at toy widths under ``JAX_PLATFORMS=cpu``; it reports the platform it
really ran on. ``--multichip`` needs four chips and runs only the
ShardPlan path and what it is compared with: BERT-base through
``fuse_step(..., shard_plan=ShardPlan({"batch": 2, "model": 2}, ...))``
for three Adam steps against the same three steps on one device.
"""
import argparse
import faulthandler
import gc
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
# everything the run generates (operator_tune's op_tune.json, ...) lives
# inside the checkout and starts empty — never from a file outside git
STATE_DIR = os.path.join(ROOT, ".chip_smoke_state")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

FULL = {
    "resnet50": dict(model="resnet50_v1", classes=1000, image=224,
                     batch=256, parity_batch=32, thumbnail=False),
    "bert": dict(model={}, seq=512, batch=16, parity_batch=4),
    "serve2": dict(
        lm=dict(vocab=32768, d_model=768, n_layers=12, n_heads=12,
                d_head=64, d_ff=3072, n_experts=1),
        page_size=16, num_pages=2048, max_inflight=8, decode_steps=4,
        prefill_buckets=(64, 128, 256, 512, 1024), new_tokens=64,
        prompt_lens=(64, 1024, 100, 333, 512, 200, 900, 700),
        parity_request=5),
}
TINY = {
    "resnet50": dict(model="resnet18_v1", classes=10, image=32, batch=8,
                     parity_batch=4, thumbnail=True),
    "bert": dict(model=dict(vocab_size=128, units=64, num_layers=2,
                            num_heads=2, hidden_size=128, max_len=64),
                 seq=64, batch=4, parity_batch=2),
    "serve2": dict(
        lm=dict(vocab=128, d_model=64, n_layers=2, n_heads=2, d_head=32,
                d_ff=128, n_experts=1),
        page_size=4, num_pages=64, max_inflight=4, decode_steps=2,
        prefill_buckets=(8, 16, 32), new_tokens=6,
        prompt_lens=(8, 32, 11, 20), parity_request=2),
}

# stated tolerances on the mean loss, relative to the first step's: the
# fused program and the op-by-op eager loop round differently —
# whole-program fusions keep intermediates in f32 where eager stores
# every op's output in the parameter dtype — so agreement is to
# rounding, not bitwise. (first step: the same forward from the same
# state; second step, where it is compared: after one update from
# gradients that were themselves rounded differently, so looser — an
# update that was not applied, or applied twice, misses it by far more)
PARITY_RTOL = {"bfloat16": (3e-2, 1e-1), "float32": (5e-3, 2e-2)}
# sharded vs one device: same math, other reduction orders and collectives
MULTICHIP_RTOL = 5e-3
# paged prefill vs the dense reference, on logits of scale ~1
SERVE_LOGIT_ATOL = 5e-2


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(obj):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# shared helpers (everything below runs after jax is known to be usable)
# ---------------------------------------------------------------------------

def cache_counts():
    from mxnet_tpu.telemetry import metrics
    return (metrics.counter("jax_compile_cache_hits_total").value(),
            metrics.counter("jax_compile_cache_misses_total").value())


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def mean_loss(loss):
    import numpy as onp
    return float(onp.asarray(loss.asnumpy(), dtype="float64").mean())


def bf16_policy(net):
    """The bf16 policy: parameters (and so activations) in bf16,
    BatchNorm scale/shift/statistics in f32."""
    keep = ("gamma", "beta", "running_mean", "running_var",
            "moving_mean", "moving_var")
    for name, p in net._collect_params_with_prefix().items():
        if name.rsplit(".", 1)[-1] not in keep:
            p.cast("bfloat16")


def snapshot(net):
    return {n: p.data().copy()
            for n, p in net._collect_params_with_prefix().items()}


def restore(net, snap):
    # a fresh copy each time: the fused step donates what it is given
    for n, p in net._collect_params_with_prefix().items():
        p.set_data(snap[n].copy())


def tuner_summary():
    """operator_tune's measured choices of this process, per tuned op:
    how often each candidate won, and which candidates failed."""
    from mxnet_tpu import operator_tune
    best = {}
    for key, cost in operator_tune.cost_table().items():
        head, _, sig = key.partition("|")
        name, _, label = head.partition("[")
        cur = best.get((name, sig))
        if cur is None or cost < cur[1]:
            best[(name, sig)] = (label.rstrip("]"), cost)
    wins = {}
    for (name, _), (label, _) in best.items():
        wins.setdefault(name, {}).setdefault(label, 0)
        wins[name][label] += 1
    return {"wins": wins,
            "failed": sorted(operator_tune.candidate_failures())}


def build_bert(cfg, ctx, seed):
    """BERT on ``ctx`` with a fixed random batch and a per-token loss:
    (net, loss_fn, tokens, labels); shapes are not resolved yet."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, models, nd

    mx.random.seed(seed)
    rng = onp.random.RandomState(seed)
    net = models.BERTModel(**cfg["model"])
    net.initialize(ctx=ctx)
    vocab = cfg["model"].get("vocab_size", 30522)
    B, T = cfg["batch"], cfg["seq"]
    tokens = nd.array(rng.randint(0, vocab, (B, T)), ctx=ctx,
                      dtype="int32")
    labels = nd.array(rng.randint(0, vocab, (B, T)).astype("float32"),
                      ctx=ctx)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    class TokenLoss(gluon.HybridBlock):
        def hybrid_forward(self, F, logits, lab):
            return ce(logits.reshape((-1, vocab)), lab.reshape((-1,)))

    return net, TokenLoss(), tokens, labels


def device_arrays(net, trainer):
    """Every parameter and optimizer-state leaf, as jax arrays."""
    import jax
    arrays = [p.data()._data
              for p in net._collect_params_with_prefix().values()]
    states = jax.tree.leaves(trainer._updaters[0].states,
                             is_leaf=lambda v: hasattr(v, "_data"))
    return arrays + [v._data for v in states if hasattr(v, "_data")]


def train_phase(cfg, device, net, loss_fn, x, y, optimizer,
                optimizer_params, dtype, seed, batch_coupled,
                after_compile=None):
    """The checks both training phases share; returns the phase's
    report. ``net`` lives on ``device`` with its shapes resolved.

    The eager reference runs on the first ``parity_batch`` samples.
    Where the model couples the samples of a batch (BatchNorm), the
    fused step is compiled a second time for that slice and two steps
    are compared: the loss before and after one update. Where it does
    not (LayerNorm; dropout bits depend on an element's index alone,
    not on the batch size), the slice's losses are read out of the
    full-batch step's per-sample loss vector, and no second program is
    needed — at BERT's width that second compile costs minutes."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, telemetry
    from mxnet_tpu.util import d2h_fence, d2h_fence_latency

    snap = snapshot(net)
    n = cfg["parity_batch"]
    xs, ys = x[:n], y[:n]
    # the same key data drives dropout in both loops (the fused step
    # takes it as rng_raw; eagerly, trace_rng derives the same keys)
    keys = [jax.random.key_data(jax.random.key(seed + i))
            for i in range(2 if batch_coupled else 1)]

    # -- the eager reference: record / backward / trainer.step ---------
    t0 = time.perf_counter()
    eager_tr = gluon.Trainer(net.collect_params(), optimizer,
                             dict(optimizer_params))
    eager = []
    for raw in keys:
        with mx.random.trace_rng(jax.random.wrap_key_data(raw)):
            with autograd.record():
                loss = loss_fn(net(xs), ys)
        loss.backward()
        eager_tr.step(n)
        eager.append(mean_loss(loss))
    eager_s = time.perf_counter() - t0
    del eager_tr, loss

    # -- the fused step from the same initial state --------------------
    restore(net, snap)
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            dict(optimizer_params))
    fused = trainer.fuse_step(net, loss_fn)
    t0 = time.perf_counter()
    if batch_coupled:
        fused_small = [mean_loss(fused.step(xs, ys, rng_raw=raw))
                       for raw in keys]
        restore(net, snap)
    parity_s = time.perf_counter() - t0
    del snap

    # -- the full batch: two warm-up steps and five more ---------------
    t0 = time.perf_counter()
    loss = fused.step(x, y, rng_raw=keys[0])
    losses = [mean_loss(loss)]
    compile_s = time.perf_counter() - t0
    if not batch_coupled:
        per_sample = loss.size // x.shape[0]
        fused_small = [mean_loss(loss[:n * per_sample])]
    rtol = PARITY_RTOL[dtype]
    rel = [abs(f - e) / abs(eager[0]) for f, e in zip(fused_small, eager)]
    check(all(r <= tol for r, tol in zip(rel, rtol)),
          f"fused step and eager step disagree beyond rtol {rtol}: "
          f"fused {fused_small} eager {eager}")
    losses.append(mean_loss(fused.step(x, y)))
    extra = after_compile(fused) if after_compile else {}
    recompiles0 = telemetry.recompile_count()
    step_s = []
    for _ in range(5):
        t0 = time.perf_counter()
        loss = fused.step(x, y)
        jax.block_until_ready(loss._data)
        step_s.append(time.perf_counter() - t0)
        losses.append(mean_loss(loss))
    recompiles = telemetry.recompile_count() - recompiles0
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    check(recompiles == 0, f"{recompiles} recompile(s) after warm-up")

    # one step timed both ways: is block_until_ready honest here? (the
    # fence's own latency first — that also compiles its tiny slice)
    fence_lat = d2h_fence_latency(loss)
    t0 = time.perf_counter()
    jax.block_until_ready(fused.step(x, y)._data)
    bur_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    d2h_fence(fused.step(x, y))
    fence_s = time.perf_counter() - t0

    leaves = device_arrays(net, trainer)
    strays = [str(a.devices()) for a in leaves
              if a.devices() != {device}]
    check(not strays, f"{len(strays)} of {len(leaves)} parameter/state "
          f"leaves are not on {device}: {strays[:3]}")

    return dict(
        compile_seconds=round(compile_s, 2),
        step_seconds_median=sorted(step_s)[len(step_s) // 2],
        step_seconds=step_s, losses=losses,
        recompiles_after_warmup=recompiles,
        leaves_on_device=len(leaves),
        parity=dict(batch=n, rtol=rtol[:len(rel)], eager=eager,
                    fused=fused_small, rel_diff=rel,
                    fused_program="its own" if batch_coupled
                    else "the full batch's, sliced",
                    eager_seconds=round(eager_s, 2),
                    fused_seconds=round(parity_s, 2)),
        one_step_block_until_ready_seconds=bur_s,
        one_step_d2h_fence_seconds=fence_s,
        d2h_fence_latency_seconds=fence_lat, **extra)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_resnet50(cfg, ctx, device, seed):
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import config, gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    rng = onp.random.RandomState(seed)
    net = getattr(vision, cfg["model"])(classes=cfg["classes"],
                                        thumbnail=cfg["thumbnail"])
    net.initialize(mx.init.Xavier(), ctx=ctx)
    B, S = cfg["batch"], cfg["image"]
    x = nd.array(rng.uniform(-1, 1, (B, 3, S, S)).astype("float32"),
                 ctx=ctx)
    y = nd.array(rng.randint(0, cfg["classes"], (B,)).astype("float32"),
                 ctx=ctx)
    t0 = time.perf_counter()
    net(x[:1]).wait_to_read()  # deferred shapes; the conv tuner measures
    init_s = time.perf_counter() - t0
    tuned = tuner_summary()
    bf16_policy(net)
    x = x.astype("bfloat16")
    # the eager reference takes the layout the tuner chose at that
    # forward instead of measuring every conv shape again at its batch
    layouts = tuned["wins"].get("conv_layout")
    if layouts:
        config.set_flag("MXNET_OPTUNE_CHOICE_CONV_LAYOUT",
                        max(layouts, key=layouts.get))
    try:
        out = train_phase(cfg, device, net,
                          gluon.loss.SoftmaxCrossEntropyLoss(), x, y,
                          "sgd", {"learning_rate": 0.05, "momentum": 0.9},
                          "bfloat16", seed, batch_coupled=True)
    finally:
        config.unset_flag("MXNET_OPTUNE_CHOICE_CONV_LAYOUT")
    return dict(model=cfg["model"], batch=B, image=S, dtype="bfloat16",
                init_forward_seconds=round(init_s, 2),
                operator_tune=tuned, **out)


def phase_bert(cfg, ctx, device, seed, on_tpu):
    from mxnet_tpu import telemetry

    net, loss_fn, tokens, labels = build_bert(cfg, ctx, seed)
    t0 = time.perf_counter()
    net(tokens[:1]).wait_to_read()  # deferred shapes
    init_s = time.perf_counter() - t0

    def after_compile(fused):
        text = fused.compiled(tokens, labels).as_text()
        n_calls = text.count("tpu_custom_call")
        check(n_calls > 0 or not on_tpu,
              "no tpu_custom_call in BERT's compiled step: the dense "
              "composition ran, not the fused attention kernel")
        return {"tpu_custom_calls_in_step_hlo": n_calls}

    out = train_phase(cfg, device, net, loss_fn, tokens, labels, "adam",
                      {"learning_rate": 1e-4}, "float32", seed,
                      batch_coupled=False, after_compile=after_compile)
    traced = {label: telemetry.counter(
        f"attention_traced_total.{label}").value()
        for label in ("kernel", "dense")}
    return dict(model="BERTModel", batch=cfg["batch"], seq=cfg["seq"],
                dtype="float32", init_forward_seconds=round(init_s, 2),
                attention_traced=traced, **out)


def phase_serve2(cfg, device, seed, on_tpu):
    import jax
    import numpy as onp
    from mxnet_tpu import serve2
    from mxnet_tpu.parallel.pipeline_lm import (dense_lm_logits,
                                                init_pipeline_lm)
    from mxnet_tpu.serve2.kvcache import pages_needed
    from mxnet_tpu.telemetry import metrics

    rng = onp.random.RandomState(seed)
    params = init_pipeline_lm(seed, **cfg["lm"])
    new = cfg["new_tokens"]
    engine = serve2.DecodeEngine(
        params, page_size=cfg["page_size"], num_pages=cfg["num_pages"],
        max_inflight=cfg["max_inflight"],
        prefill_buckets=cfg["prefill_buckets"], max_new_default=new,
        max_seq_len=max(cfg["prefill_buckets"]) + new,
        decode_steps=cfg["decode_steps"], name="smoke")
    lm = engine.lm
    formulation = dict(attention=lm.attention,
                       donate_pages=lm.donate_pages,
                       kv_dtype=lm.kv_dtype, pool_bytes=lm.pool_bytes)
    emit(dict(phase="serve2.formulation", **formulation))
    try:
        if on_tpu:
            check(lm.attention == "scan" and lm.donate_pages is True,
                  f"serve2 did not take the chip's branch: {formulation}")
        t0 = time.perf_counter()
        programs = engine.warmup()
        warm_s = time.perf_counter() - t0

        vocab = cfg["lm"]["vocab"]
        prompts = [rng.randint(0, vocab, (n,)).astype("int32")
                   for n in cfg["prompt_lens"]]
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=new) for p in prompts]
        for h in handles:
            check(h.wait(600.0), f"request {h.sid} did not finish")
            if h.error is not None:
                raise h.error
        serve_s = time.perf_counter() - t0
        for h in handles:
            check(h.result.shape == (new,) and h.result.min() >= 0
                  and h.result.max() < vocab,
                  f"request {h.sid}: bad result {h.result!r}")

        # one request against the dense reference: the prefill's last
        # logits, and every greedy token, teacher-forced (by induction
        # the tokens are then the reference's own greedy continuation)
        i = cfg["parity_request"]
        prompt, got = prompts[i], handles[i].result
        L = len(prompt)
        ref = onp.asarray(jax.jit(dense_lm_logits)(
            params, onp.concatenate([prompt, got[:-1]])[None])[0])
        pages = engine.alloc.alloc(pages_needed(L, cfg["page_size"]))
        try:
            rung = min(r for r in engine.prefill_rungs if r >= L)
            padded = onp.zeros((rung,), "int32")
            padded[:L] = prompt
            bt_row = onp.zeros((engine.max_pages_per_seq,), "int32")
            bt_row[:len(pages)] = pages
            _, last = lm.prefill(padded, L, bt_row)
        finally:
            engine.alloc.free(pages)
        logit_err = float(onp.abs(last - ref[L - 1]).max())
        check(logit_err <= SERVE_LOGIT_ATOL,
              f"prefill logits differ from dense_lm_logits by "
              f"{logit_err} > {SERVE_LOGIT_ATOL}")
        steps = ref[L - 1:]                       # (new, vocab)
        want = steps.argmax(-1)
        # a token that differs from the reference's argmax may only be
        # a tie inside the logit tolerance
        gap = steps.max(-1) - steps[onp.arange(new), got]
        check(bool((gap <= SERVE_LOGIT_ATOL).all()),
              f"greedy tokens leave the dense reference's argmax "
              f"continuation: got {got.tolist()} want {want.tolist()}")
        # what the attached chip's compiler made of the top decode rung
        # (a cache hit): ROADMAP S5's whole-pool temporaries, or not
        B, N = max(engine.decode_rungs), engine.max_pages_per_seq
        i32 = onp.zeros((B,), "int32")
        mem = lm._decode_jit.lower(
            lm.params, lm.pools, onp.zeros((B, N), "int32"), i32, i32,
            i32).compile().memory_analysis()
        decode_memory = dict(
            rung=B, argument_bytes=mem.argument_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes)
        recompiled = metrics.counter(
            "mxserve2_recompile_after_warmup_total").value()
        stats = engine.stats()
        check(recompiled == 0 and stats["recompiles_after_warmup"] == 0,
              f"serve2 compiled {recompiled} program(s) after warm-up")
    finally:
        engine.close()
    return dict(
        lm=cfg["lm"], num_pages=cfg["num_pages"], **formulation,
        compile_seconds=round(warm_s, 2),
        programs=[(p["program"], p["size"], p["compile_ms"])
                  for p in programs],
        requests=len(prompts), prompt_lens=list(cfg["prompt_lens"]),
        new_tokens=new, serve_seconds=round(serve_s, 2),
        tokens_generated=stats["tokens_generated"], ticks=stats["ticks"],
        preemptions=stats["preemptions"],
        decode_program_memory=decode_memory,
        parity=dict(request=i, prompt_len=L, logit_atol=SERVE_LOGIT_ATOL,
                    prefill_logit_max_abs_err=logit_err,
                    greedy_exact_matches=int((got == want).sum()),
                    greedy_ties_within_atol=int((got != want).sum())),
        recompiles_after_warmup=recompiled)


def phase_multichip(cfg, devices, seed, on_tpu):
    """BERT through the ShardPlan path on a 2x2 mesh against the same
    three Adam steps on one device from the same seed."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.models import tensor_parallel_shardings
    from mxnet_tpu.parallel import hlo_check
    from mxnet_tpu.shard import ShardPlan

    ctx = mx.tpu(0) if on_tpu else mx.cpu(0)
    net, loss_fn, tokens, labels = build_bert(cfg, ctx, seed)
    net(tokens[:1]).wait_to_read()  # deferred shapes
    host = {n: p.data().asnumpy()
            for n, p in net._collect_params_with_prefix().items()}
    adam = {"learning_rate": 1e-4}

    plan = ShardPlan({"batch": 2, "model": 2},
                     param_specs=tensor_parallel_shardings(net),
                     devices=devices[:4])
    mx.random.seed(seed)
    trainer = gluon.Trainer(net.collect_params(), "adam", dict(adam))
    fused = trainer.fuse_step(net, loss_fn, shard_plan=plan)
    t0 = time.perf_counter()
    sharded = [mean_loss(fused.step(tokens, labels))]
    compile_s = time.perf_counter() - t0
    sharded += [mean_loss(fused.step(tokens, labels))
                for _ in range(2)]

    params = net._collect_params_with_prefix()
    arrays = device_arrays(net, trainer)
    held = plan.per_device_bytes(arrays)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:4]}
    shardings = {n: str(p.data()._data.sharding.spec)
                 for n, p in params.items()}
    # distinct shard indices of each buffer: 1 = every device holds
    # the same (whole) array, >1 = devices hold different shards
    distinct = [len({str(s.index) for s in a.addressable_shards})
                for a in arrays]
    check(len(held) == 4 and max(held.values())
          <= 1.25 * min(held.values()),
          f"the four devices do not hold comparable bytes: {held}")
    check(sum(d > 1 for d in distinct) > len(distinct) // 2,
          "most buffers are whole on every device: nothing is "
          f"sharded ({distinct})")
    report = fused.shard_report(tokens, labels)
    # GSPMD cannot partition a Pallas kernel, so the sharded step traces
    # the dense attention (ops.pallas_kernels.gspmd_partitioned); the
    # single-device side below takes whatever its own path takes
    n_kernels = report["hlo"].count("tpu_custom_call")
    collectives = hlo_check.summarize(
        hlo_check.collective_report(report["hlo"], plan.mesh))
    check(any("[batch]" in k for k in collectives)
          and any("[model]" in k for k in collectives),
          f"no collective over both mesh axes: {collectives}")
    del fused, trainer, arrays, report

    # the same three steps on one device
    for n, p in params.items():
        p.set_data(nd.array(host[n], ctx=ctx))
    mx.random.seed(seed)
    trainer = gluon.Trainer(net.collect_params(), "adam", dict(adam))
    fused = trainer.fuse_step(net, loss_fn)
    single = [mean_loss(fused.step(tokens, labels))
              for _ in range(3)]
    rel = [abs(s - o) / abs(o) for s, o in zip(sharded, single)]
    check(all(r <= MULTICHIP_RTOL for r in rel),
          f"sharded and single-device losses disagree beyond "
          f"{MULTICHIP_RTOL}: {sharded} vs {single}")
    return dict(
        model="BERTModel", batch=cfg["batch"], seq=cfg["seq"],
        mesh=plan.axes,
        compile_seconds=round(compile_s, 2),
        tpu_custom_calls_in_sharded_hlo=n_kernels,
        losses_sharded=sharded, losses_single=single, rel_diff=rel,
        rtol=MULTICHIP_RTOL, held_bytes_per_device=held,
        bytes_in_use_per_device=in_use,
        buffers=len(distinct),
        buffers_sharded=sum(d > 1 for d in distinct),
        collectives=collectives, param_shardings=shardings)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_phase(name, device, fn, *args):
    t0 = time.perf_counter()
    hits0, misses0 = cache_counts()
    ok, out = True, {}
    try:
        out = fn(*args)
    except Exception as e:  # the boundary: report the phase, then stop
        traceback.print_exc()
        ok, out = False, {"error": f"{type(e).__name__}: {e}"[:4000]}
    hits, misses = cache_counts()
    emit(dict(phase=name, ok=ok,
              seconds=round(time.perf_counter() - t0, 2),
              compile_cache=dict(hits=hits - hits0,
                                 misses=misses - misses0),
              peak_bytes_in_use=peak_bytes(device), **out))
    if not ok:
        sys.exit(1)
    gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths on whatever platform jax has: the "
                         "rehearsal of the control flow, not a chip run")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: the ShardPlan path and what it is "
                         "compared with, and no other phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # a hung device wait must not outlive the driver's limit
    faulthandler.dump_traceback_later(1150, exit=True)
    shutil.rmtree(STATE_DIR, ignore_errors=True)
    os.environ["MXNET_HOME"] = STATE_DIR
    if args.tiny and args.multichip and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax
    devices = jax.devices()
    device = devices[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke.py: jax found no TPU (platform "
              f"{device.platform!r}); the full run does not carry on "
              "without one — use --tiny to rehearse the control flow",
              file=sys.stderr)
        return 2
    need = 4 if args.multichip else 1
    if len(devices) < need:
        print(f"chip_smoke.py: needs {need} device(s), jax has "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from importlib import metadata
    import jaxlib
    import mxnet_tpu as mx
    from mxnet_tpu import native
    from mxnet_tpu.step.cache import enable_compile_cache
    enable_compile_cache(CACHE_DIR)
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    emit(dict(phase="start", ok=True, tiny=args.tiny,
              multichip=args.multichip, seed=args.seed,
              platform=device.platform, device_kind=device.device_kind,
              device_count=len(devices), jax=jax.__version__,
              jaxlib=jaxlib.__version__, libtpu=libtpu,
              native_library=native.status(),
              compile_cache_dir=jax.config.jax_compilation_cache_dir))

    cfg = TINY if args.tiny else FULL
    if args.multichip:
        run_phase("multichip", device, phase_multichip, cfg["bert"],
                  devices, args.seed, on_tpu)
    else:
        ctx = mx.tpu(0) if on_tpu else mx.cpu(0)
        run_phase("resnet50", device, phase_resnet50, cfg["resnet50"],
                  ctx, device, args.seed)
        run_phase("bert", device, phase_bert, cfg["bert"], ctx, device,
                  args.seed, on_tpu)
        run_phase("serve2", device, phase_serve2, cfg["serve2"], device,
                  args.seed, on_tpu)
    emit({"ok": True,
          "device": {"platform": device.platform,
                     "kind": device.device_kind,
                     "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
