"""Traffic kind ``train_device_batches``: a training loop over batches
that live on the device.

``n_batches`` batches are made on the device from the seed and cycled.
The loop calls ``fused.step(x, y)`` back to back and blocks on the loss
of step *i - ahead* before it dispatches step *i*, as a loop that logs
its loss does: the host runs ahead by a bounded amount and the window's
end is well defined. After ``seconds`` it stops dispatching and blocks on
the last loss; the window runs from the first dispatch to that moment.
There is no input pipeline here: that is another kind.

Set-up builds ONE step object (``Trainer.fuse_step``), drives it through
its first steps from the seed (they compile it, warm it, and give the
readings that decide ``correct``), and hands that same object to the
window. The reference runs after the window, once the peak memory is
read and the program's state is freed.

A mix's file gives: ``kind``, ``batch``, ``n_batches`` (at least the
steps the reference follows), ``ahead`` (how many losses may be
outstanding), ``warmup_steps`` (further steps before the window),
``trace_seconds`` (the length of the traced stretch in a ``--trace 1``
run) and what the family needs besides (``seq``).
"""
import collections
import gc
import time

import jax
import jax.numpy as jnp

from benchmark import correctness

ANNOTATIONS = ("bench.dispatch", "bench.block")


def _loop(fused, batches, seconds, ahead, wrap):
    """Dispatch steps for ``seconds``; returns (steps, window seconds,
    the steps' loss vectors)."""
    pending = collections.deque()
    losses = []
    n = len(batches)
    steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        x, y = batches[steps % n]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            loss = fused.step(wrap(x), wrap(y))._data
        pending.append(loss)
        losses.append(loss)
        steps += 1
        if len(pending) > ahead:
            with jax.profiler.TraceAnnotation("bench.block"):
                pending.popleft().block_until_ready()
    with jax.profiler.TraceAnnotation("bench.block"):
        for loss in pending:
            loss.block_until_ready()
    return steps, time.perf_counter() - t0, losses


def _counters():
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import metrics
    host = sum(metrics.histogram(f"fused_step_{k}_seconds").sum
               for k in ("host", "dispatch", "writeback"))
    return {"host_seconds": host,
            "recompiles": telemetry.recompile_count(),
            "compile_cache_misses": metrics.counter(
                "jax_compile_cache_misses_total").value()}


def prepare(ctx):
    """Set-up: weights and batches from the seed, ONE step object, driven
    through its first steps. Returns ``(net, trainer, fused, batches,
    readings)``; the step object is warm and is the one to time."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import _wrap

    family, sizes, traffic, opt = ctx.family, ctx.sizes, ctx.traffic, ctx.opt
    if traffic["n_batches"] < correctness.N_STEPS:
        raise ValueError("the mix needs as many batches as the reference "
                         "follows steps")
    mx_ctx = mx.tpu(0) if ctx.device.platform == "tpu" else mx.cpu(0)
    weights = family.make_weights(sizes, ctx.policy, ctx.seed)
    batches = family.make_batches(sizes, ctx.policy, traffic, ctx.seed)
    net, loss_fn = family.build_program(sizes, ctx.policy, weights, mx_ctx,
                                        batches[0][0])
    del weights
    hyper = {k: v for k, v in opt.items() if k != "name"}
    trainer = gluon.Trainer(net.collect_params(), opt["name"], hyper)
    fused = trainer.fuse_step(net, loss_fn)
    trainable = [n for n, p in net._collect_params_with_prefix().items()
                 if p.grad_req != "null"]
    readings = correctness.ProgramReadings(opt, net, trainer, trainable)
    # the steps draw their random keys from here on as the
    # configuration states (``assumed.rng``), so the reference can make
    # the same dropout masks
    mx.random.seed(correctness.program_seed(ctx.seed))
    for i in range(correctness.N_STEPS):
        x, y = batches[i]
        loss = fused.step(_wrap(x), _wrap(y))._data
        last = i == correctness.N_STEPS - 1
        # the weights the net started from, made again from the seed: a
        # copy kept through the steps would sit in the peak memory
        start = family.make_weights(sizes, ctx.policy, ctx.seed) \
            if last else None
        readings.after_step(loss, start)
        del start
    return net, trainer, fused, batches, readings


def peak_bytes(device):
    """The peak on the chip as its allocator reports it: the most it
    held in buffers plus the most it reserved for programs' temporaries.
    ``peak_bytes_in_use`` alone leaves a program's temporaries out: PR 24
    read 0.75 GB there for a step whose program holds 8.7 GB of them,
    which ``peak_bytes_reserved`` gave to the byte."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def run(ctx):
    """Drive one cell once. ``ctx`` is the harness's ``Run``; returns
    the kind's result: end-to-end numbers, counts, ``compared``, and in
    a traced run the step's HLO text and the counters that the per-layer
    readers need."""
    from mxnet_tpu.ndarray.ndarray import _wrap

    family, sizes, traffic, opt = ctx.family, ctx.sizes, ctx.traffic, ctx.opt
    net, trainer, fused, batches, readings = prepare(ctx)
    hlo_text = None
    if ctx.trace:
        hlo_text = fused.compiled(_wrap(batches[0][0]),
                                  _wrap(batches[0][1])).as_text()
    for i in range(traffic["warmup_steps"]):
        x, y = batches[i % len(batches)]
        fused.step(_wrap(x), _wrap(y))._data.block_until_ready()
    setup_counters = _counters()
    setup_s = time.perf_counter() - ctx.t_start

    # -- the window -----------------------------------------------------
    before = _counters()
    if ctx.trace:
        seconds = min(ctx.seconds, traffic["trace_seconds"])
        with ctx.tracing():
            steps, window_s, losses = _loop(fused, batches, seconds,
                                            traffic["ahead"], _wrap)
    else:
        steps, window_s, losses = _loop(fused, batches, ctx.seconds,
                                        traffic["ahead"], _wrap)
    after = _counters()
    finite = jax.device_get(jnp.stack(
        [jnp.all(jnp.isfinite(l)) for l in losses]))
    failed = int(len(losses) - finite.sum())
    del losses
    peak = peak_bytes(ctx.device)

    # -- free the program, then the reference ---------------------------
    got = readings.readings()
    del readings, fused, trainer, net
    gc.collect()
    weights = family.make_weights(sizes, ctx.policy, ctx.seed)
    t0 = time.perf_counter()
    ref = correctness.reference_follow(
        family, sizes, opt, weights, batches,
        correctness.step_keys(ctx.seed), "reference")
    reference_s = time.perf_counter() - t0
    correct, compared, detail = correctness.compare(got, ref, ctx.limits)
    detail["reference_seconds"] = reference_s

    units = family.work_units(sizes, traffic)
    end_to_end = {"setup_s": setup_s,
                  "step_ms": 1e3 * window_s / steps}
    for unit, per_step in units.items():
        end_to_end[f"{unit}_per_s"] = per_step * steps / window_s
    return {
        "correct": correct and failed == 0, "compared": compared,
        "detail": detail, "attempted": steps, "failed": failed,
        "end_to_end": end_to_end, "memory_peak_bytes": peak,
        "window_s": window_s, "steps": steps, "hlo_text": hlo_text,
        "annotations": ANNOTATIONS,
        "counters": {
            "setup": setup_counters,
            "window": {k: after[k] - before[k] for k in after}},
    }
