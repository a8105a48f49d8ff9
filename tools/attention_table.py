#!/usr/bin/env python3
"""Kernel against dense attention on the attached chip, forward plus
backward: the measurement behind ``flash_attention_available``'s rule.

    python tools/attention_table.py [--bh 192] [--block 1] [--causal 1]
    python tools/attention_table.py --head64 1 [--block 1]
    python tools/attention_table.py --window 1 [--way band]
    python tools/attention_table.py --latent 1
    python tools/attention_table.py --kda 1

One JSON line a case: ``{"t", "d", "dtype", "causal", "kernel_ms",
"dense_ms"}`` (a side that does not fit the device reads null), the
attention alone or, with ``--block 1``, inside one block of a model
(projection, heads, attention, output projection: the difference of the
two sides is attention's, laid out as a step lays it out); for
``--check 1`` both sides' gaps to dense at ``highest`` precision.
``--head64 1`` is the table behind ``banded_attention``'s rule for a
head narrower than the lanes: causal grouped-query attention at 32 query
heads over 8 key/value heads of 64, 8192 tokens, bfloat16, forward plus
backward, as jax's splash kernel over heads zero-padded to 128 lanes
(``splash_padded_ms``), as this repo's ``flash_attention`` with the
key/value heads repeated (``flash_ms``) and as the XLA composition
(``xla_ms``).
``--window 1`` is the table behind the band kernel's rule and its
constants (``ops/banded_attention.py BAND_BLOCK``, ``BAND_SUB``): the
Laguna cell's window layer (64 query heads over 8 key/value heads of
128, 8192 tokens, window 512, bfloat16), forward plus backward, one JSON
line a way (``{"way", "alone_ms", "in_block_ms"}``): the band kernel on
token-major heads at each query block tried, jax's splash
kernel at blocks of 512 with two backward kernels (what ran before PR
34) and at 1024 with the fused one, and the XLA composition;
``in_block_ms`` between a q/k/v and an output projection of 2048, less
the projections alone.
``--latent 1`` is the table behind ``splash_available``'s 192: latent
attention's products (32 heads, scores 192 wide over values 128 wide,
4096 tokens, causal, bfloat16), forward plus backward, one JSON line a
way (``{"way", "alone_ms", "in_block_ms"}``): jax's splash kernel over
q and k zero-padded to 256 lanes and the XLA composition, alone and
between the q and key/value projections and the output projection of a
2560-wide model, less the projections alone.
``--kda 1`` is the table behind ``ops/kda.py KDA_CHUNK``: the gated
delta rule at the Ling cell's mixer (4096 tokens, 32 heads of 128,
bfloat16 q, k, v, float32 decay and beta) under ``jax.checkpoint``, as
the mixer that holds the call rematerialises it, forward plus backward,
one JSON line a chunk size (32, 64, 128).
Times are the device's own, from a profile of the calls; a CPU run
refuses to start (its times would say nothing about the chip).
"""
import argparse
import functools
import glob
import json
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from mxnet_tpu.ops.pallas_kernels import flash_attention  # noqa: E402
from mxnet_tpu.parallel.ring_attention import local_attention  # noqa: E402


def time_ms(fn, args, reps):
    """Device time of one call in ms: the program's runs on the chip's
    ``XLA Modules`` line of a profile over ``reps`` calls (a host clock
    around calls this short reads the dispatch, 0.6 ms, not the chip)."""
    trace_dir = tempfile.mkdtemp(prefix="attention_table_")
    try:
        step = jax.jit(fn)
        for _ in range(2):
            jax.block_until_ready(step(*args))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = options.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(reps):
                out = step(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(found[0])
        ns = sum(e.duration_ns for plane in profile.planes
                 if plane.name.startswith("/device:TPU:0")
                 for line in plane.lines if line.name == "XLA Modules"
                 for e in line.events)
        return ns / reps / 1e6
    except Exception as e:  # does not fit, or the compiler refuses it
        print(f"# {type(e).__name__}: {str(e)[:200]}", file=sys.stderr)
        return None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def fwd_bwd(fn):
    """``fn``'s result and its gradients for the cotangent given last."""
    def run(*args):
        out, vjp = jax.vjp(fn, *args[:-1])
        return (out,) + vjp(args[-1])
    return run


def in_block(attn, heads, d):
    """``attn`` where a model has it: between the q/k/v projection and
    the output projection of one block, so that XLA lays the heads out
    as it does inside a step (a 64-wide head is kept with T minor
    there, which the kernel takes as it is)."""
    def block(x, w, wo):
        b, t, c = x.shape
        qkv = (x @ w).reshape(b, t, 3, heads, d).transpose(2, 0, 3, 1, 4)
        out = attn(qkv[0], qkv[1], qkv[2])
        return out.transpose(0, 2, 1, 3).reshape(b, t, c) @ wo
    return block


def block_operands(bh, t, d, dtype):
    c = 12 * d
    keys = jax.random.split(jax.random.key(t * 1000 + d), 4)
    shapes = ((bh // 12, t, c), (c, 3 * c), (c, c), (bh // 12, t, c))
    return [(jax.random.normal(key, shape, jnp.float32)
             * (1.0 if i in (0, 3) else c ** -0.5)).astype(dtype)
            for i, (key, shape) in enumerate(zip(keys, shapes))]


def operands(bh, t, d, dtype):
    keys = jax.random.split(jax.random.key(t * 1000 + d), 4)
    return [jax.random.normal(key, (bh // 12, 12, t, d), jnp.float32)
            .astype(dtype) for key in keys]


def errors(bh, causal):
    """Kernel and dense against dense at ``highest`` precision, BERT's
    head shape in float32: the largest gap of the result and of each
    gradient over the reference's largest entry."""
    ops = operands(bh, 512, 64, jnp.float32)
    sides = {"kernel": lambda q, k, v: flash_attention(q, k, v, causal),
             "dense": lambda q, k, v: local_attention(q, k, v,
                                                      causal=causal)}
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(fwd_bwd(sides["dense"]))(*ops)
    for name, attn in sides.items():
        got = jax.jit(fwd_bwd(attn))(*ops)
        gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                for a, b in zip(got, ref)]
        print(json.dumps({"side": name, "gap_o_dq_dk_dv": gaps}),
              flush=True)


def head64(reps, block):
    """One JSON line: the three ways to a causal 32-over-8 head layer of
    64 at 8192 tokens; with ``block`` between a q/k/v and an output
    projection of 2048."""
    from mxnet_tpu.ops.banded_attention import banded_attention
    t, h, kv, d, c = 8192, 32, 8, 64, 2048
    keys = jax.random.split(jax.random.key(64), 6)
    bf16 = jnp.bfloat16

    def flash(q, k, v):
        g = q.shape[1] // k.shape[1]
        return flash_attention(q, jnp.repeat(k, g, axis=1),
                               jnp.repeat(v, g, axis=1), True)

    sides = {
        "splash_padded_ms": functools.partial(banded_attention,
                                              backend="splash"),
        "flash_ms": flash,
        "xla_ms": functools.partial(banded_attention, backend="xla")}
    if block:
        def model(attn):
            def run(x, wq, wkv, wo):
                q = (x @ wq).reshape(1, t, h, d).transpose(0, 2, 1, 3)
                kvs = (x @ wkv).reshape(1, t, 2, kv, d)
                k = kvs[:, :, 0].transpose(0, 2, 1, 3)
                v = kvs[:, :, 1].transpose(0, 2, 1, 3)
                o = attn(q, k, v).transpose(0, 2, 1, 3)
                return o.reshape(1, t, h * d) @ wo
            return run
        shapes = ((1, t, c), (c, h * d), (c, 2 * kv * d), (h * d, c),
                  (1, t, c))
        ops = [(jax.random.normal(key, s, jnp.float32)
                * (1.0 if i in (0, 4) else c ** -0.5)).astype(bf16)
               for i, (key, s) in enumerate(zip(keys, shapes))]
    else:
        model = lambda attn: attn
        shapes = ((1, h, t, d), (1, kv, t, d), (1, kv, t, d), (1, h, t, d))
        ops = [jax.random.normal(key, s, jnp.float32).astype(bf16)
               for key, s in zip(keys, shapes)]
    row = {"t": t, "d": d, "heads": h, "kv_heads": kv, "dtype": "bfloat16",
           "in_block": bool(block)}
    for side, attn in sides.items():
        row[side] = time_ms(fwd_bwd(model(attn)), ops, reps)
    print(json.dumps(row), flush=True)


def window_table(reps, only=""):
    """One JSON line a way to the Laguna cell's window layer (the ways
    whose name holds ``only``); see the module docstring."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    from mxnet_tpu.ops.banded_attention import (
        banded_attention, banded_attention_token_major)
    t, h, kv, d, c, window = 8192, 64, 8, 128, 2048, 512
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.key(512), 5)

    def token_major(attn):
        """``attn`` on (1, T, H * D) operands as the projections leave
        them (a way's relayouts are its own cost)."""
        def run(q, k, v):
            return attn(q.reshape(1, t, h, d), k.reshape(1, t, kv, d),
                        v.reshape(1, t, kv, d)).reshape(1, t, h * d)
        return run

    def heads_major(attn):
        def run(q, k, v):
            o = attn(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                     v.transpose(0, 2, 1, 3))
            return o.transpose(0, 2, 1, 3)
        return token_major(run)

    def splash_fused(q, k, v):
        b = 1024
        kernel = sk.make_splash_mqa_single_device(
            mask=sm.MultiHeadMask([sm.LocalMask(
                (t, t), window_size=(window - 1, 0), offset=0)] * (h // kv)),
            block_sizes=sk.BlockSizes(
                block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
                block_kv_dkv=b, block_kv_dkv_compute=b,
                use_fused_bwd_kernel=True))
        q = q * jnp.asarray(d ** -0.5, q.dtype)
        o = jax.vmap(jax.vmap(kernel))(q.reshape(1, kv, h // kv, t, d), k, v)
        return o.reshape(1, h, t, d)

    ways = {f"band_{bq}": token_major(functools.partial(
        banded_attention_token_major, window=window, block=bq,
        backend="band")) for bq in (512, 1024, 2048)}
    ways["splash_512_two_backward_kernels"] = heads_major(functools.partial(
        banded_attention, window=window, backend="splash"))
    ways["splash_1024_fused_backward"] = heads_major(splash_fused)
    ways["xla_composition"] = heads_major(functools.partial(
        banded_attention, window=window, backend="xla"))

    def model(attn):
        def run(x, wq, wkv, wo):
            q, kvs = x @ wq, x @ wkv
            o = attn(q, kvs[..., :kv * d], kvs[..., kv * d:]) if attn else q
            return o @ wo
        return run

    shapes = ((1, t, c), (c, h * d), (c, 2 * kv * d), (h * d, c), (1, t, c))
    block_ops = [(jax.random.normal(key, s, jnp.float32)
                  * (1.0 if i in (0, 4) else c ** -0.5)).astype(bf16)
                 for i, (key, s) in enumerate(zip(keys, shapes))]
    shapes = ((1, t, h * d), (1, t, kv * d), (1, t, kv * d), (1, t, h * d))
    ops = [jax.random.normal(key, s, jnp.float32).astype(bf16)
           for key, s in zip(keys, shapes)]
    projections = time_ms(fwd_bwd(model(None)), block_ops, reps)
    print(json.dumps({"way": "projections_alone",
                      "in_block_ms": projections}), flush=True)
    for way, attn in ways.items():
        if only not in way:
            continue
        inside = time_ms(fwd_bwd(model(attn)), block_ops, reps)
        print(json.dumps({
            "way": way, "alone_ms": time_ms(fwd_bwd(attn), ops, reps),
            "in_block_ms": None if inside is None else inside - projections,
        }), flush=True)


def latent_table(reps):
    """One JSON line a way to latent attention's products (module
    docstring)."""
    from mxnet_tpu.ops.banded_attention import banded_attention
    t, h, dqk, dv, c, rank = 4096, 32, 192, 128, 2560, 512
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.key(192), 6)

    def heads_major(attn):
        def run(q, k, v):
            o = attn(q.reshape(1, t, h, dqk).transpose(0, 2, 1, 3),
                     k.reshape(1, t, h, dqk).transpose(0, 2, 1, 3),
                     v.reshape(1, t, h, dv).transpose(0, 2, 1, 3))
            return o.transpose(0, 2, 1, 3).reshape(1, t, h * dv)
        return run

    def model(attn):
        def run(x, wq, wk, wv, wo):
            q = x @ wq
            o = attn(q, x[..., :rank] @ wk, x[..., :rank] @ wv) if attn \
                else q[..., :h * dv]
            return o @ wo
        return run

    shapes = ((1, t, c), (c, h * dqk), (rank, h * dqk), (rank, h * dv),
              (h * dv, c), (1, t, c))
    block_ops = [(jax.random.normal(key, s, jnp.float32)
                  * (1.0 if i in (0, 5) else s[0] ** -0.5)).astype(bf16)
                 for i, (key, s) in enumerate(zip(keys, shapes))]
    shapes = ((1, t, h * dqk), (1, t, h * dqk), (1, t, h * dv),
              (1, t, h * dv))
    ops = [jax.random.normal(key, s, jnp.float32).astype(bf16)
           for key, s in zip(keys, shapes)]
    projections = time_ms(fwd_bwd(model(None)), block_ops, reps)
    print(json.dumps({"way": "projections_alone",
                      "in_block_ms": projections}), flush=True)
    for way, backend in (("splash_padded_to_256", "splash"),
                         ("xla_composition", "xla")):
        attn = heads_major(functools.partial(banded_attention,
                                             backend=backend))
        inside = time_ms(fwd_bwd(model(attn)), block_ops, reps)
        print(json.dumps({
            "way": way, "alone_ms": time_ms(fwd_bwd(attn), ops, reps),
            "in_block_ms": None if inside is None else inside - projections,
        }), flush=True)


def kda_table(reps):
    """One JSON line a chunk size of the delta rule (module
    docstring)."""
    from mxnet_tpu.ops.kda import _kda
    t, h, d = 4096, 32, 128
    keys = jax.random.split(jax.random.key(128), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k, v, do = (jax.random.normal(key, (1, t, h, d), jnp.float32)
                   for key in keys[:4])
    log_a = -5.0 * jax.nn.sigmoid(
        jax.random.normal(keys[4], (1, t, h, d)) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, t, h)))
    bf16 = jnp.bfloat16
    ops = [unit(q).astype(bf16), unit(k).astype(bf16), v.astype(bf16),
           log_a, beta, do.astype(bf16)]
    for chunk in (32, 64, 128):
        fn = functools.partial(_kda, chunk=chunk)
        print(json.dumps({
            "chunk": chunk,
            "fwd_bwd_ms": time_ms(fwd_bwd(jax.checkpoint(fn)), ops, reps),
            "fwd_ms": time_ms(fn, ops[:5], reps)}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bh", type=int, default=192)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--causal", type=int, default=0)
    ap.add_argument("--check", type=int, default=0)
    ap.add_argument("--block", type=int, default=0)
    ap.add_argument("--head64", type=int, default=0)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--way", default="", help="with --window: only the "
                    "ways whose name holds this")
    ap.add_argument("--latent", type=int, default=0)
    ap.add_argument("--kda", type=int, default=0)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("attention_table.py measures a chip; none is attached")
    if args.head64:
        return head64(args.reps, args.block)
    if args.window:
        return window_table(args.reps, args.way)
    if args.latent:
        return latent_table(args.reps)
    if args.kda:
        return kda_table(args.reps)
    causal = bool(args.causal)
    if args.check:
        return errors(args.bh, causal)
    for d in (64, 128):
        for t in (128, 256, 384, 512, 1024, 2048):
            for dtype in (jnp.float32, jnp.bfloat16):
                row = {"t": t, "d": d, "dtype": dtype.__name__,
                       "causal": causal, "in_block": bool(args.block)}
                make = block_operands if args.block else operands
                ops = make(args.bh, t, d, dtype)
                sides = {
                    "kernel_ms": functools.partial(flash_attention,
                                                   causal=causal),
                    "dense_ms": functools.partial(local_attention,
                                                  causal=causal)}
                for side, attn in sides.items():
                    fn = in_block(attn, 12, d) if args.block else attn
                    row[side] = time_ms(fwd_bwd(fn), ops, args.reps)
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
