#!/usr/bin/env python
"""Per-operator performance harness (ref: benchmark/opperf/ — runs
representative registered ops with standard input shapes and reports
forward / forward+backward wall time).

Usage:
  python tools/opperf.py [--profile small|large] [--runs 20] [--json]
  python tools/opperf.py --ops exp,dot,Convolution
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--tpu" not in sys.argv:  # default CPU: an ad-hoc tool must not
    import jax                # take the chip from the job that holds it
    jax.config.update("jax_platforms", "cpu")

import numpy as onp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, nd  # noqa: E402

# benchmark matrix: name -> (input builder, kwargs) per profile.
_PROFILES = {
    "small": {"vec": (2 ** 14,), "mat": (128, 128), "batch": 8,
              "img": (8, 3, 32, 32), "seq": (8, 64, 64)},
    "large": {"vec": (2 ** 22,), "mat": (1024, 1024), "batch": 64,
              "img": (64, 3, 224, 224), "seq": (32, 512, 512)},
}


def _ops_table(p):
    rs = onp.random.RandomState(0)

    def rnd(shape):
        return nd.array(rs.rand(*shape).astype("float32") + 0.1)

    mat, vec, img, seq = p["mat"], p["vec"], p["img"], p["seq"]
    return {
        # unary elementwise
        "exp": (lambda: [rnd(vec)], {}, nd.exp),
        "sqrt": (lambda: [rnd(vec)], {}, nd.sqrt),
        "tanh": (lambda: [rnd(vec)], {}, nd.tanh),
        "relu": (lambda: [rnd(vec)], {}, nd.relu),
        # binary broadcast
        "broadcast_add": (lambda: [rnd(mat), rnd((1, mat[1]))], {},
                          nd.broadcast_add),
        "broadcast_mul": (lambda: [rnd(mat), rnd((mat[0], 1))], {},
                          nd.broadcast_mul),
        # reductions
        "sum": (lambda: [rnd(mat)], {}, nd.sum),
        "mean_axis": (lambda: [rnd(mat)], {"axis": 1}, nd.mean),
        "argmax": (lambda: [rnd(mat)], {"axis": 1}, nd.argmax),
        # linear algebra
        "dot": (lambda: [rnd(mat), rnd(mat)], {}, nd.dot),
        "batch_dot": (lambda: [rnd((p["batch"],) + mat),
                               rnd((p["batch"],) + mat)], {},
                      nd.batch_dot),
        # NN layers
        "FullyConnected": (
            lambda: [rnd((p["batch"], mat[0])), rnd((256, mat[0])),
                     rnd((256,))], {"num_hidden": 256},
            nd.FullyConnected),
        "Convolution": (
            lambda: [rnd(img), rnd((16, img[1], 3, 3)), rnd((16,))],
            {"num_filter": 16, "kernel": (3, 3), "pad": (1, 1)},
            nd.Convolution),
        "Pooling": (lambda: [rnd(img)],
                    {"kernel": (2, 2), "stride": (2, 2),
                     "pool_type": "max"}, nd.Pooling),
        "softmax": (lambda: [rnd(mat)], {}, nd.softmax),
        "BatchNorm": (
            lambda: [rnd(img), rnd((img[1],)), rnd((img[1],)),
                     rnd((img[1],)), rnd((img[1],))], {},
            nd.BatchNorm),
        # indexing
        "take": (lambda: [rnd(mat), nd.array(
            rs.randint(0, mat[0], (64,)).astype("float32"))], {},
            nd.take),
        "one_hot": (lambda: [nd.array(
            rs.randint(0, 64, (p["batch"] * 64,)).astype("float32"))],
            {"depth": 64}, nd.one_hot),
        "transpose": (lambda: [rnd(mat)], {}, nd.transpose),
        # random samplers
        "random_uniform": (lambda: [], {"shape": vec},
                           mx.nd.random_uniform),
        "random_normal": (lambda: [], {"shape": vec},
                          mx.nd.random_normal),
    }


def time_op(name, builder, kwargs, fn, runs, warmup=3):
    args = builder()
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    _sync(out)
    lat = _sync_latency(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args, **kwargs)
    _sync(out)
    raw = time.perf_counter() - t0
    fwd_ms = _net(raw, lat) / runs * 1e3
    dominated = _dominated(raw, lat)

    bwd_ms = None
    grad_args = [a for a in args if a.dtype.kind == "f"]
    if grad_args and name not in ("argmax", "one_hot", "random_uniform",
                                  "random_normal"):
        for a in grad_args:
            a.attach_grad()
        try:
            head = None  # allocated once; shape is fixed across runs
            for _ in range(warmup):
                with autograd.record():
                    out = fn(*args, **kwargs)
                    out = out[0] if isinstance(out, (list, tuple)) else out
                if head is None:
                    head = nd.ones(out.shape)
                out.backward(head)
            _sync(grad_args[0].grad)
            t0 = time.perf_counter()
            for _ in range(runs):
                with autograd.record():
                    out = fn(*args, **kwargs)
                    out = out[0] if isinstance(out, (list, tuple)) else out
                out.backward(head)
            _sync(grad_args[0].grad)
            raw = time.perf_counter() - t0
            bwd_ms = _net(raw, lat) / runs * 1e3
            dominated = dominated or _dominated(raw, lat)
        except Exception:
            bwd_ms = None
    return fwd_ms, bwd_ms, dominated


def _sync(out):
    from mxnet_tpu.util import d2h_fence
    d2h_fence(out)


def _sync_latency(out):
    """Flat cost of the fence itself; fed to util.net_time per timed
    region."""
    from mxnet_tpu.util import d2h_fence_latency
    return d2h_fence_latency(out)


def _net(elapsed, lat):
    from mxnet_tpu.util import net_time
    return net_time(elapsed, lat)


def _dominated(elapsed, lat):
    from mxnet_tpu.util import lat_dominated
    return lat_dominated(elapsed, lat)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--profile", default="small",
                   choices=sorted(_PROFILES))
    p.add_argument("--runs", type=int, default=10)
    p.add_argument("--ops", default=None,
                   help="comma-separated subset")
    p.add_argument("--json", action="store_true")
    p.add_argument("--tpu", action="store_true")
    args = p.parse_args(argv)

    table = _ops_table(_PROFILES[args.profile])
    selected = [s.strip() for s in args.ops.split(",")] if args.ops \
        else sorted(table)
    results = []
    for name in selected:
        if name not in table:
            print(f"unknown op {name}; choices: {sorted(table)}",
                  file=sys.stderr)
            continue
        builder, kwargs, fn = table[name]
        fwd, bwd, dom = time_op(name, builder, kwargs, fn, args.runs)
        results.append({"op": name, "fwd_ms": round(fwd, 4),
                        "fwd_bwd_ms": round(bwd, 4) if bwd else None,
                        "lat_dominated": dom})
    if not results:
        print("no valid ops selected", file=sys.stderr)
        sys.exit(2)
    if args.json:
        print(json.dumps({"profile": args.profile, "runs": args.runs,
                          "results": results}))
    else:
        w = max(len(r["op"]) for r in results) + 2
        print(f"{'operator'.ljust(w)}{'fwd (ms)':>12}{'fwd+bwd (ms)':>15}")
        for r in results:
            b = f"{r['fwd_bwd_ms']:.4f}" if r["fwd_bwd_ms"] else "-"
            star = " *" if r["lat_dominated"] else ""
            print(f"{r['op'].ljust(w)}{r['fwd_ms']:>12.4f}{b:>15}{star}")
        if any(r["lat_dominated"] for r in results):
            print("* sync round-trip >30% of the timed region — raise "
                  "--runs for a trustworthy number")
    return results


if __name__ == "__main__":
    main()
