"""Multi-process dist_sync KVStore worker.

TPU-native analog of the reference's distributed kvstore test
(ref: tests/nightly/dist_sync_kvstore.py, launched via
`tools/launch.py -n 2 --launcher local`): every rank pushes
rank-dependent values, pulls, and asserts the synchronous sum — here the
ps-lite push/pull is a Gloo/ICI allreduce under jax.distributed.

Run:  python tools/launch.py -n 2 python tests/nightly/dist_sync_kvstore.py
"""
import os
import sys

import jax

# CPU backend for the multi-process harness: a chip belongs to one
# process, and both ranks would otherwise try to open it
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as onp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402


def expected_2bit(arr, residual, threshold):
    """ref: compute_expected_2bit_quantization in the reference test."""
    acc = arr + residual
    q = onp.where(acc >= threshold, threshold,
                  onp.where(acc <= -threshold, -threshold, 0.0))
    return q, acc - q


def main():
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == int(os.environ["MX_NUM_WORKERS"]), \
        f"num_workers {nw} != launched {os.environ['MX_NUM_WORKERS']}"

    # --- plain synchronous push/pull ------------------------------------
    shape = (3, 4)
    kv.init("w", nd.zeros(shape))
    val = onp.full(shape, float(rank + 1), "float32")
    kv.push("w", nd.array(val))
    out = nd.zeros(shape)
    kv.pull("w", out=out)
    expect = sum(float(r + 1) for r in range(nw))
    assert onp.allclose(out.asnumpy(), expect), \
        f"rank {rank}: pull got {out.asnumpy()[0, 0]}, want {expect}"

    # --- barrier ---------------------------------------------------------
    kv.barrier()

    # --- int keys + multi-key push ---------------------------------------
    kv.init([3, 5], [nd.ones(shape), nd.ones(shape)])
    kv.push([3, 5], [nd.array(val), nd.array(2 * val)])
    outs = [nd.zeros(shape), nd.zeros(shape)]
    kv.pull([3, 5], out=outs)
    assert onp.allclose(outs[0].asnumpy(), 1 + expect)
    assert onp.allclose(outs[1].asnumpy(), 1 + 2 * expect)

    # --- 2-bit gradient compression with error feedback ------------------
    kv2 = mx.kv.create("dist_sync")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv2.init("g", nd.zeros(shape))
    grads = onp.full(shape, 0.3 * (rank + 1), "float32")
    exp_store = onp.zeros(shape, "float32")
    for step in range(3):
        kv2.push("g", nd.array(grads))
        got = nd.zeros(shape)
        kv2.pull("g", out=got)
        # expected: every rank quantizes its grad (with its own error
        # feedback), the sums accumulate in the store
        q_sum = onp.zeros(shape, "float32")
        for r in range(nw):
            q_r, _ = expected_2bit(onp.full(shape, 0.3 * (r + 1)),
                                   _res_of(r, step), 0.5)
            q_sum += q_r
        exp_store += q_sum
        assert onp.allclose(got.asnumpy(), exp_store, atol=1e-6), \
            f"rank {rank}: compressed pull {got.asnumpy()[0, 0]} " \
            f"vs {exp_store[0, 0]}"

    print(f"rank {rank}/{nw}: DIST_KVSTORE_OK", flush=True)


def _res_of(rank, step):
    """Residual of rank `rank` entering step `step` for grad 0.3*(rank+1),
    threshold 0.5 (closed form for the 3-step loop above)."""
    g = 0.3 * (rank + 1)
    res = 0.0
    for _ in range(step):
        acc = g + res
        q = 0.5 if acc >= 0.5 else (-0.5 if acc <= -0.5 else 0.0)
        res = acc - q
    return res


if __name__ == "__main__":
    main()
