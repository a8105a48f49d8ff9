"""``ops/banded_attention.py`` at small sizes on the CPU: equal to dense
masked attention, forward and backward, for windows under, at and over
the block size and sequences that are no multiple of it; and its HLO
holds the band's work, not T x T.
"""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ops.banded_attention import (banded_attention,  # noqa: E402
                                            band_blocks)

from benchmark.families import laguna  # noqa: E402


# ---------------------------------------------------------------------------
# banded attention
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v, window):
    b, hq, t, d = q.shape
    g = hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    qp, kp = jnp.arange(t)[:, None], jnp.arange(t)[None]
    mask = kp <= qp
    if window is not None:
        mask = mask & (qp - kp < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("t,window,block", [
    (40, 5, 16),      # window under the block
    (48, 16, 16),     # at the block
    (48, 24, 16),     # over the block
    (48, 33, 16),     # over two blocks
    (37, 8, 16),      # T not a multiple of the block
    (37, None, 16),   # full causal, T not a multiple
    (64, None, 16),   # full causal
    (32, 64, 16),     # a window longer than the sequence: causal
    (24, 1, 8),       # every query sees itself alone
], ids=["under", "at", "over", "over2", "odd-t", "full-odd-t", "full",
        "window-over-t", "window-1"])
def test_banded_attention_equals_masked_dense(t, window, block):
    ks = jax.random.split(jax.random.key(t), 4)
    q = jax.random.normal(ks[0], (2, 6, t, 8))
    k = jax.random.normal(ks[1], (2, 2, t, 8))
    v = jax.random.normal(ks[2], (2, 2, t, 8))
    ct = jax.random.normal(ks[3], (2, 6, t, 8))
    got = banded_attention(q, k, v, window=window, block=block)
    want = _dense_attention(q, k, v, window)
    assert onp.allclose(got, want, atol=2e-6)
    g_got = jax.grad(lambda *a: jnp.sum(banded_attention(
        *a, window=window, block=block) * ct), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(
        _dense_attention(*a, window) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        assert onp.allclose(a, b, atol=1e-5)


def _dot_flops(fn, *args):
    """FLOPs of the dots in the optimised HLO of ``fn``."""
    from benchmark import trace_reduce
    text = jax.jit(fn).lower(*args).compile().as_text()
    return sum(f for f in trace_reduce.hlo_flops(text).values() if f)


@pytest.mark.parametrize("window,block,most", [(128, 128, 2.5),
                                               (128, 64, 1.8),
                                               (None, 128, 1.3)],
                         ids=["band-b128", "band-b64", "causal"])
def test_a_sliding_layer_does_banded_work(window, block, most):
    """The HLO of the forward pass holds under ``most`` times the FLOPs
    of the pairs the mask allows, and far under the dense T x T's: the
    work follows T x window."""
    t, g, d = 2048, 2, 32
    q = jnp.zeros((1, g, t, d))
    kv = jnp.zeros((1, 1, t, d))
    flops = _dot_flops(lambda q, k, v: banded_attention(
        q, k, v, window=window, block=block, backend="xla"), q, kv, kv)
    ideal = 2 * 2 * g * laguna.allowed_pairs(t, window) * d
    dense = 2 * 2 * g * t * t * d
    assert ideal <= flops < most * ideal
    if window is not None:
        assert flops < dense / 4
        assert band_blocks(window, block) == -(-(window - 1) // block) + 1


@pytest.mark.parametrize("window", [128, None], ids=["band", "causal"])
def test_the_kernel_computes_the_same_mask(window):
    """The Pallas splash kernel the TPU path runs, interpreted on the
    CPU at a head size it takes (128): the same attention as the
    composition, forward and backward, for a band (two backward
    kernels) and for full causal attention (the fused one)."""
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (1, 2, 256, 128))
    k = jax.random.normal(ks[1], (1, 1, 256, 128))
    v = jax.random.normal(ks[2], (1, 1, 256, 128))
    ct = jax.random.normal(ks[3], (1, 2, 256, 128))

    def run(backend):
        return jax.value_and_grad(lambda *a: jnp.sum(banded_attention(
            *a, window=window, block=128, backend=backend) * ct),
            (0, 1, 2))(q, k, v)

    (want, g_want), (got, g_got) = run("xla"), run("splash_interpret")
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, b in zip(g_got, g_want):
        assert onp.allclose(a, b, atol=1e-3)


def test_a_head_of_64_runs_the_kernel_padded_to_the_lanes():
    """A 64-wide head (grouped-query, 4 query heads over 2): the splash
    kernel over heads zero-padded to 128 lanes, interpreted on the CPU,
    gives the composition's attention and gradients, in the head's own
    width; the rule takes 64 and multiples of 128 and nothing else."""
    from mxnet_tpu.ops.banded_attention import (default_backend,
                                                splash_available)
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (1, 4, 384, 64))
    k = jax.random.normal(ks[1], (1, 2, 384, 64))
    v = jax.random.normal(ks[2], (1, 2, 384, 64))
    ct = jax.random.normal(ks[3], (1, 4, 384, 64))

    def run(backend):
        return jax.value_and_grad(lambda *a: jnp.sum(banded_attention(
            *a, block=128, backend=backend) * ct), (0, 1, 2))(q, k, v)

    (want, g_want), (got, g_got) = run("xla"), run("splash_interpret")
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        assert onp.allclose(a, b, atol=1e-3)
    assert splash_available(8192, 64) and splash_available(8192, 128)
    assert splash_available(8192, 256)
    assert not splash_available(8192, 32) and not splash_available(8192, 96)
    assert not splash_available(8200, 64)
    assert default_backend(8192, 64) == "xla"      # no TPU here
