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
    width; the rule takes 64, 192 and multiples of 128 and nothing
    else."""
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


def test_scores_of_192_over_values_of_128_run_padded_to_the_lanes(
        monkeypatch):
    """Latent attention's products: q and k 192 wide, v 128 wide, one
    key/value head a query head. The composition and the splash kernel
    (q and k zero-padded to 256 lanes, the values' own width, the scale
    1 / sqrt(192)), interpreted on the CPU, give dense masked attention
    and its gradients, 128 wide; the rule takes 192 over 128."""
    from mxnet_tpu.ops import banded_attention as ba
    ks = jax.random.split(jax.random.key(192), 4)
    q = jax.random.normal(ks[0], (1, 2, 256, 192))
    k = jax.random.normal(ks[1], (1, 2, 256, 192))
    v = jax.random.normal(ks[2], (1, 2, 256, 128))
    ct = jax.random.normal(ks[3], (1, 2, 256, 128))

    def run(attn):
        return jax.value_and_grad(lambda *a: jnp.sum(attn(*a) * ct),
                                  (0, 1, 2))(q, k, v)

    want, g_want = run(lambda *a: _dense_attention(*a, None))
    for backend in ("xla", "splash_interpret"):
        got, g_got = run(lambda *a: banded_attention(*a, block=128,
                                                     backend=backend))
        assert banded_attention(q, k, v, backend=backend).shape == v.shape
        assert float(got) == pytest.approx(float(want), rel=1e-4)
        for a, b in zip(g_got, g_want):
            assert a.shape == b.shape
            assert onp.allclose(a, b, atol=1e-3)
    assert ba.splash_available(4096, 192, 128)
    assert ba.splash_available(4096, 192)
    assert not ba.splash_available(4096, 160, 128)
    assert not ba.splash_available(4096, 192, 96)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ba.default_backend(4096, 192, dv=128) == "splash"
    assert ba.default_backend(4096, 160, dv=128) == "xla"
    # the band kernel takes no head whose values differ from its keys
    assert ba.default_backend(8192, 128, 512, 8) == "band"
    assert ba.default_backend(8192, 128, 512, 8, dv=64) == "splash"


# ---------------------------------------------------------------------------
# the band kernel (token-major heads), interpreted
# ---------------------------------------------------------------------------

def _token_major(x):
    return x.transpose(0, 2, 1, 3)


def _band_operands(t, group, dtype, kv_heads=2, d=128):
    ks = jax.random.split(jax.random.key(t + group), 4)
    shapes = [(1, t, kv_heads * group, d), (1, t, kv_heads, d),
              (1, t, kv_heads, d), (1, t, kv_heads * group, d)]
    return [jax.random.normal(key, s, jnp.float32).astype(dtype)
            for key, s in zip(ks, shapes)]


@pytest.mark.parametrize("t,window,group,block,dtype", [
    (1024, 512, 2, None, jnp.float32),        # a window of one block
    (1024, 256, 1, None, jnp.float32),        # of two sub-blocks
    (512, 256, 2, None, jnp.float32),         # T of one block only
    (512, 128, 4, None, jnp.float32),         # a window of one sub-block
    (768, 256, 2, 256, jnp.float32),          # three blocks of the window
    (2048, 1024, 1, None, jnp.float32),       # a window over BAND_BLOCK
    (1280, 640, 1, None, jnp.float32),        # five sub-blocks a block
    (1024, 512, 2, None, jnp.bfloat16),
    (1024, 512, 8, 1024, jnp.bfloat16),       # the cell's group and dtype
], ids=["w-block", "w-2sub", "one-block", "w-sub", "blocks-256", "w-1024",
        "w-640", "bf16", "bf16-group-8"])
def test_the_band_kernel_equals_masked_dense(t, window, group, block, dtype):
    """The band kernel interpreted on the CPU, result and gradients of
    q, k and v, against dense masked attention in float32: the first
    query block (nothing before position 0) is in every case."""
    from mxnet_tpu.ops.banded_attention import banded_attention_token_major
    q, k, v, ct = _band_operands(t, group, dtype)
    f32 = [_token_major(a.astype(jnp.float32)) for a in (q, k, v, ct)]

    def band(q, k, v):
        return banded_attention_token_major(
            q, k, v, window=window, block=block, backend="band_interpret")

    got, vjp = jax.vjp(band, q, k, v)
    want, vjp_dense = jax.vjp(
        lambda *a: _dense_attention(*a, window), *f32[:3])
    assert got.dtype == dtype and got.shape == q.shape
    tol = 2e-5 if dtype == jnp.float32 else 0.05
    for a, b in zip((got,) + vjp(ct), (want,) + vjp_dense(f32[3])):
        assert a.dtype == dtype
        gap = jnp.max(jnp.abs(_token_major(a.astype(jnp.float32)) - b))
        assert float(gap) <= tol * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("t,d,window,takes", [
    (8192, 128, 512, True),       # the Laguna cell's window layers
    (8192, 256, 512, True),
    (2048, 128, 1024, True),
    (8192, 128, None, False),     # no window
    (512, 128, 512, False),       # a window that reaches the whole sequence
    (8192, 64, 512, False),       # a head narrower than the lanes
    (8192, 128, 500, False),      # a window of no whole sub-blocks
    (8192, 128, 2048, False),     # a window past BAND_MAX_WINDOW
    (8192 + 256, 128, 512, False),    # T of no whole query blocks
    (2560, 128, 1024, False),     # nor of whole windows, where longer
], ids=["cell", "d-256", "w-1024", "no-window", "window-is-t", "d-64",
        "window-500", "window-2048", "odd-t", "odd-t-w-1024"])
def test_band_available_is_a_rule_on_the_shapes(monkeypatch, t, d, window,
                                                takes):
    from mxnet_tpu.ops import banded_attention as ba
    assert ba.band_available(t, d, window) is takes
    # the cell's 8 query heads a key/value head fit; 64 at a window of
    # 512 would not
    assert ba.band_available(t, d, window, group=8) is takes
    assert not ba.band_available(t, d, window, group=64 if window != 128
                                 else 128)
    assert ba.default_backend(t, d, window) == "xla"       # no TPU here
    assert ba.default_backend(t, d) == "xla"
    monkeypatch.setattr(ba.jax, "default_backend", lambda: "tpu")
    assert ba.default_backend(t, d, window) == (
        "band" if takes else "splash" if d != 96 and t % 128 == 0
        else "xla")
    # a caller that does not say its window never gets the band kernel
    assert ba.default_backend(t, d) != "band"


@pytest.mark.parametrize("t,d,window", [
    (40, 8, 16), (48, 16, None), (256, 128, 128), (384, 128, 100)],
    ids=["band", "causal", "window-is-t", "window-100"])
def test_token_major_equals_banded_attention_transposed(t, d, window):
    """Off the chip, and for every shape the band kernel does not take,
    the token-major entry is ``banded_attention`` between two
    transposes: the same numbers, result and gradients."""
    from mxnet_tpu.ops.banded_attention import (band_available,
                                                banded_attention_token_major)
    q, k, v, ct = _band_operands(t, 3, jnp.float32, d=d)
    assert not band_available(t, d, window)

    def both(fn, *ops):
        out, vjp = jax.vjp(fn, *ops[:3])
        return (out,) + vjp(ops[3])

    got = both(lambda *a: banded_attention_token_major(*a, window=window),
               q, k, v, ct)
    want = both(lambda *a: banded_attention(*a, window=window),
                *[_token_major(a) for a in (q, k, v, ct)])
    for a, b in zip(got, want):
        assert onp.array_equal(a, _token_major(b))
    with pytest.raises(ValueError, match="band kernel takes no window"):
        banded_attention_token_major(q, k, v, window=window, backend="band")


def test_banded_attention_takes_the_band_backend_on_heads_major_operands():
    q, k, v, ct = [_token_major(a) for a in
                   _band_operands(512, 2, jnp.float32)]
    got = banded_attention(q, k, v, window=256, backend="band_interpret")
    assert onp.allclose(got, _dense_attention(q, k, v, 256), atol=2e-5)
