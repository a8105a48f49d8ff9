"""Pallas kernel tests (interpret mode on CPU; real lowering on TPU)."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import flash_attention
from mxnet_tpu.parallel.ring_attention import local_attention
from mxnet_tpu.test_utils import assert_almost_equal


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    B, H, T, D = 2, 2, 256, 64
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)


def test_flash_attention_grad():
    B, H, T, D = 1, 2, 128, 64
    rng = onp.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, False, None, 128, 128,
                                       True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(local_attention(q_, k_, v_) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grad_tiled_kernel(causal):
    """The Pallas backward (dq/dk/dv kernels with per-block recompute)
    must match the dense vjp — multi-block so the K/Q sweeps and the
    causal block-skip actually execute."""
    B, H, T, D = 1, 2, 256, 64
    rng = onp.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))

    def f_flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal, None, 128, 128, True)

    def f_ref(q_, k_, v_):
        return local_attention(q_, k_, v_, causal=causal)

    _, vjp_f = jax.vjp(f_flash, q, k, v)
    _, vjp_r = jax.vjp(f_ref, q, k, v)
    for a, b, nm in zip(vjp_f(g), vjp_r(g), "qkv"):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_attention_grad_cross_length():
    """Tq != Tk (cross attention) through the tiled backward."""
    B, H, Tq, Tk, D = 1, 1, 128, 256, 64
    rng = onp.random.RandomState(4)
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32"))
    _, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, False, None, 128, 128,
                                        True), q, k, v)
    _, vjp_r = jax.vjp(lambda a, b, c: local_attention(a, b, c), q, k, v)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_padded_odd_seq(causal):
    """Non-tiling seq length now runs the KERNEL via tail padding + the
    kv_len mask (VERDICT r3 item 2) — exact match vs dense."""
    B, H, T, D = 1, 2, 100, 64
    rng = onp.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.4)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.4)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    ref = local_attention(q, k, v, causal=causal)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_padded_head_dim_96(causal):
    """BERT-shaped head_dim 96 pads the contraction to 128 (exact) and
    the padded grad columns slice off — fwd AND bwd vs dense."""
    B, H, T, D = 1, 2, 384, 96
    rng = onp.random.RandomState(6)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    out, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, causal, None, 128, 128,
                                        True), q, k, v)
    ref, vjp_r = jax.vjp(
        lambda a, b, c: local_attention(a, b, c, causal=causal), q, k, v)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_attention_padded_odd_seq_grad():
    """Gradients through the pad/mask path: odd Tq AND odd Tk AND odd
    head_dim at once (cross-length, non-causal)."""
    B, H, Tq, Tk, D = 1, 1, 100, 200, 80
    rng = onp.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32"))
    _, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, False, None, 128, 128,
                                        True), q, k, v)
    _, vjp_r = jax.vjp(lambda a, b, c: local_attention(a, b, c), q, k, v)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_attention_fallback_tiny():
    # sequences too short to amortize a 128 block still fall back
    q = jnp.ones((1, 1, 16, 32), jnp.float32)
    out = flash_attention(q, q, q, False, None, 128, 128, True)
    ref = local_attention(q, q, q)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("bq,bk", [(256, 128), (128, 256), (64, 128)])
def test_flash_attention_causal_mixed_blocks(bq, bk):
    """Regression: causal K-block count must cover the Q-block's LAST row
    (wrong when block_q > block_k)."""
    B, H, T, D = 1, 1, 256, 64
    rng = onp.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, bq, bk, True)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)


def test_flash_attention_available_predicate():
    from mxnet_tpu.ops.pallas_kernels import flash_attention_available
    # padded-kernel shapes are now available...
    assert flash_attention_available(100, 100, 64)
    assert flash_attention_available(128, 128, 64)
    assert flash_attention_available(128, 100, 64)
    assert flash_attention_available(384, 384, 96)
    # 128-multiple big heads tile exactly; other big heads fall back
    assert flash_attention_available(128, 128, 512)
    assert not flash_attention_available(128, 128, 300)
    # tiny sequences still fall back
    assert not flash_attention_available(16, 16, 64)
