"""The gated short convolution (``ops/short_conv.py``) against three
shifted adds written out in float32: the result, the gradient of both
operands, a batch of two (one sequence's end must not leak into the
next one's start), bfloat16 in and out, and the registered operator
through the tape."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, nd
from mxnet_tpu.ops.short_conv import short_conv


def plain(u, w):
    """``C * causal_conv3(B * X)``, every position written out."""
    u, w = onp.asarray(u, onp.float64), onp.asarray(w, onp.float64)
    b, c, x = onp.split(u, 3, axis=-1)
    z = b * x
    out = onp.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(3):
            s = t - 2 + j
            if s >= 0:
                out[:, t] += w[:, j] * z[:, s]
    return c * out


def operands(batch, t, c, dtype, seed=0):
    ku, kw, kg = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ku, (batch, t, 3 * c), jnp.float32)
            .astype(dtype),
            jax.random.normal(kw, (c, 3), jnp.float32).astype(dtype),
            jax.random.normal(kg, (batch, t, c), jnp.float32).astype(dtype))


@pytest.mark.parametrize("batch,t,c", [(1, 7, 4), (2, 16, 8), (2, 2, 4),
                                       (3, 1, 4)])
def test_forward_is_three_shifted_adds(batch, t, c):
    u, w, _ = operands(batch, t, c, jnp.float32)
    assert onp.allclose(short_conv(u, w), plain(u, w), rtol=1e-5,
                        atol=1e-6)


def test_a_sequence_starts_from_zeros_not_from_its_neighbour():
    u, w, _ = operands(2, 8, 4, jnp.float32, seed=3)
    both = short_conv(u, w)
    alone = short_conv(u[1:], w)
    assert onp.array_equal(both[1:], alone)
    # and it is causal: a later position changes nothing before it
    later = u.at[:, 5:].set(0.0)
    assert onp.array_equal(short_conv(later, w)[:, :5], both[:, :5])


@pytest.mark.parametrize("batch,t,c", [(2, 16, 8), (1, 2, 4)])
def test_gradients_against_the_plain_composition(batch, t, c):
    u, w, g = operands(batch, t, c, jnp.float32, seed=1)

    def composed(u, w):
        b, cc, x = jnp.split(u, 3, axis=-1)
        z = jnp.pad(b * x, ((0, 0), (2, 0), (0, 0)))
        return cc * sum(w[:, j] * z[:, j:j + t] for j in range(3))

    with jax.default_matmul_precision("highest"):
        want = jax.vjp(composed, u, w)[1](g)
        got = jax.vjp(short_conv, u, w)[1](g)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert onp.allclose(a, b, rtol=1e-5, atol=1e-5)


def test_bfloat16_in_and_out_float32_inside():
    u, w, g = operands(2, 32, 8, jnp.bfloat16, seed=2)
    y, pull = jax.vjp(short_conv, u, w)
    du, dw = pull(g)
    assert y.dtype == du.dtype == dw.dtype == jnp.bfloat16
    want = plain(u.astype(jnp.float32), w.astype(jnp.float32))
    # one rounding of the result, none between the taps
    assert onp.allclose(onp.asarray(y, onp.float64), want, rtol=2 ** -7,
                        atol=1e-3)
    f32 = [a.astype(jnp.float32) for a in (u, w, g)]
    du32, dw32 = jax.vjp(short_conv, *f32[:2])[1](f32[2])
    assert onp.allclose(du.astype(jnp.float32), du32, rtol=2 ** -6,
                        atol=2e-2)
    assert onp.allclose(dw.astype(jnp.float32), dw32, rtol=2 ** -6,
                        atol=5e-2)


def test_the_program_holds_no_convolution_and_no_window_array():
    u, w, g = operands(1, 64, 128, jnp.bfloat16)
    text = jax.jit(lambda u, w, g: jax.vjp(short_conv, u, w)[1](g)) \
        .lower(u, w, g).compile().as_text()
    assert "convolution(" not in text
    assert "[1,64,3,128]" not in text and "[1,3,64,128]" not in text


def test_the_registered_operator_records_on_the_tape():
    u, w, g = operands(2, 8, 4, jnp.float32, seed=5)
    un, wn = nd.array(onp.asarray(u)), nd.array(onp.asarray(w))
    un.attach_grad(), wn.attach_grad()
    with autograd.record():
        y = nd._short_conv(un, wn)
    y.backward(nd.array(onp.asarray(g)))
    du, dw = jax.vjp(short_conv, u, w)[1](g)
    assert onp.allclose(y.asnumpy(), plain(u, w), rtol=1e-5, atol=1e-6)
    assert onp.allclose(un.grad.asnumpy(), du, rtol=1e-6, atol=1e-6)
    assert onp.allclose(wn.grad.asnumpy(), dw, rtol=1e-6, atol=1e-6)
    with pytest.raises(Exception, match="short_conv"):
        nd._short_conv(nd.ones((2, 8, 10)), wn)
