"""``ops.ssd``: the chunked SSD scan against the recurrence token by
token, forward and every input's gradient, in float32, across several
chunk boundaries, a padded tail and a chunk of another size; its
bfloat16 operands; decays too strong for a product of exponents."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import ssd as ssd_mod
from mxnet_tpu.ops.ssd import ssd


def recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t b_t x_t^T``, ``y_t = c_t^T S_t``
    a token at a time; head h reads group h // (H / G)."""
    bsz, t, h, p = x.shape
    group = jnp.arange(h) // (h // b.shape[2])

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = b_t[:, group], c_t[:, group]           # (B, H, N)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state,
                                 precision="highest")

    _, y = jax.lax.scan(token, jnp.zeros((bsz, h, b.shape[3], p)),
                        [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)])
    return jnp.moveaxis(y, 0, 1)


def inputs(seed, t, bsz=2, h=4, p=8, groups=2, n=16, rate=8.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (bsz, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=jnp.log(rate)))
    b = jax.random.normal(ks[3], (bsz, t, groups, n))
    c = jax.random.normal(ks[4], (bsz, t, groups, n))
    return x, dt, a, b, c


@pytest.mark.parametrize("t,chunk", [(300, 128), (300, 64), (128, 128),
                                     (50, 64)])
def test_chunks_are_the_recurrence_forward_and_backward(t, chunk):
    args = inputs(t + chunk, t)
    got = ssd(*args, chunk=chunk)
    want = recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * scale
    w = jax.random.normal(jax.random.key(9), want.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    got_g = jax.grad(loss(lambda *a: ssd(*a, chunk=chunk)),
                     argnums=range(5))(*args)
    want_g = jax.grad(loss(recurrence), argnums=range(5))(*args)
    for name, g1, g2 in zip("x dt a b c".split(), got_g, want_g):
        top = float(jnp.max(jnp.abs(g2)))
        assert top > 0, name
        assert float(jnp.max(jnp.abs(g1 - g2))) < 5e-5 * top, name


def test_the_chunk_does_not_change_the_result():
    args = inputs(3, 256)
    small, whole = ssd(*args, chunk=32), ssd(*args, chunk=256)
    assert float(jnp.max(jnp.abs(small - whole))) \
        < 2e-5 * float(jnp.max(jnp.abs(whole)))


def test_a_group_is_read_by_its_own_heads():
    """Moving group 1's ``b`` changes heads 2 and 3 alone (4 heads over 2
    groups)."""
    x, dt, a, b, c = inputs(4, 96)
    base = ssd(x, dt, a, b, c)
    moved = ssd(x, dt, a, b.at[:, :, 1].add(1.0), c)
    changed = jnp.max(jnp.abs(moved - base), axis=(0, 1, 3))
    assert onp.all(onp.asarray(changed[:2]) == 0)
    assert onp.all(onp.asarray(changed[2:]) > 0)


def test_strong_decay_stays_finite():
    """A rate of -1000 a unit step: e^(-g_s) alone would overflow after
    a token; the mask comes before the exponent."""
    x, dt, a, b, c = inputs(5, 200, rate=2.0)
    a = a.at[0].set(-1000.0)
    y, grads = jax.value_and_grad(
        lambda x, dt, a: jnp.sum(ssd(x, dt, a, b, c) ** 2),
        argnums=(0, 1, 2))(x, dt, a)
    assert onp.isfinite(float(y))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    want = recurrence(x, dt, a, b, c)
    assert onp.allclose(ssd(x, dt, a, b, c), want, rtol=1e-4, atol=1e-4)


def test_bfloat16_operands_stay_near_float32():
    x, dt, a, b, c = inputs(6, 256)
    want = ssd(x, dt, a, b, c)
    got = ssd(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
              c.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    assert 1e-4 < float(err) < 3e-2


def test_shapes_are_checked_and_the_trace_counted():
    x, dt, a, b, c = inputs(7, 64)
    with pytest.raises(ValueError, match="ssd"):
        ssd(x, dt, a, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(
            3, axis=2))
    with pytest.raises(ValueError, match="ssd"):
        ssd(x, dt[..., :2], a, b, c)
    from mxnet_tpu.telemetry import metrics
    counter = metrics.counter("ssd_traced_total.chunked")
    before = counter.value()
    ssd_mod.count_traced()
    assert counter.value() == before + 1
