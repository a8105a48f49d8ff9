"""``ops.ssd``: the chunked SSD scan and the Pallas kernel pair
(interpreted, at a group's heads of whole lane widths: the kernel takes
no other) against the recurrence token by token, forward and every
input's gradient, in float32, across several chunk boundaries, a padded
tail and a chunk of another size; their bfloat16 operands; decays too
strong for a product of exponents; the backend picked by shape and
counted by the mixer, whose result and gradients are one on both."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
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


# the kernel's smallest shape: a group's heads one lane width, one state
KERNEL_SHAPE = dict(h=2, p=64, groups=1, n=128)


@pytest.mark.parametrize("t,chunk,backend", [
    (300, 128, "chunked"), (300, 64, "chunked"), (128, 128, "chunked"),
    (50, 64, "chunked"), (128, None, "kernel_interpret"),
    (300, None, "kernel_interpret")], ids=[
    "300-128", "300-64", "128-128", "50-64", "kernel-128", "kernel-300"])
def test_chunks_are_the_recurrence_forward_and_backward(t, chunk, backend):
    """The kernel at its own chunk: one chunk, and three with a padded
    tail."""
    shape = KERNEL_SHAPE if backend == "kernel_interpret" else {}
    args = inputs(t + (chunk or 0), t, **shape)
    got = ssd(*args, chunk=chunk, backend=backend)
    want = recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * scale
    w = jax.random.normal(jax.random.key(9), want.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    got_g = jax.grad(loss(lambda *a: ssd(*a, chunk=chunk, backend=backend)),
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


def strong_decay(backend, **shape):
    """A rate of -1000 a unit step: e^(-g_s) alone would overflow after
    a token; the mask comes before the exponent."""
    x, dt, a, b, c = inputs(5, 200, rate=2.0, **shape)
    a = a.at[0].set(-1000.0)
    y, grads = jax.value_and_grad(
        lambda x, dt, a: jnp.sum(ssd(x, dt, a, b, c, backend=backend) ** 2),
        argnums=(0, 1, 2))(x, dt, a)
    assert onp.isfinite(float(y))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    want = recurrence(x, dt, a, b, c)
    assert onp.allclose(ssd(x, dt, a, b, c, backend=backend), want,
                        rtol=1e-4, atol=1e-4)


def test_strong_decay_stays_finite():
    strong_decay("chunked")


def test_strong_decay_stays_finite_in_the_kernel():
    strong_decay("kernel_interpret", **KERNEL_SHAPE)


def bfloat16_band(backend, **shape):
    """x, B, C in bfloat16 (``dt`` and ``a`` float32, as the mixer gives
    them) against the float32 operands on the same path: the products
    round their operands to 8 bits, the decays and the state stay
    float32; gradients within 3% of each one's largest entry."""
    x, dt, a, b, c = inputs(6, 256, **shape)
    fn = functools.partial(ssd, backend=backend)
    want = fn(x, dt, a, b, c)
    low = [v.astype(jnp.bfloat16) for v in (x, b, c)]
    got = fn(low[0], dt, a, *low[1:])
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    assert 1e-4 < float(err) < 3e-2
    w = jax.random.normal(jax.random.key(10), want.shape)

    def grads(x, b, c):
        return jax.grad(lambda x, dt, a, b, c: jnp.sum(
            fn(x, dt, a, b, c).astype(jnp.float32) * w),
            argnums=range(5))(x, dt, a, b, c)

    for g, r in zip(grads(*low), grads(x, b, c)):
        top = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))) \
            < 0.03 * top


def test_bfloat16_operands_stay_near_float32():
    bfloat16_band("chunked")


def test_the_kernels_bfloat16_operands_stay_near_float32():
    bfloat16_band("kernel_interpret", **KERNEL_SHAPE)


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


def test_the_kernel_is_taken_by_shape_alone(monkeypatch):
    """A group's heads of whole lane widths and a state of whole lane
    widths take the kernel on a TPU, whatever the length; everything
    else, and everything off a TPU, the chunked scan; an unknown backend
    is refused."""
    assert ssd_mod.ssd_available(64, 64, 8, 128)      # the Nemotron cell
    assert ssd_mod.ssd_available(2, 64, 1, 128)
    assert not ssd_mod.ssd_available(64, 64, 64, 128)  # a group's 64 lanes
    assert not ssd_mod.ssd_available(4, 8, 2, 16)
    assert not ssd_mod.ssd_available(64, 64, 8, 64)
    assert ssd_mod.default_backend(64, 64, 8, 128) == "chunked"
    monkeypatch.setattr(ssd_mod.jax, "default_backend", lambda: "tpu")
    assert ssd_mod.default_backend(64, 64, 8, 128) == "kernel"
    assert ssd_mod.default_backend(4, 8, 2, 16) == "chunked"
    args = inputs(8, 16)
    with pytest.raises(ValueError, match="backend"):
        ssd(*args, backend="dense")


def test_the_mixer_counts_the_backend_it_takes(monkeypatch):
    """``NemotronHMamba`` asks the rule once a traced call and bumps
    ``ssd_traced_total.chunked`` or ``.kernel``; the two give one result
    and one gradient through the tape (the mixer rematerialised under
    ``jax.checkpoint``, the kernel's primal forward inside it)."""
    from mxnet_tpu.models import nemotron_h
    from mxnet_tpu.telemetry import metrics

    def counts():
        return [metrics.counter(f"ssd_traced_total.{label}").value()
                for label in ("chunked", "kernel")]

    net = nemotron_h.NemotronHMamba(32, num_heads=2, head_dim=64,
                                    state_size=128, groups=1,
                                    prefix="mamba_")
    net.initialize(mx.init.Normal(0.1))
    net.A_log.set_data(nd.array(onp.log([0.5, 4.0]).astype("float32")))
    rs = onp.random.RandomState(0)
    x = nd.array(rs.normal(size=(1, 150, 32)).astype("float32"))
    cotangent = nd.array(rs.normal(size=(1, 150, 32)).astype("float32"))
    x.attach_grad()

    def run():
        with autograd.record():
            loss = (net(x) * cotangent).sum()
        loss.backward()
        return loss.asscalar(), x.grad.asnumpy(), \
            net.in_proj_weight.grad().asnumpy(), net.A_log.grad().asnumpy()

    before = counts()
    chunked = run()
    assert counts() == [before[0] + 1, before[1]]
    monkeypatch.setattr(nemotron_h, "ssd_backend",
                        lambda *shape: "kernel_interpret")
    kernel = run()
    assert counts() == [before[0] + 1, before[1] + 1]
    for got, ref in zip(kernel, chunked):
        assert onp.allclose(got, ref, rtol=1e-4, atol=1e-5)
