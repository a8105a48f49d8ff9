"""The delta-rule mixer's operands (``ops/kda_operands.py``): the XLA
composition and the Pallas pair (interpreted, at heads of 128: the
kernel takes whole lane widths) as cases of the same tests, against the
composition the mixer ran before the pair, copied here as the
reference: values, and gradients with respect to the four projections,
the three filters, ``A_log`` and ``dt_bias``; sequences shorter than the
filter, not whole token blocks, and across a block's boundary (the halos
both ways); under ``jax.checkpoint``, as the mixer calls it; bfloat16
projections as the cell has them; the backend picked by shape and
counted by the mixer."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.ops import kda_operands as M
from mxnet_tpu.ops.short_conv import _filtered

pytestmark = pytest.mark.usefixtures("layer_gauges_cleaned")

BACKENDS = ("xla", "kernel_interpret")
LOWER_BOUND = -5.0


def reference(q, k, v, f, q_filter, k_filter, v_filter, a_log, dt_bias,
              heads):
    """What ``models/ling.py`` computed before the pair: heads
    (B, T, H, D), float32 inside, plain autodiff."""
    f32 = jnp.float32

    def heads_of(y):
        b, t, c = y.shape
        return y.reshape(b, t, heads, c // heads)

    def mixed(x, w):
        return heads_of(jax.nn.silu(_filtered(x.astype(f32),
                                              w.astype(f32))))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                         keepdims=True) + 1e-6)

    rate = jnp.exp(a_log.astype(f32))[:, None]
    log_a = LOWER_BOUND * jax.nn.sigmoid(
        rate * heads_of(f.astype(f32) + dt_bias.astype(f32)))
    out = (unit(mixed(q, q_filter)).astype(q.dtype),
           unit(mixed(k, k_filter)).astype(k.dtype),
           mixed(v, v_filter).astype(v.dtype), log_a)
    return tuple(a.reshape(q.shape) for a in out)


def operands(t, heads=2, d=128, b=1, seed=0, dtype=jnp.float32):
    """The projections, filters and decay's leaves, and cotangents of
    the four results (the decay's in float32)."""
    c = heads * d
    ks = jax.random.split(jax.random.key(seed), 13)
    x = [jax.random.normal(key, (b, t, c)).astype(dtype) for key in ks[:4]]
    x += [(0.5 * jax.random.normal(key, (c, 4))).astype(dtype)
          for key in ks[4:7]]
    x += [0.5 * jax.random.normal(ks[7], (heads,)),
          -4.0 + 0.5 * jax.random.normal(ks[8], (c,))]
    cotangents = tuple(
        [jax.random.normal(key, (b, t, c)).astype(dtype)
         for key in ks[9:12]] + [jax.random.normal(ks[12], (b, t, c))])
    return x, cotangents


def path(backend, heads=2):
    return lambda *a: M.kda_operands(*a, heads=heads,
                                     lower_bound=LOWER_BOUND,
                                     backend=backend)


@jax.jit
def _values_and_grads(fn, args, cotangents):
    out, vjp = jax.vjp(fn, *args)
    return out, vjp(cotangents)


def values_and_grads(fn, args, cotangents):
    return _values_and_grads(jax.tree_util.Partial(fn), args, cotangents)


def gap(a, b):
    a, b = (onp.asarray(x, onp.float64) for x in (a, b))
    return onp.abs(a - b).max() / max(onp.abs(b).max(), 1e-30)


@pytest.mark.parametrize("t", [3, 40, 300, 512],
                         ids=["shorter-than-the-filter", "one-block",
                              "not-whole-blocks", "two-whole-blocks"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_values_and_all_nine_gradients_are_the_references(backend, t):
    """Float32 projections: the four results and the gradients of the
    four projections, three filters, ``A_log`` and ``dt_bias`` within
    float32's rounding of the composition's. At 300 and 512 tokens the
    kernel runs two token blocks (``MIX_ROWS`` 256), so a filter reaches
    across a block's start forward and its transpose across its end
    backward."""
    args, cotangents = operands(t, seed=t)
    ref = values_and_grads(lambda *a: reference(*a, heads=2), args,
                           cotangents)
    got = values_and_grads(path(backend), args, cotangents)
    for a, b in zip(got[0], ref[0]):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert gap(a, b) <= 1e-6
    for a, b in zip(got[1], ref[1]):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert gap(a, b) <= 2e-5


@pytest.mark.parametrize("backend", BACKENDS)
def test_under_checkpoint_as_the_mixer_calls_it(backend):
    """``jax.checkpoint`` round the call, a loss over all four results:
    the same gradients as without it, and as the reference's."""
    args, cotangents = operands(272, seed=1)

    def loss(fn):
        return lambda *a: sum(jnp.sum(o.astype(jnp.float32) * g)
                              for o, g in zip(fn(*a), cotangents))

    def grads(fn):
        return jax.jit(jax.grad(loss(fn), tuple(range(9))))(*args)

    fn = path(backend)
    plain, kept = grads(fn), grads(jax.checkpoint(fn))
    ref = grads(lambda *a: reference(*a, heads=2))
    for a, b, r in zip(kept, plain, ref):
        assert gap(a, b) <= 1e-6
        assert gap(a, r) <= 2e-5


def test_bfloat16_projections_as_the_cell_has_them():
    """bf16 projections and filters: the kernel's results are the
    composition's to the bit (the same float32 arithmetic, rounded
    once); its gradients, rounded once to bf16 from float32 sums taken
    in another order, within a bf16 step of each one's largest entry."""
    args, cotangents = operands(300, seed=2, dtype=jnp.bfloat16)
    ref = values_and_grads(path("xla"), args, cotangents)
    got = values_and_grads(path("kernel_interpret"), args, cotangents)
    for a, b in zip(got[0], ref[0]):
        assert a.dtype == b.dtype
        assert onp.array_equal(onp.asarray(a, onp.float32),
                               onp.asarray(b, onp.float32))
    for a, b in zip(got[1], ref[1]):
        assert a.dtype == b.dtype
        assert gap(a, b) <= 2 ** -7


def test_the_kernel_is_taken_by_shape_alone(monkeypatch):
    """Heads of whole lane widths and whole token blocks on a TPU take
    the pair; everything else, and everything off a TPU, the
    composition; an unknown backend is refused."""
    rows = M.MIX_ROWS
    assert M.default_backend(16 * rows, 128) == "xla"
    args, _ = operands(8)
    by_rule = M.kda_operands(*args, heads=2, lower_bound=LOWER_BOUND)
    for a, b in zip(by_rule, path("xla")(*args)):
        assert onp.array_equal(a, b)
    monkeypatch.setattr(M.jax, "default_backend", lambda: "tpu")
    assert M.default_backend(16 * rows, 128) == "kernel"
    assert M.default_backend(rows, 256) == "kernel"
    assert M.default_backend(16 * rows, 64) == "xla"
    assert M.default_backend(16 * rows + 8, 128) == "xla"
    with pytest.raises(ValueError, match="backend"):
        path("dense")(*args)


def test_the_mixer_counts_both_backends_it_takes(monkeypatch):
    """``LingKDA`` asks both rules once a traced mixer and bumps
    ``kda_traced_total.<backend>`` and ``kda_mix_traced_total.<backend>``
    once each; the composition and the interpreted pair give one result
    and one gradient through the tape (the mixer rematerialised, the
    pair's forward inside it)."""
    from mxnet_tpu.models import ling
    from mxnet_tpu.telemetry import metrics

    def counts():
        return [metrics.counter(name).value() for name in (
            "kda_traced_total.chunked", "kda_mix_traced_total.xla",
            "kda_mix_traced_total.kernel")]

    net = ling.LingKDA(64, num_heads=2, head_dim=128, prefix="kda_")
    net.initialize(mx.init.Normal(0.1))
    rs = onp.random.RandomState(0)
    x = nd.array(rs.normal(size=(1, 40, 64)).astype("float32"))
    cotangent = nd.array(rs.normal(size=(1, 40, 64)).astype("float32"))
    x.attach_grad()

    def run():
        with autograd.record():
            loss = (net(x) * cotangent).sum()
        loss.backward()
        return loss.asscalar(), x.grad.asnumpy(), \
            net.q_filter.grad().asnumpy(), net.A_log.grad().asnumpy()

    before = counts()
    composed = run()
    assert counts() == [before[0] + 1, before[1] + 1, before[2]]
    monkeypatch.setattr(ling, "mix_backend",
                        lambda t, d: "kernel_interpret")
    kernel = run()
    assert counts() == [before[0] + 2, before[1] + 1, before[2] + 1]
    for got, ref in zip(kernel, composed):
        assert onp.allclose(got, ref, rtol=1e-4, atol=1e-6)
