"""The gated delta rule (``ops/kda.py``): the chunked scan against the
recurrence written out token by token (float64 loops for the outputs, a
float32 ``lax.scan`` under plain autodiff for all five gradients); sequences that are whole chunks and not, one chunk and many;
``beta`` = 0 and a decay of 1 as edge cases; the strongest decay the
safe gate allows; bfloat16 operands within a stated band; the registered
operator through the tape."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, nd
from mxnet_tpu.ops import kda as K


def plain(q, k, v, log_a, beta):
    """``S_t = (I - beta k k^T) diag(a) S_{t-1} + beta k v^T``,
    ``o_t = S_t^T q_t / sqrt(d)``: every token written out, float64."""
    q, k, v, log_a, beta = (onp.asarray(a, onp.float64)
                            for a in (q, k, v, log_a, beta))
    b, t, h, d = q.shape
    out = onp.zeros(v.shape)
    for i in range(b):
        for j in range(h):
            state = onp.zeros((d, v.shape[-1]))
            for s in range(t):
                kk = k[i, s, j]
                state = onp.exp(log_a[i, s, j])[:, None] * state
                state = state - beta[i, s, j] * onp.outer(kk, kk @ state) \
                    + beta[i, s, j] * onp.outer(kk, v[i, s, j])
                out[i, s, j] = state.T @ q[i, s, j] / onp.sqrt(d)
    return out


def recurrence(q, k, v, log_a, beta):
    """``plain`` as a ``lax.scan`` over the tokens in float32, for plain
    autodiff to differentiate."""
    f32, exact = jnp.float32, jax.lax.Precision.HIGHEST
    b, _, h, d = q.shape

    def step(state, x):
        q_t, k_t, v_t, log_a_t, beta_t = x
        state = state * jnp.exp(log_a_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=exact)
        state = state + k_t[..., None] \
            * (beta_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t / onp.sqrt(d), state,
                                 precision=exact)

    xs = [jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, log_a, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def operands(t, b=2, h=2, d=8, dv=8, seed=0, gate=1.0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, h, d)))
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    log_a = -5.0 * jax.nn.sigmoid(gate * jax.random.normal(ks[3],
                                                           (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    do = jax.random.normal(ks[5], (b, t, h, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), log_a,
            beta), do


def grads(fn, args, do):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * do),
                    argnums=(0, 1, 2, 3, 4))(*args)


def test_a_chunk_is_whole_sub_blocks():
    assert K.KDA_CHUNK % K.KDA_SUB == 0
    args, _ = operands(40)
    with pytest.raises(ValueError, match="sub-blocks"):
        K._kda(*args, chunk=K.KDA_SUB + 8)


@pytest.mark.parametrize("t", [1, 16, 17, 64, 100, 192])
def test_forward_is_the_recurrence(t):
    """One token and a sub-block (all but padding), one chunk with and
    without padding, many chunks; values 4 wide where keys are 8."""
    args, _ = operands(t, dv=4, seed=t)
    assert onp.allclose(K.kda(*args), plain(*args), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,gate", [(64, 1.0), (100, 1.0), (192, 3.0),
                                    (16, 1.0)])
def test_all_five_gradients_against_the_recurrence(t, gate):
    """The chunked scan's gradients (its body checkpointed) against
    plain autodiff of the token-by-token scan, float32, 1e-5 of each
    gradient's largest entry; ``gate`` 3 pushes many channels to the
    safe gate's strongest decay."""
    args, do = operands(t, seed=t + 1, gate=gate)
    got = grads(K.kda, args, do)
    ref = grads(recurrence, args, do)
    assert onp.allclose(K.kda(*args), recurrence(*args), atol=1e-5)
    for g, r in zip(got, ref):
        assert float(jnp.max(jnp.abs(g - r))) \
            <= 1e-5 * max(1.0, float(jnp.max(jnp.abs(r))))


def test_a_chunk_size_is_a_constant_of_the_operator_not_of_the_result():
    args, do = operands(160, seed=5)
    base = K._kda(*args, chunk=64)
    for chunk in (32, 128):
        assert onp.allclose(K._kda(*args, chunk=chunk), base, atol=1e-5)


def test_beta_zero_writes_nothing_and_decay_one_forgets_nothing():
    (q, k, v, log_a, beta), _ = operands(80, seed=7)
    assert onp.array_equal(K.kda(q, k, v, log_a, jnp.zeros_like(beta)),
                           jnp.zeros_like(v))
    # decay 1 and orthonormal keys written once each with beta 1: the
    # state is sum k_s v_s^T, a query that is key s reads v_s / sqrt(d)
    d = 8
    eye = jnp.tile(jnp.eye(d)[None, :, None, :], (1, 10, 1, 1))  # T = 80
    v1 = v[:1, :, :1]
    out = K.kda(eye, eye, v1, jnp.zeros((1, 80, 1, d)), jnp.ones((1, 80, 1)))
    # token t rewrites key t % 8: the delta rule replaces what was there
    assert onp.allclose(out, v1 / onp.sqrt(d), atol=1e-5)
    strongest = jnp.full_like(log_a, -5.0)
    assert onp.allclose(K.kda(q, k, v, strongest, beta),
                        plain(q, k, v, strongest, beta), atol=1e-5)


def test_a_sequence_starts_from_zero_and_sees_no_future():
    args, _ = operands(96, seed=9)
    both = K.kda(*args)
    alone = K.kda(*(a[1:] for a in args))
    assert onp.allclose(both[1:], alone, atol=1e-6)
    cut = [a.at[:, 70:].set(0.0) for a in args]
    assert onp.allclose(K.kda(*cut)[:, :70], both[:, :70], atol=1e-6)


def test_bfloat16_operands_stay_in_a_band_of_the_float32_result():
    """q, k, v in bfloat16 (decay and beta float32, as a model gives
    them): the products round their operands to 8 bits, the state and
    the solve stay float32; within 2% of the result's largest entry."""
    args, do = operands(192, seed=11, dtype=jnp.bfloat16)
    exact = plain(*args)
    got = K.kda(*args)
    assert got.dtype == jnp.bfloat16
    band = 0.02 * onp.abs(exact).max()
    assert onp.abs(onp.asarray(got, onp.float64) - exact).max() <= band
    ref = grads(recurrence, args, do)
    for g, r in zip(grads(K.kda, args, do), ref):
        assert g.dtype == r.dtype
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - r.astype(jnp.float32)))) \
            <= 0.03 * max(1.0, float(jnp.max(jnp.abs(r))))


def test_shapes_are_checked():
    (q, k, v, log_a, beta), _ = operands(8)
    with pytest.raises(ValueError, match="kda"):
        K.kda(q, k[:, :4], v, log_a, beta)
    with pytest.raises(ValueError, match="kda"):
        K.kda(q, k, v, log_a, beta[..., None])


def test_the_registered_operator_through_the_tape():
    args, do = operands(40, seed=13)
    arrays = [nd.array(onp.asarray(a)) for a in args]
    for a in arrays:
        a.attach_grad()
    with autograd.record():
        out = nd._kda(*arrays)
        loss = (out * nd.array(onp.asarray(do))).sum()
    loss.backward()
    ref = grads(K.kda, args, do)
    assert onp.allclose(out.asnumpy(), K.kda(*args), atol=1e-6)
    for a, r in zip(arrays, ref):
        assert onp.allclose(a.grad.asnumpy(), r, atol=1e-5)
