"""``ops.sparse_attention`` against a direct evaluation over the whole
(T, T) at tiny sizes: the output, the gradients the loss gives q, k and
v, and the gradients the indexer's KL loss gives the indexer; plain
causal grouped-query attention wherever T <= topk; the selection
``lax.top_k``'s, ties and all."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import sparse_attention as sa

B, T, H, G, D, HI, DI = 2, 64, 4, 2, 16, 4, 8


def _inputs(seed=0, t=T):
    keys = jax.random.split(jax.random.key(seed), 7)
    n = jax.random.normal
    return (n(keys[0], (B, t, H, D)), n(keys[1], (B, t, G, D)),
            n(keys[2], (B, t, G, D)), n(keys[3], (B, t, HI, DI)),
            n(keys[4], (B, t, DI)), n(keys[5], (B, t, HI)) / math.sqrt(HI))


def _op(topk, block):
    return jax.jit(functools.partial(sa.sparse_attention, topk=topk,
                                     block=block))


def _selection(qi, ki, w, topk):
    t = qi.shape[1]
    scores = jnp.einsum("btjd,bsd->btjs", qi, ki, precision="highest")
    scores = jnp.sum(jax.nn.relu(scores) * w[..., None], axis=2) \
        / math.sqrt(qi.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    masked = jnp.where(causal, scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(topk, t))
    chosen = jnp.sum(jax.nn.one_hot(idx, t), axis=-2) > 0
    return scores, chosen & causal


def _direct(q, k, v, scores, mask):
    """``(o, probabilities (B, H, T, T))`` over the whole (T, T)."""
    kh = jnp.repeat(k, H // G, axis=2)
    vh = jnp.repeat(v, H // G, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kh, precision="highest") \
        / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, vh, precision="highest"), p


def _kl(qi, ki, w, p, topk):
    """``L_I`` summed over the queries, ``p`` held."""
    scores, mask = _selection(qi, ki, w, topk)
    target = jax.lax.stop_gradient(jnp.mean(p, axis=1))
    logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.sum(jnp.where(mask & (target > 0),
                             target * (jnp.log(target) - logq), 0.0))


@pytest.mark.parametrize("block", [16, 64])
def test_the_op_is_the_direct_evaluation_forward_and_backward(block):
    topk = 12
    args = _inputs()
    q, k, v, qi, ki, w = args
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(_op(topk, block), *args)
        scores, mask = _selection(qi, ki, w, topk)
        want, p = _direct(q, k, v, scores, mask)
        assert int(mask.sum(-1).max()) == topk
        assert onp.allclose(o, want, atol=1e-5)
        g = jax.random.normal(jax.random.key(9), o.shape)
        got = vjp(g)
        main = jax.grad(lambda q, k, v: jnp.sum(
            _direct(q, k, v, scores, mask)[0] * g), argnums=(0, 1, 2))(
                q, k, v)
        index = jax.grad(lambda qi, ki, w: _kl(qi, ki, w, p, topk),
                         argnums=(0, 1, 2))(qi, ki, w)
    for a, b in zip(got, main + index):
        assert onp.allclose(a, b, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(got[3]).max()) > 1e-3


def test_the_loss_reaches_the_main_path_and_the_kl_loss_the_indexer():
    """The indexer's gradients are the KL loss's whatever the output's
    cotangent, and a cotangent of zero gives q, k and v nothing: the
    LM loss never reaches the indexer, and ``L_I`` reaches nothing
    else."""
    args = _inputs(seed=1)
    _, vjp = jax.vjp(_op(12, 16), *args)
    shape = args[0].shape
    zero = vjp(jnp.zeros(shape))
    some = vjp(jax.random.normal(jax.random.key(2), shape))
    for a, b in zip(zero[3:], some[3:]):
        assert onp.array_equal(a, b)
        assert float(jnp.abs(a).max()) > 0
    for a in zero[:3]:
        assert not onp.any(a)


def test_at_most_topk_keys_it_is_causal_gqa():
    args = _inputs(seed=3, t=32)
    q, k, v = args[:3]
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((32, 32), bool)),
                              (B, 32, 32))
    with jax.default_matmul_precision("highest"):
        want, _ = _direct(q, k, v, None, causal)
        for topk in (32, 40):
            got = _op(topk, 16)(*args)
            assert onp.allclose(got, want, atol=1e-5)
        assert not onp.allclose(_op(31, 16)(*args), want, atol=1e-3)


def test_the_selection_is_lax_top_k_ties_and_all():
    """Scores drawn from a few integers tie everywhere: the mask keeps
    what ``lax.top_k`` keeps (the lower position first among equals),
    for blocks of queries that start anywhere."""
    scores = jax.random.randint(jax.random.key(4), (2, 16, 64), 0, 5
                                ).astype(jnp.float32)
    for start in (0, 8, 48):
        mask = sa.select(scores, 10, start)
        qpos = start + jnp.arange(16)[:, None]
        causal = jnp.arange(64)[None, :] <= qpos
        _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), 10)
        want = (jnp.sum(jax.nn.one_hot(idx, 64), axis=-2) > 0) & causal
        assert onp.array_equal(mask, want)
        assert onp.array_equal(mask.sum(-1),
                               jnp.minimum(qpos[:, 0] + 1, 10)[None]
                               .repeat(2, 0))
