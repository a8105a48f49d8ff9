"""Grouped-query attention over the keys a learned indexer selects
(DeepSeek-V3.2's lightning indexer and its sparse attention), with the
indexer's own training signal in its backward pass.

``sparse_attention(q, k, v, qi, ki, w, topk)``: ``q`` (B, T, H, D),
``k`` and ``v`` (B, T, G, D), query head ``h`` reading key/value head
``h // (H / G)``; the indexer's queries ``qi`` (B, T, Hi, Di), its one
key head ``ki`` (B, T, Di) and its head weights ``w`` (B, T, Hi). Query
``t``'s index scores over the keys ``s <= t`` are

    I[t, s] = Di^-1/2 sum_j w[t, j] relu(qi[t, j] . ki[s])

and ``S_t`` is the ``min(t + 1, topk)`` keys of the largest scores: an
exact top-k, ties broken by the lower position (``select``). Every head
attends to ``S_t`` alone,

    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)] / sqrt(D))
              v[s, g(h)]

The selection is not differentiable, so the loss reaches ``q``, ``k``
and ``v`` alone. The backward pass gives the indexer the gradient of its
KL loss instead, ``L_I = sum_t KL(p_t || softmax(I[t, S_t]))`` with
``p_t`` the attention's probabilities over ``S_t`` summed over the
heads and renormalised (held fixed): ``dI[t, S_t] = softmax(I[t, S_t])
- p_t``, whatever the output's cotangent, taken on through the scores
to ``qi``, ``ki`` and ``w``. So a caller that feeds the indexer from a
``stop_gradient`` of the layer's input trains it by ``L_I`` alone, and
``L_I`` moves nothing else, without a second loss in the step.

Blocks of ``QUERY_BLOCK`` queries; a (T, T) array never exists. The
blocks fall into at most ``KEY_GROUPS`` runs, each a ``lax.scan`` over
its blocks with the keys up to its last block's end: the causal half at
about 1.1 times its work, where one scan over every key would be 2. A
block builds its index scores (the products in the operands' dtype,
their sum in float32), the top-k's threshold and the cut among ties at
it (``lax.top_k``), the selection as a mask, and the attention over
every key it may see under that mask: dense, so the work is the causal
range's and not ``topk``'s. The backward pass keeps the mask as bits
(T / 8 bytes a query) and rebuilds the scores. Scope names in a traced
program: ``indexer`` around the scores, the top-k, the mask and the KL
gradient; ``sparse`` around the attention products, the softmax and
their backward. A caller counts a traced call under
``sparse_attention_traced_total.xla`` (``count_traced``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["sparse_attention", "index_scores", "select", "count_traced",
           "QUERY_BLOCK", "KEY_GROUPS"]

# queries a block: a block's float32 scores, probabilities and their
# gradients are (H, QUERY_BLOCK, keys) each, 268 MB at 32 heads over
# 16384 keys
QUERY_BLOCK = 128
KEY_GROUPS = 8


def count_traced(backend="xla"):
    """One traced layer's call: telemetry counter
    ``sparse_attention_traced_total.<backend>``."""
    from ..telemetry import metrics
    metrics.counter(f"sparse_attention_traced_total.{backend}",
                    "sparse attention calls traced, by backend").inc()


def _exact(x):
    """Float32 operands at the highest precision, others at the
    default (float32 accumulation either way)."""
    return jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None


def _products(qi, ki):
    """Every indexer head's products ``qi . ki`` (B, Q, Hi, K), in the
    operands' dtype."""
    return jnp.einsum("bqjd,bkd->bqjk", qi, ki, precision=_exact(qi),
                      preferred_element_type=qi.dtype)


def _combine(a, w, di):
    """The index scores (B, Q, K) float32 from the heads' products ``a``
    and their weights ``w`` (B, Q, Hi)."""
    relu = jax.nn.relu(a.astype(jnp.float32))
    return jnp.sum(relu * w.astype(jnp.float32)[..., None], axis=2) \
        / math.sqrt(di)


def index_scores(qi, ki, w):
    """``I`` (B, Q, K) float32 of queries ``qi`` (B, Q, Hi, Di) over keys
    ``ki`` (B, K, Di)."""
    return _combine(_products(qi, ki), w, qi.shape[-1])


def select(scores, topk, start):
    """The selection (B, Q, K) bool of queries at positions ``start`` ..
    ``start + Q - 1`` from their scores over keys 0 .. K - 1: the
    ``min(t + 1, topk)`` causal keys of the largest scores, ties broken
    by the lower position (``lax.top_k``'s order: the last key it keeps
    is the threshold and, among keys at it, the cut)."""
    nq, nk = scores.shape[-2:]
    kpos = jnp.arange(nk)[None, :]
    causal = kpos <= start + jnp.arange(nq)[:, None]
    if nk <= topk:
        return jnp.broadcast_to(causal, scores.shape)
    masked = jnp.where(causal, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, topk)
    thr, cut = vals[..., -1:], idx[..., -1:]
    return causal & ((masked > thr) | ((masked == thr) & (kpos <= cut)))


def _pack(mask, width):
    """``mask`` (B, Q, K) as bits, 8 keys a byte, zero-padded to
    ``width`` bytes."""
    b, nq, nk = mask.shape
    bits = jnp.sum(mask.reshape(b, nq, nk // 8, 8).astype(jnp.uint8)
                   << jnp.arange(8, dtype=jnp.uint8), axis=-1,
                   dtype=jnp.uint8)
    return jnp.pad(bits, ((0, 0), (0, 0), (0, width - nk // 8)))


def _unpack(bits, nk):
    b, nq, _ = bits.shape
    ones = (bits[..., :nk // 8, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return ones.reshape(b, nq, nk).astype(bool)


def _heads(x, g):
    """(B, Q, H, D) -> (B, Q, G, H / G, D)."""
    b, nq, h, d = x.shape
    return x.reshape(b, nq, g, h // g, d)


def _scores(q, k):
    """(B, G, R, Q, K) float32 of grouped queries (B, Q, G, R, D) over
    keys (B, K, G, D)."""
    return jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=_exact(q),
                      preferred_element_type=jnp.float32) \
        / math.sqrt(q.shape[-1])


def _attend(q, k, v, mask):
    """Attention of a block ``q`` (B, Q, H, D) over keys ``k``, ``v``
    (B, K, G, D) under ``mask`` (B, Q, K): ``(o (B, Q, H, D),
    logsumexp (B, G, R, Q) float32)``. Every query keeps at least one
    key, so no row is empty."""
    g = k.shape[2]
    s = jnp.where(mask[:, None, None], _scores(_heads(q, g), k), -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    total = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", (e / total).astype(v.dtype), v,
                   precision=_exact(v), preferred_element_type=jnp.float32)
    return o.reshape(q.shape).astype(q.dtype), (m + jnp.log(total))[..., 0]


def _attend_bwd(q, k, v, o, do, lse, mask):
    """The block's attention backward: ``(dq, dk, dv, p)``, the
    gradients float32 and ``p`` (B, G, R, Q, K) its probabilities."""
    g = k.shape[2]
    qg, og, dog = _heads(q, g), _heads(o, g), _heads(do, g)
    p = jnp.where(mask[:, None, None],
                  jnp.exp(_scores(qg, k) - lse[..., None]), 0.0)
    dv = jnp.einsum("bgrqk,bqgrd->bkgd", p.astype(do.dtype), dog,
                    precision=_exact(do), preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqgrd,bkgd->bgrqk", dog, v, precision=_exact(v),
                    preferred_element_type=jnp.float32)
    delta = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32),
                    axis=-1)                               # (B, Q, G, R)
    ds = (p * (dp - jnp.moveaxis(delta, 1, 3)[..., None])
          / math.sqrt(q.shape[-1])).astype(q.dtype)
    dq = jnp.einsum("bgrqk,bkgd->bqgrd", ds, k, precision=_exact(k),
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bgrqk,bqgrd->bkgd", ds, qg, precision=_exact(qg),
                    preferred_element_type=jnp.float32)
    return dq.reshape(q.shape), dk, dv, p


def _indexer_bwd(qi, ki, w, mask, p):
    """The KL loss's gradients ``(dqi, dki, dw)``, float32, of a block of
    indexer queries ``qi`` (B, Q, Hi, Di) over keys ``ki`` (B, K, Di),
    the attention's probabilities ``p`` (B, G, R, Q, K) the target."""
    di = qi.shape[-1]
    a = _products(qi, ki)
    scores = jnp.where(mask, _combine(a, w, di), -jnp.inf)
    target = jnp.mean(p, axis=(1, 2))
    d_scores = (jax.nn.softmax(scores, axis=-1) - target) / math.sqrt(di)
    a32 = a.astype(jnp.float32)
    dw = jnp.sum(d_scores[:, :, None] * jax.nn.relu(a32), axis=-1)
    da = jnp.where(a32 > 0, d_scores[:, :, None]
                   * w.astype(jnp.float32)[..., None], 0.0).astype(qi.dtype)
    dqi = jnp.einsum("bqjk,bkd->bqjd", da, ki, precision=_exact(ki),
                     preferred_element_type=jnp.float32)
    dki = jnp.einsum("bqjk,bqjd->bkd", da, qi, precision=_exact(qi),
                     preferred_element_type=jnp.float32)
    return dqi, dki, dw


def _runs(t, block):
    """The runs of blocks: ``[(first block, end block), ...]``."""
    n = t // block
    per = -(-n // min(KEY_GROUPS, n))
    return [(c, min(c + per, n)) for c in range(0, n, per)]


def _blocks(x, c0, c1, block):
    """Blocks ``c0`` .. ``c1 - 1`` of ``x`` (B, T, ...) as the scan's
    leading axis: (c1 - c0, B, block, ...)."""
    part = x[:, c0 * block:c1 * block]
    return jnp.moveaxis(part.reshape((x.shape[0], c1 - c0, block)
                                     + x.shape[2:]), 1, 0)


def _unblock(parts):
    """The scans' (n, B, block, ...) outputs back to (B, T, ...)."""
    x = jnp.moveaxis(jnp.concatenate(parts, axis=0), 0, 1)
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def _check(q, k, qi, ki, block):
    b, t, h, _ = q.shape
    if t % block or block % 8 or h % k.shape[2] or qi.shape[:2] != (b, t) \
            or ki.shape[:2] != (b, t):
        raise ValueError(
            f"sparse_attention: T = {t} must be whole blocks of {block} "
            f"(a multiple of 8), the query heads ({h}) a multiple of the "
            f"key heads ({k.shape[2]}), the indexer over the same tokens")


def _forward(q, k, v, qi, ki, w, topk, block):
    """``(o, logsumexp (B, T, G, R), the selection's bits (B, T, T / 8))``."""
    _check(q, k, qi, ki, block)
    t = q.shape[1]
    outs, lses, bits = [], [], []
    for c0, c1 in _runs(t, block):
        nk = c1 * block
        keys, values, ikeys = k[:, :nk], v[:, :nk], ki[:, :nk]

        def body(_, xs, keys=keys, values=values, ikeys=ikeys):
            qb, qib, wb, start = xs
            with jax.named_scope("indexer"):
                # a run whose keys all fit the top-k ignores the scores,
                # and the compiler drops them
                mask = select(index_scores(qib, ikeys, wb), topk, start)
                packed = _pack(mask, t // 8)
            with jax.named_scope("sparse"):
                o, lse = _attend(qb, keys, values, mask)
            return None, (o, jnp.moveaxis(lse, 3, 1), packed)

        starts = jnp.arange(c0, c1) * block
        _, (o, lse, packed) = jax.lax.scan(
            body, None, (_blocks(q, c0, c1, block), _blocks(qi, c0, c1, block),
                         _blocks(w, c0, c1, block), starts))
        outs.append(o)
        lses.append(lse)
        bits.append(packed)
    return _unblock(outs), _unblock(lses), _unblock(bits)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sparse(q, k, v, qi, ki, w, topk, block):
    return _forward(q, k, v, qi, ki, w, topk, block)[0]


def _sparse_fwd(q, k, v, qi, ki, w, topk, block):
    o, lse, bits = _forward(q, k, v, qi, ki, w, topk, block)
    return o, (q, k, v, qi, ki, w, o, lse, bits)


def _sparse_bwd(topk, block, res, do):
    q, k, v, qi, ki, w, o, lse, bits = res
    f32 = jnp.float32
    dk, dv = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    dki = jnp.zeros(ki.shape, f32)
    dqs, dqis, dws = [], [], []
    for c0, c1 in _runs(q.shape[1], block):
        nk = c1 * block
        keys, values, ikeys = k[:, :nk], v[:, :nk], ki[:, :nk]

        def body(acc, xs, nk=nk, keys=keys, values=values, ikeys=ikeys):
            qb, qib, wb, ob, dob, lseb, bitsb = xs
            mask = _unpack(bitsb, nk)
            with jax.named_scope("sparse"):
                dq, dkb, dvb, p = _attend_bwd(qb, keys, values, ob, dob,
                                              jnp.moveaxis(lseb, 1, 3), mask)
            with jax.named_scope("indexer"):
                dqi, dkib, dw = _indexer_bwd(qib, ikeys, wb, mask, p)
            return (acc[0] + dkb, acc[1] + dvb, acc[2] + dkib), (dq, dqi, dw)

        zeros = (jnp.zeros(keys.shape, f32), jnp.zeros(values.shape, f32),
                 jnp.zeros(ikeys.shape, f32))
        (dkr, dvr, dkir), (dq, dqi, dw) = jax.lax.scan(
            body, zeros, tuple(_blocks(x, c0, c1, block)
                               for x in (q, qi, w, o, do, lse, bits)))
        dk, dv = dk.at[:, :nk].add(dkr), dv.at[:, :nk].add(dvr)
        dki = dki.at[:, :nk].add(dkir)
        dqs.append(dq)
        dqis.append(dqi)
        dws.append(dw)
    grads = (_unblock(dqs), dk, dv, _unblock(dqis), dki, _unblock(dws))
    return tuple(g.astype(x.dtype) for g, x in zip(grads,
                                                   (q, k, v, qi, ki, w)))


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention(q, k, v, qi, ki, w, topk, block=None):
    """The attention of every query over the ``topk`` keys its index
    scores select (module docstring): ``o`` (B, T, H, D) in ``q``'s
    dtype. ``block``: queries a block (``QUERY_BLOCK`` by default, or T
    where T is shorter); it changes no result."""
    block = min(block or QUERY_BLOCK, q.shape[1])
    return _sparse(q, k, v, qi, ki, w, int(topk), int(block))
