"""The gated delta rule of a linear-attention mixer (Kimi Delta
Attention, arXiv:2510.26692), as a chunked scan.

``kda(q, k, v, log_a, beta)``: ``q``, ``k`` (B, T, H, Dk), ``v``
(B, T, H, Dv), ``log_a`` (B, T, H, Dk) the logarithm of a decay in
(0, 1] a channel of the key, ``beta`` (B, T, H) a step size in [0, 1].
Every head of every sequence keeps a state ``S`` (Dk, Dv), zero at the
sequence's start:

    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(Dk)                            (B, T, H, Dv)

One path, whatever the sequence's length (a model's traced mixers are
counted under ``kda_traced_total.chunked``): chunks of ``KDA_CHUNK``
tokens. With ``g`` the running sum of ``log_a`` inside a chunk and ``S``
the state that enters it, the delta rule's WY form gives every token's
correction ``u`` at once,

    (I + diag(beta) tril(A, -1)) U = diag(beta) (V - (K e^g) S),
    A[t, s] = sum_c k_t[c] e^(g_t[c] - g_s[c]) k_s[c],

so ``U = W_v - W_k S`` with ``W_v``, ``W_k`` the unit-lower-triangular
solve of ``beta V`` and ``beta K e^g`` (the inverse as a product of
``log2(chunk)`` factors ``I + M^(2^j)``, ``M = -diag(beta) tril(A,
-1)``, which is nilpotent: matrix products only), the outputs
``O = (Q e^g) S + tril(B) U`` with ``B`` as ``A`` with ``q_t`` for
``k_t``, and the state that leaves ``e^(g_end) S + (K e^(g_end -
g))^T U``. ``A``, ``B``, the solve and ``tril(B) U`` are batched
products over all chunks at once; between chunks one ``lax.scan``
carries the (Dk, Dv) state a head through three products a turn.

``e^(g_t - g_s)`` is never split into ``e^(g_t)`` and ``e^(-g_s)``
over a whole chunk (32 steps of a decay of e^-5 are e^-160): ``A``
and ``B`` are taken a sub-block of ``KDA_SUB`` query rows at a time
against a reference inside the sub-block, so that every factor lies
between e^-80 and e^75 where ``log_a >= -5``, the bound of KDA's safe
gate, which the caller keeps.

The cumulative decay, the solve and the state are float32; the operands
of the products with the keys' width (``A``, ``B`` and the scan's three)
are in ``q``'s dtype with float32 accumulation (float32 operands at the
highest precision). T need not be a multiple of the chunk: the tail is
padded with tokens that change nothing (k, v, beta zero, decay one).

Backward: ``jax.checkpoint`` round the scan's body, so the backward
pass keeps ONE state a chunk (T / 32 x H x Dk x Dv floats: 268 MB at
4096 tokens and 32 heads of 128) and recomputes inside a chunk. The
chunk quantities before the scan (the pairs, the solve, ``W_v``,
``W_k``) are plain autodiff's to keep; a caller that cannot afford them
a layer rematerialises the block that holds the call, as ``models/
ling.py`` does with its whole mixer (it keeps the mixer's input alone
and rebuilds projections, operands and chunks once in the backward
pass).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["kda", "KDA_CHUNK", "KDA_SUB"]

# Tokens a chunk. Measured on a v5e at the Ling cell's mixer (4096
# tokens, 32 heads of 128, bfloat16 q, k, v, float32 decay and beta;
# device ms a call under ``jax.checkpoint``, forward plus backward /
# forward alone; tools/attention_table.py --kda 1; PERF.md section 6,
# PR 35): chunks of 32 tokens 34.9 / 8.6, of 64 (the published kernels'
# chunk) 39.9 / 10.2, of 128 54.7 / 15.9: the chunks' own products (the
# pairs, the solve) grow with the chunk faster than the scan's turns
# fall.
KDA_CHUNK = 32
# rows of a sub-block of A and B: 16 steps of the safe gate's strongest
# decay (e^-5 a step) stay inside float32's range
KDA_SUB = 16


def _mm(eq, a, b, dtype):
    """``einsum(eq, a, b)`` with the operands in ``dtype`` and float32
    accumulation; float32 operands at the highest precision."""
    exact = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype), precision=exact,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(lower):
    """``(I + lower)^-1`` for strictly lower-triangular ``lower``
    (.., C, C) in float32: with ``M = -lower``, nilpotent of order C,
    ``(I - M)^-1 = (I + M)(I + M^2)(I + M^4)..``: matrix products
    only."""
    c = lower.shape[-1]
    exact = jax.lax.Precision.HIGHEST
    power = -lower
    inverse = jnp.eye(c, dtype=lower.dtype) + power
    for _ in range(max(0, math.ceil(math.log2(c)) - 1)):
        power = jnp.matmul(power, power, precision=exact)
        inverse = inverse + jnp.matmul(inverse, power, precision=exact)
    return inverse


def _decayed_pairs(rows, keys, g, sub, dtype):
    """``P[r, t, s] = sum_c rows_r,t[c] e^(g_t[c] - g_s[c]) keys_s[c]``
    for ``s`` no later than ``t``'s sub-block (entries past ``t`` inside
    it are for the caller's mask; later sub-blocks read zero):
    (R, .., C, C) float32 from ``rows`` (R, .., C, D) and ``keys``
    (.., C, D), a sub-block of ``sub`` rows against a reference of its
    own, the running decay at its first row."""
    *lead, c, d = keys.shape
    m = c // sub
    gs = g.reshape(*lead, m, sub, d)
    ref = gs[..., :1, :]                                  # (.., m, 1, d)
    row_side = rows.reshape(-1, *lead, m, sub, d) * jnp.exp(gs - ref)
    # (.., I, J, s, d): sub-block J's keys as sub-block I's rows see them
    gap = ref[..., :, None, :, :] - gs[..., None, :, :, :]
    later = jnp.arange(m)[None, :] > jnp.arange(m)[:, None]
    key_side = keys.reshape(*lead, 1, m, sub, d) * jnp.exp(
        jnp.where(later[:, :, None, None], -jnp.inf, gap))
    pairs = _mm("r...itd,...ijsd->r...itjs", row_side, key_side, dtype)
    return pairs.reshape(-1, *lead, c, c)


def _chunked(q, k, v, log_a, beta, chunk):
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dtype, out_dtype = q.dtype, v.dtype
    if chunk % KDA_SUB:
        raise ValueError(f"kda: a chunk is whole sub-blocks of {KDA_SUB} "
                         f"tokens, got {chunk}")
    n = -(-t // chunk)
    exact = jax.lax.Precision.HIGHEST

    def chunks(a):
        """(B, T, H, D) -> (N, B, H, chunk, D) in float32."""
        a = jnp.pad(a.astype(f32), ((0, 0), (0, n * chunk - t), (0, 0),
                                    (0, 0)))
        return a.reshape(b, n, chunk, h, -1).transpose(1, 0, 3, 2, 4)

    q, k, v = chunks(q) / math.sqrt(dk), chunks(k), chunks(v)
    beta = chunks(beta[..., None])                    # (N, B, H, C, 1)
    g = jnp.cumsum(chunks(log_a), axis=-2)
    g_end = g[..., -1:, :]
    k_in = k * jnp.exp(g)            # a key as the entering state sees it
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    a, b_low = _decayed_pairs(jnp.stack([k, q]), k, g, KDA_SUB, dtype)
    solve = _unit_lower_inverse(jnp.where(col < row, a, 0.0) * beta)
    w_v = jnp.matmul(solve, beta * v, precision=exact)
    w_k = jnp.matmul(solve, beta * k_in, precision=exact)
    b_low = jnp.where(col <= row, b_low, 0.0)

    def turn(state, x):
        w_v, w_k, q_in, k_out, decay = x
        u = w_v - _mm("bhck,bhkv->bhcv", w_k, state, dtype)
        o_in = _mm("bhck,bhkv->bhcv", q_in, state, dtype)
        state = state * decay + _mm("bhck,bhcv->bhkv", k_out, u, dtype)
        return state, (u, o_in)

    xs = (w_v, w_k.astype(dtype), (q * jnp.exp(g)).astype(dtype),
          (k * jnp.exp(g_end - g)).astype(dtype),
          jnp.exp(jnp.swapaxes(g_end, -1, -2)))
    _, (u, o_in) = jax.lax.scan(
        jax.checkpoint(turn, prevent_cse=False),
        jnp.zeros((b, h, dk, v.shape[-1]), f32), xs)
    o = o_in + _mm("...ts,...sv->...tv", b_low, u, dtype)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, -1)
    return o[:, :t].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _kda(q, k, v, log_a, beta, chunk=KDA_CHUNK):
    return _chunked(q, k, v, log_a, beta, chunk)


@register_op("_kda", input_names=("q", "k", "v", "log_a", "beta"))
def kda(q, k, v, log_a, beta):
    """The gated delta rule (module docstring): ``q``, ``k``, ``log_a``
    (B, T, H, Dk), ``v`` (B, T, H, Dv), ``beta`` (B, T, H) ->
    (B, T, H, Dv) in ``v``'s dtype. ``log_a`` in [-5, 0]."""
    if q.ndim != 4 or k.shape != q.shape or log_a.shape != q.shape \
            or v.shape[:3] != q.shape[:3] or beta.shape != q.shape[:3]:
        raise ValueError(
            "kda: q, k, log_a (B, T, H, Dk), v (B, T, H, Dv), beta "
            f"(B, T, H); got {q.shape}, {k.shape}, {v.shape}, "
            f"{log_a.shape}, {beta.shape}")
    return _kda(q, k, v, log_a, beta)
