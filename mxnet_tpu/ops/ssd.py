"""The state-space duality (SSD) of a Mamba-2 mixer (Dao and Gu,
arXiv:2405.21060), as a chunked scan in XLA.

``ssd(x, dt, a, b, c)``: ``x`` (B, T, H, P), ``dt`` (B, T, H) a step
size a token and head (after the caller's softplus), ``a`` (H,) a
negative rate a head, ``b`` and ``c`` (B, T, G, N) shared by the
``H / G`` heads of a group (head ``h`` reads group ``h // (H / G)``).
Every head of every sequence keeps a state ``S`` (N, P), zero at the
sequence's start:

    S_t = exp(dt_t a) S_{t-1} + dt_t b_t x_t^T
    y_t = c_t^T S_t                                       (B, T, H, P)

(the skip ``D x`` is the caller's).

Chunks of ``SSD_CHUNK`` tokens. With ``g`` the running sum of ``dt a``
inside a chunk (float32), ``X`` the chunk's ``dt x`` and ``S`` the state
that enters it,

    Y     = (C B^T o L) X + e^g o (C S)
    L     = e^(g_t - g_s) for s <= t, 0 above
    S_out = e^(g_end) S + B^T (e^(g_end - g) o X)

``L`` is masked above its diagonal BEFORE the exponent, so no entry
leaves float32's range whatever the decay. The products are batched over
heads x chunks (``C B^T`` once a group, the other three a head); one
``lax.scan`` between the chunks carries the (H, N, P) float32 state.
The operands of the products are ``x``'s dtype with float32
accumulation (float32 operands at the highest precision); the decays
and the state are float32. T need not be a multiple of the chunk: the
tail is padded with tokens that change nothing (``dt`` zero).

The backward pass is autodiff's: it keeps the chunks' products and the
states that enter them, so a caller that cannot afford a layer's
residuals rematerialises the block that holds the call (the Mamba-2
mixer of ``models/nemotron_h.py`` is one rematerialised unit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssd", "count_traced", "SSD_CHUNK"]

# Tokens a chunk. The published ``chunk_size`` is 128; at the Nemotron
# cell's mixer (8192 tokens, 64 heads of 64, 8 groups of a 128-wide
# state, bfloat16) on a TPU v5e, forward + backward under
# ``jax.checkpoint`` took 22.08 ms at 64, 12.86 at 128 and 10.07 at 256
# (``tools/attention_table.py --ssd 1``): the chunks' products fill the
# matrix unit better as they grow, the decays' passes grow with them.
SSD_CHUNK = 256


def _mm(eq, a, b, dtype):
    """``einsum(eq, a, b)`` with the operands in ``dtype`` and float32
    accumulation; float32 operands at the highest precision."""
    exact = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype), precision=exact,
                      preferred_element_type=jnp.float32)


def _chunked(x, dt, a, b, c, chunk):
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    groups = b.shape[2]
    r = h // groups
    dtype = x.dtype
    n = -(-t // chunk)

    def chunks(arr):
        """(B, T, ..) -> (B, n, chunk, ..), the tail padded with zeros."""
        arr = jnp.pad(arr, [(0, 0), (0, n * chunk - t)]
                      + [(0, 0)] * (arr.ndim - 2))
        return arr.reshape((bsz, n, chunk) + arr.shape[2:])

    def by_head(arr):
        """(B, n, chunk, H, ..) -> (B, n, G, H / G, chunk, ..)."""
        arr = arr.reshape((bsz, n, chunk, groups, r) + arr.shape[4:])
        return jnp.moveaxis(arr, 2, 4)

    dt = chunks(dt.astype(f32))
    g = by_head(jnp.cumsum(dt * a.astype(f32), axis=2))     # (B,n,G,R,L)
    xdt = by_head(chunks(x.astype(f32)) * dt[..., None])    # (B,n,G,R,L,P)
    bg = jnp.moveaxis(chunks(b), 2, 3)                      # (B,n,G,L,N)
    cg = jnp.moveaxis(chunks(c), 2, 3)
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(col <= row, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                    # (B,n,G,R,L,L)
    scores = _mm("bcgln,bcgsn->bcgls", cg, bg, dtype)[:, :, :, None] * decay
    y = _mm("bcgrls,bcgrsp->bcgrlp", scores, xdt, dtype)
    g_end = g[..., -1:]
    states = _mm("bcgsn,bcgrsp->bcgrnp", bg,
                 xdt * jnp.exp(g_end - g)[..., None], dtype)

    def turn(state, x):
        contribution, chunk_decay = x
        return state * chunk_decay[..., None, None] + contribution, state

    _, entering = jax.lax.scan(
        turn, jnp.zeros((bsz, groups, r) + states.shape[-2:], f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(g_end[..., 0]), 1, 0)))
    y = y + _mm("bcgln,bcgrnp->bcgrlp", cg, jnp.moveaxis(entering, 0, 1),
                dtype) * jnp.exp(g)[..., None]
    y = jnp.moveaxis(y, 4, 2).reshape(bsz, n * chunk, h, p)
    return y[:, :t].astype(dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd(x, dt, a, b, c, chunk):
    return _chunked(x, dt, a, b, c, chunk)


def ssd(x, dt, a, b, c, chunk=None):
    """The SSD recurrence (module docstring): ``x`` (B, T, H, P), ``dt``
    (B, T, H), ``a`` (H,), ``b``, ``c`` (B, T, G, N) with G dividing H ->
    ``y`` (B, T, H, P) in ``x``'s dtype, without the skip. ``chunk``:
    tokens a chunk (``SSD_CHUNK``)."""
    bsz, t, h, _ = x.shape if x.ndim == 4 else (None,) * 4
    if x.ndim != 4 or dt.shape != (bsz, t, h) or a.shape != (h,) \
            or b.ndim != 4 or b.shape != c.shape or b.shape[:2] != (bsz, t) \
            or h % b.shape[2]:
        raise ValueError(
            "ssd: x (B, T, H, P), dt (B, T, H), a (H,), b and c (B, T, G, N) "
            f"with G dividing H; got {x.shape}, {dt.shape}, {a.shape}, "
            f"{b.shape}, {c.shape}")
    return _ssd(x, dt, a, b, c, chunk=chunk or SSD_CHUNK)


def count_traced(backend="chunked"):
    """One traced mixer that took ``backend``: telemetry counter
    ``ssd_traced_total.<backend>`` (``.chunked``: the XLA scan above, the
    one implementation today)."""
    from ..telemetry import metrics
    metrics.counter(f"ssd_traced_total.{backend}",
                    "state-space scans traced, by backend").inc()
