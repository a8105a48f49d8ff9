"""The gated short convolution of a conv/attention hybrid decoder.

``short_conv(u, w)``: ``u`` (B, T, 3C) is one projection of the layer's
input, its three thirds ``B``, ``C``, ``X`` in that order; ``w`` (C, L)
is one causal L-tap filter a channel. With ``z = B * X``,

    c[t] = sum_j w[:, j] * z[t - (L - 1) + j]        (z zero before t = 0)
    y    = C * c                                     (B, T, C)

a cross-correlation with ``L - 1`` zeros on the left, every sequence of
the batch on its own, no bias.

Written as L shifted adds in float32 on the arrays' own dtype in and
out, which XLA fuses into one pass over ``u`` forward; never a grouped
``conv_general_dilated`` with C groups of one channel (the TPU's
convolution unit does C tiny matrix products for it), and never a
(T, L, C) array of windows. A ``custom_vjp``: the backward pass keeps
``u`` and ``w`` alone, recomputes ``z`` and ``c``, and takes the
filter's transpose as L shifted adds the other way. XLA writes the
thirds of ``du`` apart and joins them, so the backward pass moves about
three times its least bytes where the forward pass moves them once
(0.73 ms a layer forward and backward at 8192 x 2048 on a v5e against
0.45 of roofline: PERF.md section 6, PR 32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["short_conv"]


def _shifted(a, back):
    """``a`` (B, T, C) moved ``back`` positions later along T, zeros at
    a sequence's start (``back`` negative: earlier, zeros at its end)."""
    if back == 0:
        return a
    t = a.shape[1]
    if back > 0:
        return jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return jnp.pad(a, ((0, 0), (0, -back), (0, 0)))[:, -back:]


def _thirds(u):
    f32 = jnp.float32
    b, c, x = jnp.split(u, 3, axis=-1)
    return b.astype(f32), c.astype(f32), x.astype(f32)


def _filtered(z, w):
    """``c`` of the module docstring from float32 ``z`` and ``w``."""
    taps = w.shape[1]
    return sum(w[:, j] * _shifted(z, taps - 1 - j) for j in range(taps))


@jax.custom_vjp
def _short_conv(u, w):
    b, c, x = _thirds(u)
    return (c * _filtered(b * x, w.astype(jnp.float32))).astype(u.dtype)


def _short_conv_fwd(u, w):
    return _short_conv(u, w), (u, w)


def _short_conv_bwd(res, g):
    u, w = res
    taps = w.shape[1]
    b, c, x = _thirds(u)
    w32, g = w.astype(jnp.float32), g.astype(jnp.float32)
    z = b * x
    dc = g * c
    # the filter transposed: position t gives to c[t .. t + L - 1]
    dz = sum(w32[:, j] * _shifted(dc, j - (taps - 1)) for j in range(taps))
    du = jnp.concatenate([dz * x, g * _filtered(z, w32), dz * b], axis=-1)
    dw = jnp.stack([jnp.sum(dc * _shifted(z, taps - 1 - j), axis=(0, 1))
                    for j in range(taps)], axis=1)
    return du.astype(u.dtype), dw.astype(w.dtype)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


@register_op("_short_conv", input_names=("data", "weight"))
@jax.jit
def short_conv(data, weight):
    """Gated short convolution (module docstring): ``data`` (B, T, 3C),
    ``weight`` (C, L) -> (B, T, C) in ``data``'s dtype."""
    if data.ndim != 3 or data.shape[-1] != 3 * weight.shape[0]:
        raise ValueError("short_conv: data must be (B, T, 3C) for a "
                         f"weight (C, L), got {data.shape} and "
                         f"{weight.shape}")
    return _short_conv(data, weight)
