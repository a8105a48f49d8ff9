"""The delta-rule mixer's operands from its projections, one pass each
way: a Kimi-delta mixer's three causal filters with their SiLU, q's and
k's l2 norms and the decay's gate.

``kda_operands(q, k, v, f, q_filter, k_filter, v_filter, a_log,
dt_bias, heads=, lower_bound=, backend=)``: ``q``, ``k``, ``v``, ``f``
(B, T, H * D) are four projections of the mixer's input, each filter
(H * D, L) one causal L-tap filter a channel, ``a_log`` (H,),
``dt_bias`` (H * D,). In float32,

    conv(x, w)[t] = sum_j w[:, j] * x[t - (L - 1) + j]   (x zero before 0)
    q'    = l2norm(silu(conv(q, q_filter)))       over a head's D channels
    k'    = l2norm(silu(conv(k, k_filter)))
    v'    = silu(conv(v, v_filter))
    log_a = lower_bound * sigmoid(exp(a_log)[h] * (f + dt_bias))

with ``l2norm(x) = x * rsqrt(sum(x^2) + L2_EPS)``; ``q'``, ``k'``,
``v'`` in the projections' dtype, ``log_a`` in float32, all four
(B, T, H * D), where the projections leave them and ``ops.kda``'s
kernel pair reads them.

Two implementations by ``default_backend`` (a shape rule, no flag;
``backend=`` forces one, for tests and tables; the mixer counts what it
traced under ``kda_mix_traced_total.kernel`` / ``.xla``):

- the kernel pair (on a TPU where the heads are whole lane widths and T
  whole blocks of ``MIX_ROWS`` tokens): grid (batch, block of
  ``MIX_ROWS`` tokens, block of ``MIX_HEADS`` heads). ``kda_mix_fwd``
  reads a block's rows of the four projections and the ``HALO`` rows
  before it (zeros before the sequence), filters, norms and gates in
  fast memory and writes each result once. A ``jax.custom_vjp`` keeps
  the four projections alone; ``kda_mix_bwd`` reads them with halos on
  both sides and the four cotangents with the halo after (the filter's
  transpose reaches L - 1 tokens ahead), recomputes the filtered values,
  writes the four projections' gradients once and a block's partial
  sums of the filters', ``dt_bias``'s and the rate's gradients, which
  XLA adds up.
- the XLA composition (everywhere else, and the tests' oracle): plain
  autodiff through ``jnp`` on heads (B, T, H, D); on the chip each pass
  there pays a relayout between that layout and the projections'.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .short_conv import _filtered

__all__ = ["kda_operands", "default_backend", "count_traced", "L2_EPS",
           "MIX_ROWS", "MIX_HEADS"]

L2_EPS = 1e-6            # under the root of q's and k's l2 norm
# Tokens and heads a grid step of the kernel pair. Measured on a v5e at
# the Ling cell's mixer (4096 tokens, 32 heads of 128, bfloat16
# projections; device ms a call forward plus backward under
# ``jax.checkpoint`` / forward alone; tools/attention_table.py
# --kda-mix 1; PERF.md section 6): 256 tokens and 4 heads 1.457 /
# 0.506, 256 and 8 1.444 / 0.500, 128 and 8 1.502, 512 and 4 1.509, 512
# and 2 1.531, 1024 and 2 1.613, 1024 and 4 1.635; the composition on
# heads 8.704 / 1.769, an XLA custom_vjp on (B, T, H * D) 7.051 / 1.769.
MIX_ROWS = 256
MIX_HEADS = 4
# rows of the tile read before (and, backward, after) a block: a
# bfloat16 tile's height, and more than a filter's L - 1 taps reach
HALO = 16
_F32 = jnp.float32


def default_backend(t, d) -> str:
    """``"kernel"`` on a TPU where a head is whole lane widths and the
    sequence whole blocks of ``MIX_ROWS`` tokens, else ``"xla"``."""
    if jax.default_backend() == "tpu" and d % 128 == 0 \
            and t % MIX_ROWS == 0:
        return "kernel"
    return "xla"


def count_traced(backend):
    """One traced mixer that took ``backend``: telemetry counter
    ``kda_mix_traced_total.kernel`` or ``.xla``."""
    from ..telemetry import metrics
    label = "kernel" if backend.startswith("kernel") else "xla"
    metrics.counter(f"kda_mix_traced_total.{label}",
                    "delta-rule operand preparations traced, by "
                    "backend").inc()


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def _composition(q, k, v, f, q_filter, k_filter, v_filter, a_log, dt_bias,
                 heads, lower_bound):
    def heads_of(y):
        b, t, c = y.shape
        return y.reshape(b, t, heads, c // heads)

    def mixed(x, w):
        return heads_of(jax.nn.silu(_filtered(x.astype(_F32),
                                              w.astype(_F32))))

    rate = jnp.exp(a_log.astype(_F32))[:, None]
    log_a = lower_bound * jax.nn.sigmoid(
        rate * heads_of(f.astype(_F32) + dt_bias.astype(_F32)))
    out = (_unit(mixed(q, q_filter)).astype(q.dtype),
           _unit(mixed(k, k_filter)).astype(k.dtype),
           mixed(v, v_filter).astype(v.dtype), log_a)
    return tuple(a.reshape(q.shape) for a in out)


# ---------------------------------------------------------------------------
# The kernel pair.

def _taps(x, w, start, rows):
    """The filter ``w`` (L, D) over ``rows`` rows of ``x`` from row
    ``start``: ``sum_j w[j] * x[start + r - (L - 1 - j)]``, in the
    composition's order of the taps."""
    taps = w.shape[0]
    return sum(w[j:j + 1] * x[start - (taps - 1 - j):
                              start - (taps - 1 - j) + rows]
               for j in range(taps))


def _taps_back(g, w, rows):
    """The filter's transpose: ``sum_j w[j] * g[r + L - 1 - j]`` for
    ``rows`` rows, ``g`` reaching L - 1 rows past them."""
    taps = w.shape[0]
    return sum(w[j:j + 1] * g[taps - 1 - j:taps - 1 - j + rows]
               for j in range(taps))


def _rows(ref, lanes):
    return ref[0, :, lanes].astype(_F32)


def _fwd_kernel(q_ref, k_ref, v_ref, f_ref, q0_ref, k0_ref, v0_ref, w_ref,
                bias_ref, rate_ref, oq_ref, ok_ref, ov_ref, la_ref, *, d,
                lower_bound):
    """One block: a head at a time, each of q, k, v with the ``HALO``
    rows before it (zeros in the sequence's first block) filtered, its
    SiLU, q's and k's norms; the decay's gate."""
    first = pl.program_id(1) == 0
    rows = q_ref.shape[1]
    for h in range(q_ref.shape[2] // d):
        lanes = slice(h * d, (h + 1) * d)
        for n, (x_ref, before_ref, out_ref) in enumerate((
                (q_ref, q0_ref, oq_ref), (k_ref, k0_ref, ok_ref),
                (v_ref, v0_ref, ov_ref))):
            x = jnp.concatenate([
                jnp.where(first, 0.0, _rows(before_ref, lanes)),
                _rows(x_ref, lanes)], axis=0)
            s = jax.nn.silu(_taps(x, w_ref[n, :, lanes], HALO, rows))
            out_ref[0, :, lanes] = (_unit(s) if n < 2 else s).astype(
                out_ref.dtype)
        z = _rows(f_ref, lanes) + bias_ref[:, lanes]
        la_ref[0, :, lanes] = lower_bound * jax.nn.sigmoid(
            rate_ref[:, lanes] * z)


def _bwd_kernel(q_ref, k_ref, v_ref, f_ref, q0_ref, k0_ref, v0_ref, q1_ref,
                k1_ref, v1_ref, w_ref, bias_ref, rate_ref, gq_ref, gk_ref,
                gv_ref, gla_ref, gq1_ref, gk1_ref, gv1_ref, dq_ref, dk_ref,
                dv_ref, df_ref, sums_ref, *, d, lower_bound):
    """One block: a head at a time, each of q, k, v filtered again over
    its rows and the ``HALO`` after them, the cotangent taken back
    through the norm and the SiLU there (zero past the sequence's last
    block), then through the filter's transpose; the block's sums of
    the filters' gradients in rows ``n * L + j`` of ``sums``, of
    ``dt_bias``'s and the rate's in rows ``3L`` and ``3L + 1``."""
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    rows = q_ref.shape[1]
    taps = w_ref.shape[1]
    for h in range(q_ref.shape[2] // d):
        lanes = slice(h * d, (h + 1) * d)
        for n, (x_ref, before_ref, after_ref, g_ref, g_after_ref,
                dx_ref) in enumerate((
                    (q_ref, q0_ref, q1_ref, gq_ref, gq1_ref, dq_ref),
                    (k_ref, k0_ref, k1_ref, gk_ref, gk1_ref, dk_ref),
                    (v_ref, v0_ref, v1_ref, gv_ref, gv1_ref, dv_ref))):
            w = w_ref[n, :, lanes]
            x = jnp.concatenate([
                jnp.where(first, 0.0, _rows(before_ref, lanes)),
                _rows(x_ref, lanes), _rows(after_ref, lanes)], axis=0)
            y = _taps(x, w, HALO, rows + HALO)
            sg = jax.nn.sigmoid(y)
            g = jnp.concatenate([
                _rows(g_ref, lanes),
                jnp.where(last, 0.0, _rows(g_after_ref, lanes))], axis=0)
            if n < 2:
                s = y * sg
                r = jax.lax.rsqrt(jnp.sum(jnp.square(s), axis=-1,
                                          keepdims=True) + L2_EPS)
                g = r * g - s * (r * r * r) * jnp.sum(g * s, axis=-1,
                                                       keepdims=True)
            dy = g * (sg * (1.0 + y * (1.0 - sg)))
            dx_ref[0, :, lanes] = _taps_back(dy, w, rows).astype(
                dx_ref.dtype)
            for j in range(taps):
                back = HALO - (taps - 1 - j)
                sums_ref[0, 0, pl.ds(n * taps + j, 1), lanes] = jnp.sum(
                    dy[:rows] * x[back:back + rows], axis=0, keepdims=True)
        z = _rows(f_ref, lanes) + bias_ref[:, lanes]
        sg = jax.nn.sigmoid(rate_ref[:, lanes] * z)
        du = _rows(gla_ref, lanes) * (lower_bound * sg * (1.0 - sg))
        dz = du * rate_ref[:, lanes]
        df_ref[0, :, lanes] = dz.astype(df_ref.dtype)
        sums_ref[0, 0, pl.ds(3 * taps, 1), lanes] = jnp.sum(
            dz, axis=0, keepdims=True)
        sums_ref[0, 0, pl.ds(3 * taps + 1, 1), lanes] = jnp.sum(
            du * z, axis=0, keepdims=True)


def _specs(t, rows, width, taps):
    """Block specs over the grid (batch, token block, head block): a
    block's rows, the ``HALO`` rows before and after it (clamped to the
    sequence: the kernels mask them), the filters (3, L, C) and the
    (1, C) rows of ``dt_bias`` and the rate, the sums (B, T / rows,
    3L + 2, C)."""
    tiles, per = t // HALO, rows // HALO
    return dict(
        block=pl.BlockSpec((1, rows, width), lambda b, i, j: (b, i, j)),
        before=pl.BlockSpec((1, HALO, width), lambda b, i, j: (
            b, jnp.maximum(i * per - 1, 0), j)),
        after=pl.BlockSpec((1, HALO, width), lambda b, i, j: (
            b, jnp.minimum((i + 1) * per, tiles - 1), j)),
        filters=pl.BlockSpec((3, taps, width), lambda b, i, j: (0, 0, j)),
        lane_row=pl.BlockSpec((1, width), lambda b, i, j: (0, j)),
        sums=pl.BlockSpec((1, 1, 3 * taps + 2, width),
                          lambda b, i, j: (b, i, 0, j)))


def _call(kernel, name, args, in_specs, out_specs, out_shape, rows, width,
          interpret, **static):
    b, t, c = args[0].shape
    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid=(b, t // rows, c // width), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name=name)(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _pair(q, k, v, f, w, bias, rate, d, lower_bound, rows, width, interpret):
    s = _specs(q.shape[1], rows, width, w.shape[1])
    return tuple(_call(
        _fwd_kernel, "kda_mix_fwd", (q, k, v, f, q, k, v, w, bias, rate),
        [s["block"]] * 4 + [s["before"]] * 3
        + [s["filters"], s["lane_row"], s["lane_row"]],
        [s["block"]] * 4,
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)]
        + [jax.ShapeDtypeStruct(f.shape, _F32)], rows, width, interpret,
        d=d, lower_bound=lower_bound))


def _pair_fwd(q, k, v, f, w, bias, rate, d, lower_bound, rows, width,
              interpret):
    out = _pair(q, k, v, f, w, bias, rate, d, lower_bound, rows, width,
                interpret)
    return out, (q, k, v, f, w, bias, rate)


def _pair_bwd(d, lower_bound, rows, width, interpret, res, cotangents):
    q, k, v, f, w, bias, rate = res
    gq, gk, gv, gla = cotangents
    b, t, c = q.shape
    taps = w.shape[1]
    s = _specs(t, rows, width, taps)
    *grads, sums = _call(
        _bwd_kernel, "kda_mix_bwd",
        (q, k, v, f, q, k, v, q, k, v, w, bias, rate, gq, gk, gv, gla, gq,
         gk, gv),
        [s["block"]] * 4 + [s["before"]] * 3 + [s["after"]] * 3
        + [s["filters"], s["lane_row"], s["lane_row"]]
        + [s["block"]] * 4 + [s["after"]] * 3,
        [s["block"]] * 4 + [s["sums"]],
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v, f)]
        + [jax.ShapeDtypeStruct((b, t // rows, 3 * taps + 2, c), _F32)],
        rows, width, interpret, d=d, lower_bound=lower_bound)
    sums = jnp.sum(sums, axis=(0, 1))
    return (*grads, sums[:3 * taps].reshape(w.shape),
            sums[3 * taps:3 * taps + 1], sums[3 * taps + 1:])


_pair.defvjp(_pair_fwd, _pair_bwd)


def _kernel_path(q, k, v, f, q_filter, k_filter, v_filter, a_log, dt_bias,
                 heads, lower_bound, interpret, rows=MIX_ROWS,
                 block_heads=MIX_HEADS):
    """The kernel pair on the projections, ``rows`` tokens and
    ``block_heads`` heads a grid step; a sequence that is not whole
    blocks is padded with zeros at its end, which no earlier token
    sees."""
    t = q.shape[1]
    d = q.shape[2] // heads
    rows = min(rows, -(-t // HALO) * HALO)
    tail = -t % rows
    x = (q, k, v, f)
    if tail:
        x = [jnp.pad(a, ((0, 0), (0, tail), (0, 0))) for a in x]
    w = jnp.stack([q_filter.T, k_filter.T, v_filter.T]).astype(_F32)
    rate = jnp.repeat(jnp.exp(a_log.astype(_F32)), d)[None]
    out = _pair(*x, w, dt_bias.astype(_F32)[None], rate, d,
                float(lower_bound), rows, math.gcd(heads, block_heads) * d,
                interpret)
    return tuple(a[:, :t] for a in out) if tail else out


@functools.partial(jax.jit, static_argnames=("heads", "lower_bound",
                                             "backend"))
def kda_operands(q, k, v, f, q_filter, k_filter, v_filter, a_log, dt_bias,
                 *, heads, lower_bound, backend=None):
    """The delta rule's q, k, v and decay's logarithm (module docstring)
    from the projections (B, T, H * D). ``backend``: ``"xla"`` (the
    composition), ``"kernel"`` (the Pallas pair; ``"kernel_interpret"``
    interprets it, for tests off the chip) or None (``default_backend``:
    the pair on a TPU where the heads are whole lane widths and T whole
    blocks)."""
    if backend is None:
        backend = default_backend(q.shape[1], q.shape[2] // heads)
    if backend in ("kernel", "kernel_interpret"):
        return _kernel_path(q, k, v, f, q_filter, k_filter, v_filter, a_log,
                            dt_bias, heads, lower_bound,
                            backend == "kernel_interpret")
    if backend != "xla":
        raise ValueError(f"kda_operands: no backend {backend!r}")
    return _composition(q, k, v, f, q_filter, k_filter, v_filter, a_log,
                        dt_bias, heads, lower_bound)
