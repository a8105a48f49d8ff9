"""Pallas kernels backing the optimizer's fused patterns.

Two patterns XLA reliably refuses to fuse on its own (PAPERS.md
"Operator Fusion in XLA": multi-output loop fusion across a dtype
boundary, and softmax-contraction chains):

- **fused optimizer + cast** — the mixed-precision SGD step writes the
  f32 master weight, the f32 momentum, AND the low-precision working
  copy in one pass over the data (:func:`mp_sgd_mom_update_pallas`).
  XLA lowers the reference composition (``mp_sgd_mom_update``) as an
  update kernel followed by a separate cast kernel — one extra HBM
  round trip per parameter per step. The Pallas kernel emits all three
  outputs from one VMEM-resident tile sweep.
- **fused attention** — ``_fused_attention`` (ops/fused.py) lowers to
  the flash-attention kernel in ops/pallas_kernels.py; this module
  only hosts the availability probe so the policy lives in one place.

Availability contract (the "automatic XLA fallback" the level-2
pipeline promises): every entry point here returns the PLAIN-XLA
composition's result when the TPU Pallas backend is absent, shapes
don't tile, or ``MXNET_GRAPH_OPT_PALLAS=0`` — callers never need to
branch. CPU tier-1 therefore exercises the fallback paths; the kernels
themselves are validated in Pallas interpret mode (tests/test_graph_opt
.py) where the same Mosaic program runs on the host interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mp_sgd_mom_update_pallas", "pallas_kernels_active",
           "fused_attention_available"]

_LANES = 128
_BLOCK_ROWS = 256


def pallas_kernels_active() -> bool:
    """True when Pallas lowering is allowed AND a TPU backend is
    present (the Mosaic compile path; interpret mode bypasses this)."""
    from ..base import get_env
    if not get_env("MXNET_GRAPH_OPT_PALLAS", True):
        return False
    return any(d.platform == "tpu" for d in jax.devices())


def fused_attention_available(q_len: int, k_len: int, head_dim: int,
                              dtype=jnp.float32) -> bool:
    """Will ``_fused_attention`` lower to the attention kernel here?"""
    from ..ops.fused import pallas_attention_active
    return pallas_attention_active(q_len, k_len, head_dim, dtype)


# ---------------------------------------------------------------------------
# fused mixed-precision SGD + cast
# ---------------------------------------------------------------------------

def _mp_sgd_kernel(s_ref, g_ref, m_ref, w32_ref, w_out, m_out, w32_out,
                   *, momentum, clip):
    # per-step scalars arrive TRACED in the padded scalar row (the
    # eager _jk path keeps lr/wd/rescale_grad as traced weak-f32 so an
    # LR scheduler never retraces — this kernel must honor the same
    # contract); structural scalars (momentum, clip) are static
    lr = s_ref[0, 0]
    wd = s_ref[0, 1]
    rescale = s_ref[0, 2]
    g = g_ref[...].astype(jnp.float32) * rescale
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    g = g + wd * w32_ref[...]
    new_m = momentum * m_ref[...] - lr * g
    new_w32 = w32_ref[...] + new_m
    w32_out[...] = new_w32
    m_out[...] = new_m
    w_out[...] = new_w32.astype(w_out.dtype)


def _pad_rows(flat, rows, cols):
    need = rows * cols - flat.shape[0]
    return jnp.pad(flat, (0, need)) if need else flat


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "momentum", "clip", "interpret"))
def _mp_sgd_call(grad, mom, weight32, lr, wd, rescale, *, out_dtype,
                 momentum, clip, interpret):
    n = weight32.size
    cols = _LANES
    rows = -(-n // cols)
    rows_pad = -(-rows // 8) * 8
    g2 = _pad_rows(grad.ravel(), rows_pad, cols).reshape(rows_pad, cols)
    m2 = _pad_rows(mom.ravel(), rows_pad, cols).reshape(rows_pad, cols)
    w2 = _pad_rows(weight32.ravel(), rows_pad,
                   cols).reshape(rows_pad, cols)
    # traced per-step scalars ride in one tile-aligned row block
    scal = jnp.zeros((8, cols), jnp.float32)
    scal = scal.at[0, 0].set(lr).at[0, 1].set(wd).at[0, 2].set(rescale)
    br = min(_BLOCK_ROWS, rows_pad)
    grid = (-(-rows_pad // br),)
    spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    scal_spec = pl.BlockSpec((8, cols), lambda i: (0, 0))
    w_out, m_out, w32_out = pl.pallas_call(
        functools.partial(_mp_sgd_kernel, momentum=momentum, clip=clip),
        grid=grid,
        in_specs=[scal_spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, cols), jnp.dtype(out_dtype)),
            jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
            jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32),
        ],
        interpret=interpret,
    )(scal, g2, m2, w2)
    shape = weight32.shape
    return (w_out.ravel()[:n].reshape(shape),
            m_out.ravel()[:n].reshape(shape),
            w32_out.ravel()[:n].reshape(shape))


def _static_float(v):
    """float(v) when concrete, None when traced (a structural scalar
    that arrives as a tracer cannot parameterize the kernel)."""
    if isinstance(v, jax.core.Tracer):
        return None
    try:
        return float(v)
    except TypeError:
        return None


def mp_sgd_mom_update_pallas(weight, grad, mom, weight32, lr=0.01,
                             momentum=0.0, wd=0.0, rescale_grad=1.0,
                             clip_gradient=-1.0, interpret=False):
    """One-launch mixed-precision SGD-momentum step + low-precision
    cast: returns ``(new_weight, new_mom, new_weight32)`` — the exact
    contract (and formula) of the ``mp_sgd_mom_update`` op. Lowers via
    Pallas when :func:`pallas_kernels_active` (or ``interpret=True``
    for host validation); otherwise returns the XLA composition —
    automatic fallback, same numerics contract.

    ``lr``/``wd``/``rescale_grad`` may be traced (the eager ``_jk``
    jit keeps them so — schedulers must not retrace); ``momentum`` and
    ``clip_gradient`` are structural and must be concrete — a traced
    value there falls back to the XLA composition."""
    mom_s = _static_float(momentum)
    clip_s = None if clip_gradient is None else _static_float(
        clip_gradient)
    structural_traced = mom_s is None or (
        clip_gradient is not None and clip_s is None)
    if structural_traced or (not interpret
                             and not pallas_kernels_active()):
        from ..ops.optimizer_ops import _mp_sgd_mom_update_xla
        return _mp_sgd_mom_update_xla(
            weight, grad, mom, weight32, lr=lr, momentum=momentum,
            wd=wd, rescale_grad=rescale_grad,
            clip_gradient=clip_gradient)
    clip = None if clip_gradient is None or clip_s < 0 else clip_s
    return _mp_sgd_call(
        jnp.asarray(grad), jnp.asarray(mom), jnp.asarray(weight32),
        jnp.asarray(lr, jnp.float32), jnp.asarray(wd, jnp.float32),
        jnp.asarray(rescale_grad, jnp.float32),
        out_dtype=str(weight.dtype), momentum=mom_s, clip=clip,
        interpret=interpret)
