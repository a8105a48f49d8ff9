"""Fused-region ops emitted by the graph optimizer (mxnet_tpu/opt/).

Three ops that exist only as rewrite TARGETS — user graphs never spell
them; the level-2 pipeline partitions matched patterns into them:

- ``_fused_group``     — a collapsed fusion group: carries its subgraph
  as serialized symbol JSON and evaluates it through ONE jit region
  (per-group cached ``jax.jit``), so an eager/non-bulk executor pays a
  single dispatch per group and a bulk trace stamps one named_scope
  over the whole region (the explicit partitioning "Operator Fusion in
  XLA" shows XLA won't always discover on its own);
- ``_fused_attention`` — softmax(QKᵀ·scale)·V collapsed from its
  4-node graph spelling; lowers to the Pallas attention kernel (no
  T x T array in HBM) where ``flash_attention_available`` says so and
  falls back to the exact op-by-op composition of the unfused graph
  otherwise — same functions, so the fallback is bitwise-identical to
  the graph it replaced;
- ``_nhwc_conv``       — Convolution evaluated in NHWC with the weight
  kept in the frozen OIHW parameter layout (transposed in-kernel; XLA
  folds it). Emitted by the layout-selection pass inside NHWC regions.

Kept under ops/ (not opt/) so deserialized optimized graphs evaluate
without importing the optimizer package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["fused_group", "fused_attention", "nhwc_conv",
           "pallas_attention_active"]


@functools.lru_cache(maxsize=256)
def _group_symbol(graph_json: str):
    from ..symbol.symbol import load_json
    return load_json(graph_json)


@functools.lru_cache(maxsize=256)
def _group_callable(graph_json: str, training: bool):
    """One jit region per (group, mode) for EAGER dispatch of a fused
    group: the whole subgraph is a single compiled program."""
    from ..symbol.symbol import eval_graph

    def f(*inputs):
        vm = {f"_fg_in{i}": v for i, v in enumerate(inputs)}
        outs, _aux = eval_graph(_group_symbol(graph_json), vm,
                                training, None)
        return tuple(outs)

    return jax.jit(f)


def _aux_map_of(params) -> dict:
    return {int(k): int(v)
            for k, v in (params.get("aux_map") or {}).items()}


@register_op("_fused_group", n_out=-1, needs_train=True,
             aux_updates=_aux_map_of)
def fused_group(*inputs, graph="", pattern="", num_outputs=1,
                aux_map=None, _training=False):
    """Evaluate a fusion group's subgraph (see module docstring).
    ``graph`` is symbol JSON whose variables are ``_fg_in{i}`` in input
    order; ``aux_map`` maps this node's output index -> input position
    of the aux variable it updates (BatchNorm moving stats).

    Under an enclosing trace (the bulk-mode executor jit) the subgraph
    evaluates INLINE so XLA fuses freely across the group boundary
    (a nested pjit would wall off the neighboring ops — measured as a
    real regression when layout-pass transposes sit at group edges);
    at a true eager boundary it runs through the cached per-group jit —
    one dispatch for the whole group."""
    with jax.named_scope(f"mxopt_fused_{pattern or 'group'}"):
        if any(isinstance(x, jax.core.Tracer) for x in inputs):
            from ..symbol.symbol import eval_graph
            sym = _group_symbol(graph)
            vm = {f"_fg_in{i}": v for i, v in enumerate(inputs)}
            outs, _aux = eval_graph(sym, vm, bool(_training), None)
            outs = tuple(outs)
        else:
            outs = _group_callable(graph, bool(_training))(*inputs)
    return tuple(outs)  # n_out=-1 contract: always a tuple


def pallas_attention_active(q_len: int, k_len: int, head_dim: int,
                            dtype=jnp.float32) -> bool:
    """True when ``_fused_attention`` will lower to the Pallas attention
    kernel: a TPU backend is present, the kernel's rule takes the shape
    and dtype (``flash_attention_available``), and the
    MXNET_GRAPH_OPT_PALLAS escape hatch is on (default). Everything
    else takes the XLA fallback — the bitwise op-by-op composition."""
    from ..base import get_env
    from .pallas_kernels import flash_attention_available
    if not get_env("MXNET_GRAPH_OPT_PALLAS", True):
        return False
    if not any(d.platform == "tpu" for d in jax.devices()):
        return False
    return flash_attention_available(q_len, k_len, head_dim, dtype)


@register_op("_fused_attention", input_names=("q", "k", "v"))
def fused_attention(q, k, v, scale=1.0, causal=False):
    """Fused scaled-dot-product attention over (B, H, T, D) operands.

    Pallas attention kernel on TPU where its rule takes the call
    (tolerance class "fusion": the softmax is normalised after the
    weighted sum), exact unfused composition everywhere else (bitwise
    with the graph it replaced — the same registered softmax/batch_dot
    functions run in the same order)."""
    from .pallas_kernels import count_traced, flash_attention
    kernel = pallas_attention_active(q.shape[-2], k.shape[-2], q.shape[-1],
                                     q.dtype)
    count_traced("kernel" if kernel else "dense")
    if kernel:
        return flash_attention(q, k, v, causal=causal, scale=float(scale))
    # XLA fallback: literally the ops the fusion pass collapsed
    from .nn import softmax as _softmax
    from .tensor import batch_dot as _batch_dot
    scores = _batch_dot(q, k, transpose_b=True) * jnp.asarray(
        scale, q.dtype)
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), jnp.bool_), t_k - t_q)
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    return _batch_dot(_softmax(scores, axis=-1), v)


@register_op("_nhwc_conv", input_names=("data", "weight", "bias"))
def nhwc_conv(data, weight, *bias, kernel=None, stride=None, dilate=None,
              pad=None, num_filter=0, num_group=1, workspace=1024,
              no_bias=False, cudnn_tune=None, cudnn_off=False,
              layout=None):
    """NHWC 2-D convolution with the weight still in OIHW (the bound
    parameter's layout — the optimizer must not change arg shapes).
    Same param surface as Convolution; emitted only inside NHWC layout
    regions."""
    k = len(kernel) if kernel else 2
    stride = tuple(stride) if stride else (1,) * k
    dilate = tuple(dilate) if dilate else (1,) * k
    pad = tuple(pad) if pad else (0,) * k
    w = jnp.transpose(weight, (2, 3, 1, 0))  # OIHW -> HWIO
    out = jax.lax.conv_general_dilated(
        data, w, window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=num_group)
    if not no_bias and bias:
        out = out + bias[0].reshape((1, 1, 1, -1))
    return out


@register_op("_nhwc_pool")
def nhwc_pool(data, kernel=(2, 2), pool_type="max", global_pool=False,
              cudnn_off=False, pooling_convention="valid", stride=None,
              pad=None, p_value=2, count_include_pad=True, layout=None):
    """NHWC 2-D pooling (Pooling's param surface; channels-last window).
    Emitted only inside NHWC layout regions."""
    if global_pool:
        kernel = data.shape[1:3]
        stride = (1, 1)
        pad = (0, 0)
    else:
        kernel = tuple(kernel)
        stride = tuple(stride) if stride else (1, 1)
        pad = tuple(pad) if pad else (0, 0)
    window = (1,) + tuple(kernel) + (1,)
    strides = (1,) + tuple(stride) + (1,)
    if pooling_convention == "full":
        pads = [(0, 0)]
        for i in range(2):
            size = data.shape[1 + i] + 2 * pad[i]
            out = -(-max(size - kernel[i], 0) // stride[i]) + 1
            need = (out - 1) * stride[i] + kernel[i] - size
            pads.append((pad[i], pad[i] + max(need, 0)))
        pads.append((0, 0))
    else:
        pads = [(0, 0)] + [(p, p) for p in pad] + [(0, 0)]
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else jnp.iinfo(data.dtype).min
        return jax.lax.reduce_window(data, init, jax.lax.max, window,
                                     strides, pads)
    if pool_type in ("avg", "sum"):
        s = jax.lax.reduce_window(
            data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
            jax.lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            import numpy as onp
            return s / jnp.asarray(float(onp.prod(kernel)), s.dtype)
        ones = jnp.ones_like(data)
        cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                    strides, pads)
        return s / cnt
    raise ValueError(f"unsupported pool_type {pool_type!r} in an NHWC "
                     f"layout region")
