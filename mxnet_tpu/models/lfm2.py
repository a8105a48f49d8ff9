"""LFM2-MoE decoder family: gated short-convolution mixers mixed with
qk-normed grouped-query attention, dense leading FFNs, then experts
routed by sigmoid scores with a selection bias, and a tied head.

The blocks follow the published ``config.json`` of
``LiquidAI/LFM2-8B-A1B`` (``model_type: lfm2_moe``) key by key;
``Lfm2MoeLM.from_config`` builds the net from such a dict. Per layer
``x + op(norm(x))`` then ``x + ffn(norm(x))`` (``laguna.DecoderLayer``,
the skeleton the decoder families share), a final RMSNorm, logits
``h E^T`` with ``E`` the embedding matrix.

- ``Lfm2ShortConv`` (``layer_types[i] == "conv"``): ``in_proj`` to
  three times the width, ``ops.short_conv`` (``C * conv3(B * X)``, one
  causal ``conv_L_cache``-tap filter a channel), ``out_proj``. No bias.
- ``Lfm2Attention`` (``"full_attention"``): grouped-query attention,
  each head of q and k through an RMSNorm over the head's dimensions
  (one weight for q, one for k) before the rotary positions (over the
  whole head, rotate-half), causal, ``ops.banded_attention`` without a
  window: on a TPU the splash kernel, its 64-wide heads zero-padded to
  the lanes.
- the FFN of layer ``i`` is ``parallel.moe.GatedFFN`` (``mlp``) for
  ``i < num_dense_layers`` and ``parallel.moe.RoutedExpertsFFN``
  (``moe``) with ``scoring="sigmoid"`` after: top-k of
  ``sigmoid(x W_r) + expert_bias``, weighed by the unbiased scores
  renormalised; no shared expert.
- the head is ``laguna.LMHead`` built over the embedding's own
  parameter (``params=``): one ``Parameter`` under ``embed.weight`` and
  ``head.weight``, one leaf of a fused step.

Scope names in a traced program: ``layers/<i>/conv`` around the whole
mixer and ``.../conv/mix`` around the operator alone;
``layers/<i>/attn/full`` around the two attention products and the
softmax; under ``layers/<i>/moe``: ``route``, ``dispatch``,
``experts``, ``combine``. Every traced attention call bumps the counter
``attention_traced_total.kernel`` or ``.dense``.
"""
from __future__ import annotations

import functools

import jax

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import invoke
from ..ops.banded_attention import banded_attention, default_backend
from ..ops.pallas_kernels import count_traced
from ..ops.short_conv import short_conv
from ..parallel.moe import GatedFFN, RoutedExpertsFFN
from .laguna import (DecoderLayer, LMHead, RMSNorm, _dense, _rotate,
                     rotary_tables)
from .transformer import _in_scope

__all__ = ["Lfm2ShortConv", "Lfm2Attention", "Lfm2DecoderLayer",
           "Lfm2MoeLM"]


class Lfm2ShortConv(HybridBlock):
    """``out_proj(C * conv(B * X))`` with ``(B, C, X)`` the thirds of
    ``in_proj(x)`` and ``conv`` one causal ``kernel``-tap filter a
    channel (``filter``: (units, kernel))."""

    def __init__(self, units, kernel=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = _dense(3 * units, units, "in_proj_")
            self.filter = self.params.get("filter", shape=(units, kernel),
                                          init=None)
            self.out_proj = _dense(units, units, "out_proj_")

    def hybrid_forward(self, F, x, filter):
        mixed = invoke(_in_scope("mix", short_conv),
                       [self.in_proj(x), filter])
        return self.out_proj(mixed)


@functools.partial(jax.jit, static_argnames=("backend",))
def _causal_attention(q, k, v, cos, sin, turn, backend):
    """Normed heads ``q`` (B, T, H, D), ``k`` (B, T, Hkv, D) and
    projected ``v`` (B, T, Hkv * D) to the heads' outputs
    (B, T, H * D): rotary positions, then causal attention."""
    b, t, h, d = q.shape
    q, k = _rotate(q, cos, sin, turn), _rotate(k, cos, sin, turn)
    v = v.reshape(b, t, k.shape[2], d)
    with jax.named_scope("full"):
        o = banded_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), backend=backend)
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * d)


class Lfm2Attention(HybridBlock):
    """Causal grouped-query attention with ``num_heads`` query heads
    over ``num_kv_heads`` key/value heads; q and k each through an
    RMSNorm over a head's ``head_dim`` dimensions before the rotary
    positions of ``rope`` (a ``rotary_tables`` entry)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be a multiple of "
                             "num_kv_heads")
        self._shape = (num_heads, num_kv_heads, head_dim)
        self._rope = dict(rope)
        self._tables = {}
        with self.name_scope():
            self.q_proj = _dense(num_heads * head_dim, units, "q_proj_")
            self.k_proj = _dense(num_kv_heads * head_dim, units, "k_proj_")
            self.v_proj = _dense(num_kv_heads * head_dim, units, "v_proj_")
            self.q_norm = RMSNorm(head_dim, eps, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, eps, prefix="k_norm_")
            self.o_proj = _dense(units, num_heads * head_dim, "o_proj_")

    def hybrid_forward(self, F, x):
        b, t, _ = x.shape
        heads, kv_heads, d = self._shape
        if t not in self._tables:
            self._tables[t] = rotary_tables(t, d, self._rope)
        cos, sin, turn = self._tables[t]
        backend = default_backend(t, d)
        count_traced("kernel" if backend == "splash" else "dense")
        q = self.q_norm(self.q_proj(x).reshape((b, t, heads, d)))
        k = self.k_norm(self.k_proj(x).reshape((b, t, kv_heads, d)))
        out = invoke(functools.partial(_causal_attention, cos=cos, sin=sin,
                                       turn=turn, backend=backend),
                     [q, k, self.v_proj(x)])
        return self.o_proj(out)


class Lfm2DecoderLayer(DecoderLayer):
    """``make_op`` builds the mixer (under ``conv`` or ``attn``),
    ``make_ffn`` the FFN (``mlp`` where dense, ``moe`` where routed)."""

    def __init__(self, units, eps, make_op, conv, make_ffn, sparse,
                 **kwargs):
        super().__init__(units, eps, ("conv" if conv else "attn", make_op),
                         ("moe" if sparse else "mlp", make_ffn),
                         norms=("operator_norm", "ffn_norm"), **kwargs)


class Lfm2MoeLM(HybridBlock):
    """The decoder over token ids (B, T) -> float32 logits (B, T, V),
    the head tied to the embedding.

    ``layer_types`` gives one entry a layer, ``"conv"`` or
    ``"full_attention"`` (a longer list is read up to ``num_layers``);
    the first ``num_dense_layers`` FFNs are dense. ``experts_held`` is
    the range of the ``num_experts_routed`` experts this chip holds."""

    def __init__(self, vocab_size, units, num_layers, layer_types,
                 num_dense_layers, num_heads, num_kv_heads, hidden_size,
                 moe_hidden_size, num_experts_routed, experts_held,
                 num_experts_per_tok, routed_scaling, rope_theta,
                 conv_kernel=3, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        head_dim = units // num_heads
        rope = {"rope_type": "default", "rope_theta": rope_theta,
                "partial_rotary_factor": 1.0}
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i in range(num_layers):
                    if layer_types[i] not in ("conv", "full_attention"):
                        raise ValueError("layer_types holds 'conv' and "
                                         "'full_attention', got "
                                         f"{layer_types[i]!r}")
                    conv = layer_types[i] == "conv"
                    sparse = i >= num_dense_layers
                    if conv:
                        make_op = functools.partial(
                            Lfm2ShortConv, units, conv_kernel,
                            prefix="conv_")
                    else:
                        make_op = functools.partial(
                            Lfm2Attention, units, num_heads, num_kv_heads,
                            head_dim, rope, eps, prefix="attn_")
                    if sparse:
                        make_ffn = functools.partial(
                            RoutedExpertsFFN, units, moe_hidden_size,
                            num_experts_routed, num_experts_per_tok,
                            experts_held, routed_scaling,
                            label=f"layers.{i}", scoring="sigmoid",
                            prefix="moe_")
                    else:
                        make_ffn = functools.partial(
                            GatedFFN, units, hidden_size, prefix="mlp_")
                    self.layers.add(Lfm2DecoderLayer(
                        units, eps, make_op, conv, make_ffn, sparse))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            # the embedding's own Parameter: one weight, two names
            self.head = LMHead(vocab_size, units, params=self.embed.params)

    @classmethod
    def from_config(cls, config, **kwargs):
        """The net of a ``config.json``-shaped dict. ``num_experts``
        counts the experts held here where ``deployment`` gives
        ``experts_held`` (start, stop) and ``num_experts_published``;
        without a ``deployment`` the layer holds all it routes over."""
        deployment = config.get("deployment", {})
        start, stop = deployment.get("experts_held",
                                     (0, config["num_experts"]))
        if not config.get("use_expert_bias", True) \
                or not config.get("norm_topk_prob", True) \
                or config.get("conv_bias", False):
            raise ValueError("lfm2_moe is built with use_expert_bias and "
                             "norm_topk_prob true and conv_bias false")
        return cls(
            vocab_size=config["vocab_size"], units=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            layer_types=config["layer_types"],
            num_dense_layers=config["num_dense_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            hidden_size=config["intermediate_size"],
            moe_hidden_size=config["moe_intermediate_size"],
            num_experts_routed=deployment.get("num_experts_published",
                                              config["num_experts"]),
            experts_held=range(start, stop),
            num_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling=config["routed_scaling_factor"],
            rope_theta=config["rope_theta"],
            conv_kernel=config["conv_L_cache"], eps=config["norm_eps"],
            **kwargs)

    def hybrid_forward(self, F, tokens):
        return self.head(self.norm(self.layers(self.embed(tokens))))
