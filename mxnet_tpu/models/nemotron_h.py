"""Nemotron-H decoder family (``model_type: nemotron_h``): Mamba-2
state-space mixers, grouped-query attention without positions and
experts routed by sigmoid scores with a selection bias beside a shared
expert, one block a layer in the order ``hybrid_override_pattern``
gives.

The blocks follow the published ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` key by key;
``NemotronHLM.from_config`` builds the net from such a dict and refuses
what it would have to guess. Layer ``i`` is ``x + block(norm(x))``
(``laguna.DecoderLayer`` with one block), the block chosen by the
pattern's character ``i``: ``M`` a Mamba-2 mixer, ``*`` attention, ``E``
an expert layer. RMSNorm, a final RMSNorm, an untied head
(``laguna.LMHead``), no bias but the filter's.

- ``NemotronHMamba`` (``M``): ``[z, xBC, dt] = x W_in`` (``d_inner =
  mamba_num_heads * mamba_head_dim``, ``xBC`` of ``d_inner + 2 n_groups
  ssm_state_size``, ``dt`` one a head); ``xBC = silu(conv(xBC) + b)``
  (one causal ``conv_kernel``-tap filter a channel, ``ops.short_conv``'s
  shifted adds); ``xBC = [x, B, C]``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the SSD (``ops.ssd``: a state of
  ``ssm_state_size`` x ``mamba_head_dim`` a head, B and C shared by the
  heads of a group) plus ``D x``; ``y * silu(z)`` through an RMSNorm over
  each of the ``n_groups`` groups of channels (one weight of
  ``d_inner``); ``W_out``. The whole mixer is ONE rematerialised unit.
- ``*``: ``laguna.LagunaAttention`` with no positions and no gate,
  causal, ``ops.banded_attention`` (on a TPU the splash kernel).
- ``E``: ``parallel.moe.RoutedExpertsFFN`` with ``scoring="sigmoid"``
  (``n_group`` 1: no group limit) and ``activation="relu2"``: every
  expert and the shared one ``down(relu(up x)^2)``.

Scope names in a traced program: ``layers/<i>/mamba`` around the whole
mixer, ``.../mamba/conv`` around the filter, its bias and its SiLU,
``.../mamba/scan`` around the SSD and the ``D`` skip,
``.../mamba/norm`` around the gated norm; ``layers/<i>/attn/full``
around the attention products and the softmax; under
``layers/<i>/moe``: ``route``, ``dispatch``, ``experts``, ``combine``,
``shared``. Every traced mixer asks ``ops.ssd.default_backend`` and
bumps ``ssd_traced_total.kernel`` (on a TPU at heads of whole lane
widths: the Pallas pair ``ssd_fwd`` / ``ssd_bwd``) or ``.chunked`` (the
XLA scan), every traced attention block
``attention_traced_total.kernel`` or ``.dense``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import invoke
from ..ops.short_conv import _filtered
from ..ops.ssd import count_traced as count_ssd
from ..ops.ssd import default_backend as ssd_backend
from ..ops.ssd import ssd_packed
from ..parallel.moe import RoutedExpertsFFN
from .laguna import DecoderLayer, LagunaAttention, LMHead, RMSNorm

__all__ = ["NemotronHMamba", "NemotronHLM"]


def _gated_group_norm(y, z, weight, groups, eps):
    """``y * silu(z)`` (B, T, C) through an RMSNorm over each of
    ``groups`` groups of channels, times ``weight`` (C,); float32
    inside."""
    f32 = jnp.float32
    h = y.astype(f32) * jax.nn.silu(z.astype(f32))
    g = h.reshape(h.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return (g.reshape(h.shape) * weight.astype(f32)).astype(y.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "state",
                                             "eps", "backend"))
def _mamba_mixer(x, in_proj, conv_weight, conv_bias, dt_bias, a_log, d,
                 norm_weight, out_proj, *, heads, groups, state, eps,
                 backend):
    """The whole mixer on its input ``x`` (B, T, C) and its leaves, ONE
    rematerialised unit: the backward pass keeps ``x`` alone and
    rebuilds the projections, the filter and the SSD from it, once.
    Kept instead, a layer's projections and the XLA scan's chunk
    products are about 1.5 GB at 8192 tokens. ``backend``: the SSD's
    (``ops.ssd.ssd_packed``), which reads x, B and C where the filter
    leaves them."""

    @jax.checkpoint
    def unit(x, in_proj, conv_weight, conv_bias, dt_bias, a_log, d,
             norm_weight, out_proj):
        f32 = jnp.float32
        inner = norm_weight.shape[0]
        width = conv_weight.shape[0]
        # the projection's three parts by the rows of its weight, which
        # start on whole tiles: no slice of the (B, T, 10304) activation
        z = jnp.matmul(x, in_proj[:inner].T)
        xbc = jnp.matmul(x, in_proj[inner:inner + width].T)
        dt = jnp.matmul(x, in_proj[inner + width:].T)
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(_filtered(xbc.astype(f32),
                                        conv_weight.astype(f32))
                              + conv_bias.astype(f32)).astype(x.dtype)
        with jax.named_scope("scan"):
            y = ssd_packed(
                xbc, jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
                -jnp.exp(a_log.astype(f32)), groups, state, backend)
            skip = jnp.repeat(d.astype(f32), inner // heads)
            y = (y.astype(f32) + skip * xbc[..., :inner].astype(f32)
                 ).astype(x.dtype)
        with jax.named_scope("norm"):
            y = _gated_group_norm(y, z, norm_weight, groups, eps)
        return jnp.matmul(y, out_proj.T)

    return unit(x, in_proj, conv_weight, conv_bias, dt_bias, a_log, d,
                norm_weight, out_proj)


class NemotronHMamba(HybridBlock):
    """The Mamba-2 mixer: ``num_heads`` heads of ``head_dim``, a state of
    ``state_size`` a head, ``groups`` groups of B and C, filters of
    ``kernel`` taps with a bias. Its leaves are its own parameters
    (``in_proj_weight`` (out, in) as ``nn.Dense`` holds it, ``conv_weight``
    (channels, taps), ``conv_bias``, ``dt_bias``, ``A_log``, ``D``,
    ``norm_weight``, ``out_proj_weight``), so that the mixer is one
    function of its input."""

    def __init__(self, units, num_heads, head_dim, state_size, groups,
                 kernel=4, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        inner = num_heads * head_dim
        width = inner + 2 * groups * state_size
        self._static = dict(heads=num_heads, groups=groups, state=state_size,
                            eps=eps)
        self._head_dim = head_dim
        shapes = {"in_proj_weight": ((inner + width + num_heads, units), None),
                  "conv_weight": ((width, kernel), None),
                  "conv_bias": ((width,), "zeros"),
                  "dt_bias": ((num_heads,), "zeros"),
                  "A_log": ((num_heads,), "zeros"),
                  "D": ((num_heads,), "ones"),
                  "norm_weight": ((inner,), "ones"),
                  "out_proj_weight": ((units, inner), None)}
        with self.name_scope():
            for name, (shape, init) in shapes.items():
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init))

    def hybrid_forward(self, F, x, **leaves):
        static = self._static
        backend = ssd_backend(static["heads"], self._head_dim,
                              static["groups"], static["state"])
        count_ssd(backend)
        return invoke(
            functools.partial(_mamba_mixer, backend=backend, **static),
            [x] + [leaves[name] for name in (
                "in_proj_weight", "conv_weight", "conv_bias", "dt_bias",
                "A_log", "D", "norm_weight", "out_proj_weight")])


# the block a character of ``hybrid_override_pattern`` stands for, under
# its attribute name
_BLOCKS = {"M": "mamba", "*": "attn", "E": "moe"}

# what ``from_config`` builds, key by key: any other value would be a
# guess at a mechanism the family does not write down
_BUILT_WITH = {
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False,
    "use_bias": False, "use_conv_bias": True, "tie_word_embeddings": False,
    "residual_in_fp32": False, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "n_shared_experts": 1, "sliding_window": None,
    "moe_latent_size": None, "num_nextn_predict_layers": 0,
    "mtp_hybrid_override_pattern": None,
}


class NemotronHLM(HybridBlock):
    """The decoder over token ids (B, T) -> float32 logits (B, T, V).

    ``pattern`` holds one character a layer (``M``, ``*`` or ``E``).
    ``experts_held`` is the range of the ``num_experts_routed`` experts
    this chip holds."""

    def __init__(self, vocab_size, units, pattern, num_heads, num_kv_heads,
                 head_dim, mamba_heads, mamba_head_dim, state_size, groups,
                 moe_hidden_size, shared_hidden_size, num_experts_routed,
                 experts_held, num_experts_per_tok, routed_scaling,
                 conv_kernel=4, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i, kind in enumerate(pattern):
                    if kind not in _BLOCKS:
                        raise ValueError(
                            "hybrid_override_pattern holds 'M', '*' and "
                            f"'E', got {kind!r} at layer {i}")
                    name = _BLOCKS[kind]
                    if kind == "M":
                        make = functools.partial(
                            NemotronHMamba, units, mamba_heads,
                            mamba_head_dim, state_size, groups, conv_kernel,
                            eps, prefix=name + "_")
                    elif kind == "*":
                        make = functools.partial(
                            LagunaAttention, units, num_heads, num_kv_heads,
                            head_dim, None, gated=False, prefix=name + "_")
                    else:
                        make = functools.partial(
                            RoutedExpertsFFN, units, moe_hidden_size,
                            num_experts_routed, num_experts_per_tok,
                            experts_held, routed_scaling, shared_hidden_size,
                            label=f"layers.{i}", scoring="sigmoid",
                            activation="relu2", prefix=name + "_")
                    self.layers.add(DecoderLayer(units, eps, (name, make),
                                                 norms=("norm",)))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.head = LMHead(vocab_size, units, prefix="head_")

    @classmethod
    def from_config(cls, config, **kwargs):
        """The net of a ``config.json``-shaped dict. ``n_routed_experts``
        counts the experts held here where ``deployment`` gives
        ``experts_held`` (start, stop) and ``num_experts_published``;
        without a ``deployment`` the layer holds all it routes over.
        Raises, naming the key, on every value it would have to guess
        at (``_BUILT_WITH``; a pattern of another length than
        ``num_hidden_layers`` or with a block other than ``M``, ``*``,
        ``E``; a clamped step size)."""
        for key, built in _BUILT_WITH.items():
            if config.get(key, built) != built:
                raise ValueError(
                    f"nemotron_h is built with {key} = {built!r}, got "
                    f"{config[key]!r}")
        pattern = config["hybrid_override_pattern"]
        if len(pattern) != config["num_hidden_layers"] \
                or set(pattern) - set(_BLOCKS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: one of 'M', '*', 'E' "
                f"a layer for num_hidden_layers = "
                f"{config['num_hidden_layers']}")
        limit = config.get("time_step_limit", [0.0, None])
        if limit[0] or limit[1] not in (None, float("inf")):
            raise ValueError(f"time_step_limit {limit!r}: the step size is "
                             "built unclamped")
        deployment = config.get("deployment", {})
        start, stop = deployment.get("experts_held",
                                     (0, config["n_routed_experts"]))
        return cls(
            vocab_size=config["vocab_size"], units=config["hidden_size"],
            pattern=pattern, num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            mamba_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            state_size=config["ssm_state_size"], groups=config["n_groups"],
            moe_hidden_size=config["moe_intermediate_size"],
            shared_hidden_size=config["moe_shared_expert_intermediate_size"],
            num_experts_routed=deployment.get("num_experts_published",
                                              config["n_routed_experts"]),
            experts_held=range(start, stop),
            num_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling=config["routed_scaling_factor"],
            conv_kernel=config["conv_kernel"],
            eps=config["layer_norm_epsilon"], **kwargs)

    def hybrid_forward(self, F, tokens):
        return self.head(self.norm(self.layers(self.embed(tokens))))
