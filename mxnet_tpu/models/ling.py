"""Ling hybrid decoder family (``model_type: bailing_hybrid``): Kimi
delta-rule linear-attention mixers mixed with multi-head latent
attention, a dense leading FFN, then experts routed by sigmoid scores
with a selection bias inside a token's best groups, beside a shared
expert.

The blocks follow the published ``config.json`` of
``inclusionAI/Ling-3.0-flash`` key by key; ``LingHybridLM.from_config``
builds the net from such a dict and refuses what it would have to
guess. Per layer ``x + mixer(norm(x))`` then ``x + ffn(norm(x))``
(``laguna.DecoderLayer``), RMSNorm, a final RMSNorm, an untied head
(``laguna.LMHead``), no bias.

- ``LingKDA`` (every layer but each ``layer_group_size``-th): per head
  ``q = l2norm(silu(conv(x W_q)))``, ``k`` alike, ``v = silu(conv(x
  W_v))`` (``conv``: one causal ``short_conv_kernel_size``-tap filter a
  channel); ``beta = sigmoid(x W_b)`` a head; a decay a channel,
  ``log a = kda_lower_bound * sigmoid(exp(A_log)[h] * (x W_f +
  dt_bias))`` (the filters, norms and gate: ``ops.kda_operands``, on a
  TPU a Pallas kernel pair); the gated delta rule over a (head_dim x
  head_dim) state a head (``ops.kda``: on a TPU a Pallas kernel pair,
  else a chunked scan); an RMSNorm over each head's channels (one
  weight of ``head_dim``) times ``sigmoid(x W_g)[h]``;
  ``W_o``.
- ``LingLatentAttention`` (layers ``i`` with ``(i + 1) %
  layer_group_size == 0``): ``q = x W_q`` in heads of ``qk_nope_head_dim
  + qk_rope_head_dim``; ``c = x W_kva`` (``kv_lora_rank +
  qk_rope_head_dim``); keys' position-free parts and values rebuilt from
  the normed latent, ``[k_nope, v] = rmsnorm(c[:rank]) W_kvb``; one
  rotary key ``c[rank:]`` shared by all heads; rotary positions in
  interleaved pairs on the last ``qk_rope_head_dim`` of q and on the
  shared key; causal softmax of ``q . k / sqrt(192)`` in float32 times
  ``v`` (``ops.banded_attention``: on a TPU the splash kernel, the
  192-wide heads zero-padded to the lanes); the same per-head sigmoid
  gate; ``W_o``.
- the FFN of layer ``i`` is ``parallel.moe.GatedFFN`` (``mlp``) for
  ``i < first_k_dense_replace`` and ``parallel.moe.RoutedExpertsFFN``
  (``moe``) after, with ``scoring="sigmoid"``, ``groups=(n_group,
  topk_group)`` and a shared expert (the source's 1e-20 under the sum
  of a token's chosen scores is ``parallel.moe``'s one constant, 1e-6:
  under 2 ulp of float32 apart).

Scope names in a traced program: ``layers/<i>/kda`` around the whole
mixer, ``.../kda/mix`` around what turns the projections into the delta
rule's operands (the three filters, q's and k's norms, the decay's
gate, beta) and ``.../kda/scan`` around the delta rule alone, so that
what lies under ``kda`` and under neither is the seven projections and
the gated norm of the output; ``layers/<i>/attn/full`` around the
latent layer's two products and the softmax; under ``layers/<i>/moe``:
``route``, ``dispatch``, ``experts``, ``combine``, ``shared``. Every
traced mixer bumps ``kda_traced_total.kernel`` or ``.chunked`` (the
backend ``ops.kda.default_backend`` picks by shape) and
``kda_mix_traced_total.kernel`` or ``.xla`` (``ops.kda_operands``'),
every traced latent block ``attention_traced_total.kernel`` or
``.dense``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import invoke
from ..ops.banded_attention import banded_attention, default_backend
from ..ops.kda import count_traced as count_kda
from ..ops.kda import default_backend as kda_backend
from ..ops.kda import kda
from ..ops.kda_operands import count_traced as count_mix
from ..ops.kda_operands import default_backend as mix_backend
from ..ops.kda_operands import kda_operands
from ..ops.pallas_kernels import count_traced
from ..parallel.moe import GatedFFN, RoutedExpertsFFN
from .laguna import (DecoderLayer, LMHead, RMSNorm, _dense, _gate_heads,
                     _rotate)

__all__ = ["LingKDA", "LingLatentAttention", "LingDecoderLayer",
           "LingHybridLM"]

def _gated_head_norm(o, gate, weight, eps):
    """Each head's output (B, T, H, D) through an RMSNorm over its
    channels (one ``weight`` (D,)), times the sigmoid of its gate logit
    (B, T, H); float32 inside."""
    f32 = jnp.float32
    o32 = o.astype(f32)
    y = o32 * jax.lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1,
                                     keepdims=True) + eps)
    return (y * weight.astype(f32)
            * jax.nn.sigmoid(gate.astype(f32))[..., None]).astype(o.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "lower_bound", "eps",
                                             "backend", "mix"))
def _kda_mixer(x, w_q, w_k, w_v, w_f, w_b, w_g, w_o, q_filter, k_filter,
               v_filter, a_log, dt_bias, norm_weight, *, heads, lower_bound,
               eps, backend, mix):
    """The whole mixer on its input ``x`` (B, T, C) and its leaves, ONE
    rematerialised unit: the backward pass keeps ``x`` alone and
    rebuilds the seven projections, the delta rule's operands (the
    float32 decay among them) and the operator's chunks from it, once.
    ``mix`` is ``ops.kda_operands``' backend, ``backend`` ``ops.kda``'s.
    Kept instead, a layer's projections, operands and outputs are 0.4 GB
    at 4096 tokens, and six layers of them do not fit beside the step's
    state (PERF.md section 6, PR 35)."""

    @jax.checkpoint
    def unit(x, w_q, w_k, w_v, w_f, w_b, w_g, w_o, q_filter, k_filter,
             v_filter, a_log, dt_bias, norm_weight):
        def project(w):
            return jnp.matmul(x, w.T)

        b, t, _ = x.shape
        *projected, beta = [project(w) for w in (w_q, w_k, w_v, w_f, w_b)]
        with jax.named_scope("mix"):
            operands = [a.reshape(b, t, heads, -1) for a in kda_operands(
                *projected, q_filter, k_filter, v_filter, a_log, dt_bias,
                heads=heads, lower_bound=lower_bound, backend=mix)]
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        with jax.named_scope("scan"):
            o = kda(*operands, beta, backend=backend)
        o = _gated_head_norm(o, project(w_g), norm_weight, eps)
        return jnp.matmul(o.reshape(x.shape[:2] + (-1,)), w_o.T)

    return unit(x, w_q, w_k, w_v, w_f, w_b, w_g, w_o, q_filter, k_filter,
                v_filter, a_log, dt_bias, norm_weight)


class LingKDA(HybridBlock):
    """The Kimi-delta mixer: ``num_heads`` heads of ``head_dim`` for
    keys and values alike, filters of ``kernel`` taps, the decay's
    logarithm bounded below by ``lower_bound``. Its seven projections
    are its own parameters (``q_proj_weight`` .. ``o_proj_weight``,
    (out, in) as ``nn.Dense`` holds them), so that the mixer is one
    function of its input."""

    PROJECTIONS = ("q_proj", "k_proj", "v_proj", "f_proj", "b_proj",
                   "g_proj", "o_proj")

    def __init__(self, units, num_heads, head_dim, kernel=4,
                 lower_bound=-5.0, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        width = num_heads * head_dim
        self._static = dict(heads=num_heads, lower_bound=float(lower_bound),
                            eps=eps)
        self._head_dim = head_dim
        shapes = dict.fromkeys(self.PROJECTIONS[:4], (width, units))
        shapes.update(b_proj=(num_heads, units), g_proj=(num_heads, units),
                      o_proj=(units, width))
        with self.name_scope():
            for name in self.PROJECTIONS:
                setattr(self, name + "_weight", self.params.get(
                    name + "_weight", shape=shapes[name], init=None))
            for name in ("q_filter", "k_filter", "v_filter"):
                setattr(self, name, self.params.get(
                    name, shape=(width, kernel), init=None))
            self.A_log = self.params.get("A_log", shape=(num_heads,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(width,),
                                           init="zeros")
            self.o_norm_weight = self.params.get(
                "o_norm_weight", shape=(head_dim,), init="ones")

    def hybrid_forward(self, F, x, q_filter, k_filter, v_filter, A_log,
                       dt_bias, o_norm_weight, **projections):
        backend = kda_backend(x.shape[1], self._head_dim, self._head_dim)
        mix = mix_backend(x.shape[1], self._head_dim)
        count_kda(backend)
        count_mix(mix)
        return invoke(
            functools.partial(_kda_mixer, backend=backend, mix=mix,
                              **self._static),
            [x] + [projections[name + "_weight"]
                   for name in self.PROJECTIONS]
            + [q_filter, k_filter, v_filter, A_log, dt_bias, o_norm_weight])


def _interleaved_tables(positions, nope, rope, theta):
    """``(cos, sin, turn)`` for a head of ``nope`` position-free
    dimensions followed by ``rope`` rotary ones in interleaved pairs
    (dimensions 2i and 2i + 1 turn together by ``position *
    theta^(-2i / rope)``): ``cos`` / ``sin`` (positions, nope + rope)
    float32, one and zero on the first ``nope``; ``turn`` the signed
    permutation with ``(x @ turn)[2i] = -x[2i + 1]`` and ``(x @
    turn)[2i + 1] = x[2i]`` on the rotary dimensions, zero elsewhere."""
    d = nope + rope
    inv_freq = 1.0 / theta ** (onp.arange(0, rope, 2, dtype=onp.float64)
                               / rope)
    angles = onp.repeat(onp.arange(positions, dtype=onp.float64)[:, None]
                        * inv_freq[None, :], 2, axis=-1)
    cos = onp.ones((positions, d), onp.float32)
    sin = onp.zeros((positions, d), onp.float32)
    cos[:, nope:], sin[:, nope:] = onp.cos(angles), onp.sin(angles)
    turn = onp.zeros((d, d), onp.float32)
    for j in range(nope, d, 2):
        turn[j + 1, j] = -1.0
        turn[j, j + 1] = 1.0
    return cos, sin, turn


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _latent_keys(kv, k_rope, heads, nope):
    """Keys (B, T, H, nope + rope) and values (B, T, H, v) from the
    rebuilt ``kv`` (B, T, H * (nope + v)) and the turned rotary key
    (B, T, 1, rope), which every head shares."""
    b, t, _, rope = k_rope.shape
    kv = kv.reshape(b, t, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, rope))],
        axis=-1)
    return k, kv[..., nope:]


@functools.partial(jax.jit, static_argnames=("heads", "nope", "backend"))
def _latent_attention(q, kv, k_rope, gate, cos, sin, turn, *, heads, nope,
                      backend):
    """Projected ``q`` (B, T, H * (nope + rope)), rebuilt ``kv``
    (B, T, H * (nope + v)), the shared rotary key (B, T, rope) and gate
    logits (B, T, H) to the gated heads' outputs (B, T, H * v)."""
    b, t, _ = q.shape
    q = _rotate(q.reshape(b, t, heads, -1), cos, sin, turn)
    k_rope = _rotate(k_rope[:, :, None, :], cos[:, nope:], sin[:, nope:],
                     turn[nope:, nope:])
    k, v = _latent_keys(kv, k_rope, heads, nope)
    with jax.named_scope("full"):
        o = banded_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), backend=backend)
    o = _gate_heads(o.transpose(0, 2, 1, 3), gate)
    return o.reshape(b, t, -1)


class LingLatentAttention(HybridBlock):
    """Multi-head latent attention without a query latent: ``num_heads``
    heads whose scores are ``nope_dim + rope_dim`` wide and whose values
    are ``v_dim`` wide, keys and values rebuilt from one normed latent of
    ``kv_rank`` a token, one rotary key of ``rope_dim`` for all heads,
    a per-head sigmoid gate on the output."""

    def __init__(self, units, num_heads, nope_dim, rope_dim, v_dim, kv_rank,
                 rope_theta, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._shape = (num_heads, nope_dim, rope_dim, v_dim, kv_rank)
        self._theta = rope_theta
        self._tables = {}
        with self.name_scope():
            self.q_proj = _dense(num_heads * (nope_dim + rope_dim), units,
                                 "q_proj_")
            self.kv_a_proj = _dense(kv_rank + rope_dim, units, "kv_a_proj_")
            self.kv_norm = RMSNorm(kv_rank, eps, prefix="kv_norm_")
            self.kv_b_proj = _dense(num_heads * (nope_dim + v_dim), kv_rank,
                                    "kv_b_proj_")
            self.g_proj = _dense(num_heads, units, "g_proj_")
            self.o_proj = _dense(units, num_heads * v_dim, "o_proj_")

    def hybrid_forward(self, F, x):
        t = x.shape[1]
        heads, nope, rope, v_dim, rank = self._shape
        if t not in self._tables:
            self._tables[t] = _interleaved_tables(t, nope, rope, self._theta)
        cos, sin, turn = self._tables[t]
        backend = default_backend(t, nope + rope, dv=v_dim)
        count_traced("kernel" if backend == "splash" else "dense")
        latent = self.kv_a_proj(x)
        kv = self.kv_b_proj(self.kv_norm(
            latent.slice_axis(axis=-1, begin=0, end=rank)))
        out = invoke(functools.partial(
            _latent_attention, cos=cos, sin=sin, turn=turn, heads=heads,
            nope=nope, backend=backend),
            [self.q_proj(x), kv,
             latent.slice_axis(axis=-1, begin=rank, end=rank + rope),
             self.g_proj(x)])
        return self.o_proj(out)


class LingDecoderLayer(DecoderLayer):
    """``make_mixer`` builds the mixer (under ``kda`` or ``attn``),
    ``make_ffn`` the FFN (``mlp`` where dense, ``moe`` where routed)."""

    def __init__(self, units, eps, make_mixer, latent, make_ffn, sparse,
                 **kwargs):
        super().__init__(units, eps,
                         ("attn" if latent else "kda", make_mixer),
                         ("moe" if sparse else "mlp", make_ffn), **kwargs)


# what ``from_config`` builds, key by key: any other value would be a
# guess at a mechanism the family does not write down
_BUILT_WITH = {
    "q_lora_rank": None, "use_kda_lora": False, "no_kda_lora": True,
    "num_nextn_predict_layers": 0, "value_norm": False,
    "up_proj_norm": False, "use_nGPT": False, "scale_router_input": False,
    "topk_method": "noaux_tc", "score_function": "sigmoid",
    "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
    "num_shared_experts": 1, "kda_safe_gate": True, "linear_silu": True,
    "rope_interleave": True, "rope_scaling": None, "use_bias": False,
    "use_qkv_bias": False, "tie_word_embeddings": False,
    "group_norm_size": 1, "hidden_act": "silu",
    "gated_attention_proj_granularity_type": "head_wise",
}


class LingHybridLM(HybridBlock):
    """The decoder over token ids (B, T) -> float32 logits (B, T, V).

    Layer ``i`` mixes by latent attention where ``(i + 1) % group_size
    == 0`` and by the delta rule elsewhere; the first ``num_dense_layers``
    FFNs are dense. ``experts_held`` is the range of the
    ``num_experts_routed`` experts this chip holds; ``groups`` the
    router's ``(n_group, topk_group)``."""

    def __init__(self, vocab_size, units, num_layers, group_size,
                 num_dense_layers, num_heads, head_dim, nope_dim, rope_dim,
                 v_dim, kv_rank, hidden_size, moe_hidden_size,
                 shared_hidden_size, num_experts_routed, experts_held,
                 num_experts_per_tok, groups, routed_scaling, rope_theta,
                 conv_kernel=4, lower_bound=-5.0, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i in range(num_layers):
                    latent = (i + 1) % group_size == 0
                    sparse = i >= num_dense_layers
                    if latent:
                        make_mixer = functools.partial(
                            LingLatentAttention, units, num_heads, nope_dim,
                            rope_dim, v_dim, kv_rank, rope_theta, eps,
                            prefix="attn_")
                    else:
                        make_mixer = functools.partial(
                            LingKDA, units, num_heads, head_dim,
                            conv_kernel, lower_bound, eps, prefix="kda_")
                    if sparse:
                        make_ffn = functools.partial(
                            RoutedExpertsFFN, units, moe_hidden_size,
                            num_experts_routed, num_experts_per_tok,
                            experts_held, routed_scaling,
                            shared_hidden_size, label=f"layers.{i}",
                            scoring="sigmoid", groups=groups,
                            prefix="moe_")
                    else:
                        make_ffn = functools.partial(
                            GatedFFN, units, hidden_size, prefix="mlp_")
                    self.layers.add(LingDecoderLayer(
                        units, eps, make_mixer, latent, make_ffn, sparse))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.head = LMHead(vocab_size, units, prefix="head_")

    @classmethod
    def from_config(cls, config, **kwargs):
        """The net of a ``config.json``-shaped dict. ``num_experts``
        counts the experts held here where ``deployment`` gives
        ``experts_held`` (start, stop) and ``num_experts_published``;
        without a ``deployment`` the layer holds all it routes over.
        Raises on every key whose value would make it guess
        (``_BUILT_WITH``; a nonzero SwiGLU limit among the layers kept;
        key/value heads of the delta rule other than its query
        heads)."""
        for key, built in _BUILT_WITH.items():
            if key in config and config[key] != built:
                raise ValueError(
                    f"bailing_hybrid is built with {key} = {built!r}, "
                    f"got {config[key]!r}")
        n = config["num_hidden_layers"]
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            if any(config.get(key, [])[:n]):
                raise ValueError(f"{key} is nonzero among the {n} layers "
                                 "kept: the clamp's form is not written "
                                 "down here")
        heads = config["num_attention_heads"]
        if config.get("num_kv_heads_for_linear_attn", 0) not in (0, heads):
            raise ValueError("num_kv_heads_for_linear_attn: the delta rule "
                             "is built with as many key/value heads as "
                             "query heads")
        deployment = config.get("deployment", {})
        start, stop = deployment.get("experts_held",
                                     (0, config["num_experts"]))
        return cls(
            vocab_size=config["vocab_size"], units=config["hidden_size"],
            num_layers=n, group_size=config["layer_group_size"],
            num_dense_layers=config["first_k_dense_replace"],
            num_heads=heads, head_dim=config["head_dim"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            hidden_size=config["intermediate_size"],
            moe_hidden_size=config["moe_intermediate_size"],
            shared_hidden_size=config[
                "moe_shared_expert_intermediate_size"],
            num_experts_routed=deployment.get("num_experts_published",
                                              config["num_experts"]),
            experts_held=range(start, stop),
            num_experts_per_tok=config["num_experts_per_tok"],
            groups=(config["n_group"], config["topk_group"]),
            routed_scaling=config["routed_scaling_factor"],
            rope_theta=config["rope_theta"],
            conv_kernel=config["short_conv_kernel_size"],
            lower_bound=config["kda_lower_bound"],
            eps=config["rms_norm_eps"], **kwargs)

    def hybrid_forward(self, F, tokens):
        return self.head(self.norm(self.layers(self.embed(tokens))))
