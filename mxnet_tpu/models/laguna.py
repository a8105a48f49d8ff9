"""Laguna decoder family: window and full attention mixed, per-layer head
counts, a per-head output gate, a dense leading FFN and top-k routed
experts with a shared expert.

The blocks follow the published ``config.json`` of ``poolside/Laguna-XS.2``
(``model_type: laguna``) key by key; ``LagunaLM.from_config`` builds the
net from such a dict. Per layer ``x + attn(norm(x))`` then
``x + mlp(norm(x))``, a final RMSNorm, an untied head.

- ``RMSNorm``: float32 statistics and weight, the activation's dtype
  out.
- ``LagunaAttention``: grouped-query attention whose number of query
  heads is a per-layer argument, rotary positions by the layer's kind
  (``rope_parameters["sliding_attention"]`` / ``["full_attention"]``:
  default or yarn, over the first ``partial_rotary_factor`` of a head's
  dimensions), causal, keys no further back than ``window - 1``
  positions on sliding layers (``ops.banded_attention``: blocked, work
  in proportion to the band), each head's output times
  ``sigmoid(x W_g)[h]``, then ``o_proj``. No bias anywhere.
- the FFN of a layer is ``parallel.moe.GatedFFN`` (``mlp``) where
  ``mlp_layer_types`` says ``dense`` and
  ``parallel.moe.RoutedExpertsFFN`` (``moe``) where it says ``sparse``:
  the layer is told which of the ``num_experts_routed`` experts it
  holds. Of the expert layer's three scoring rules this family uses
  ``"softmax"`` (the default: softmax over all the router's outputs, the
  k largest renormalised, times the scaling factor, with a shared
  expert); ``"sigmoid"`` with a selection bias is ``models/lfm2.py``'s,
  the same limited to a token's best groups ``models/ling.py``'s.
- ``DecoderLayer`` is the pre-norm residual skeleton the three decoder
  families here build their layers from; ``RMSNorm``, ``LMHead`` and
  the rotation are shared with ``models/lfm2.py`` (``rotary_tables``
  too) and ``models/ling.py`` (the per-head gate too).

Scope names in a traced program (``jax.named_scope`` under the blocks'
attribute names): ``layers/<i>/attn/window`` or ``.../attn/full``
around the two attention products and the softmax (a sliding layer that
the band kernel of ``ops.banded_attention`` takes has its rotary
positions and its gate inside the kernels, so under ``window`` too);
under
``layers/<i>/moe``: ``route``, ``dispatch``, ``experts``, ``combine``,
``shared``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import invoke
from ..ops.banded_attention import (banded_attention,
                                    banded_attention_token_major,
                                    default_backend)
from ..ops.pallas_kernels import count_traced
from ..parallel.moe import GatedFFN, RoutedExpertsFFN

__all__ = ["RMSNorm", "LagunaAttention", "DecoderLayer",
           "LagunaDecoderLayer", "LMHead", "LagunaLM", "rotary_tables"]


def _yarn_inverse_frequencies(dim, rope):
    """Yarn's blend of interpolated and extrapolated frequencies, as
    ``transformers`` computes it (``_compute_yarn_parameters``)."""
    base, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]
    pos_freqs = base ** (onp.arange(0, dim, 2, dtype=onp.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = onp.clip((onp.arange(dim // 2, dtype=onp.float64) - low)
                    / (high - low), 0, 1)
    return interpolation * ramp + extrapolation * (1 - ramp)


def rotary_tables(positions, head_dim, rope):
    """``(cos, sin, turn)`` for one kind of layer (``rope`` is its entry
    of ``rope_parameters``): ``cos`` and ``sin`` (positions, head_dim)
    float32, one and zero on the dimensions past the rotary ones;
    ``turn`` (head_dim, head_dim), the signed permutation with
    ``x @ turn`` = rotate-half of ``x``'s rotary dimensions (zero past
    them)."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    if rope.get("rope_type", "default") == "yarn":
        inv_freq = _yarn_inverse_frequencies(dim, rope)
        factor = rope.get("attention_factor")
        if factor is None:
            factor = 0.1 * math.log(rope["factor"]) + 1.0
    else:
        inv_freq = 1.0 / rope["rope_theta"] ** (
            onp.arange(0, dim, 2, dtype=onp.float64) / dim)
        factor = 1.0
    angles = onp.arange(positions, dtype=onp.float64)[:, None] \
        * inv_freq[None, :]
    angles = onp.concatenate([angles, angles], axis=-1)
    cos = onp.ones((positions, head_dim), onp.float32)
    sin = onp.zeros((positions, head_dim), onp.float32)
    cos[:, :dim] = onp.cos(angles) * factor
    sin[:, :dim] = onp.sin(angles) * factor
    turn = onp.zeros((head_dim, head_dim), onp.float32)
    half = dim // 2
    for j in range(half):
        turn[j + half, j] = -1.0      # (x @ turn)[j] = -x[j + half]
        turn[j, j + half] = 1.0       # (x @ turn)[j + half] = x[j]
    return cos, sin, turn


@jax.checkpoint
def _rotate(x, cos, sin, turn):
    """Rotary positions on every head of ``x`` (B, T, H, D), rotate-half
    layout: ``x * cos + rotate_half(x) * sin`` in ``x``'s dtype, the
    tables cast to it (as ``transformers`` applies them). The rotation
    of the halves is a product with the signed permutation ``turn``
    (exact in any dtype), so that nothing slices or joins the minor
    dimension, which on a TPU costs a relayout of the whole array."""
    # one pass of the matrix unit is exact on bfloat16 (every product
    # is a value or its negative); float32 needs the full precision
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    turned = jnp.einsum("bthd,de->bthe", x, turn.astype(x.dtype),
                        precision=exact)
    return x * cos.astype(x.dtype)[None, :, None, :] \
        + turned * sin.astype(x.dtype)[None, :, None, :]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "head_dim", "window",
                                             "backend"))
def _gated_attention(q, k, v, gate, cos, sin, turn, *, heads, kv_heads,
                     head_dim, window, backend=None):
    """Projected ``q`` (B, T, H * D), ``k`` / ``v`` (B, T, Hkv * D) and
    gate logits (B, T, H) to the gated heads' outputs (B, T, H * D);
    ``backend`` as ``ops.banded_attention`` names them (None: its
    rule)."""
    b, t, _ = q.shape
    q = q.reshape(b, t, heads, head_dim)
    k = k.reshape(b, t, kv_heads, head_dim)
    v = v.reshape(b, t, kv_heads, head_dim)
    if window is not None and backend in ("band", "band_interpret"):
        # the band kernel reads a head where the projection left it,
        # and turns, scales and gates in the same pass
        with jax.named_scope("window"):
            o = banded_attention_token_major(
                q, k, v, window=window, backend=backend,
                rotary=(cos, sin, turn), gate=gate)
        return o.reshape(b, t, heads * head_dim)
    q, k = _rotate(q, cos, sin, turn), _rotate(k, cos, sin, turn)
    with jax.named_scope("full" if window is None else "window"):
        o = banded_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), window=window,
                             backend=backend)
    o = _gate_heads(o.transpose(0, 2, 1, 3), gate)      # (B, T, H, D)
    return o.reshape(b, t, heads * head_dim)


@jax.checkpoint
def _gate_heads(o, gate):
    """Each head's output (B, T, H, D) times the sigmoid of its gate
    logit (B, T, H), in float32; rematerialised in the backward pass."""
    return (o.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
            ).astype(o.dtype)


@functools.partial(jax.jit, static_argnames="eps")
def _rms_norm(x, weight, eps):
    @jax.checkpoint
    def norm(x, weight):
        # rematerialised in the backward pass: the float32 copy of the
        # activation is not kept
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                         keepdims=True) + eps)
        return (y * weight.astype(jnp.float32)).astype(x.dtype)

    return norm(x, weight)


class RMSNorm(HybridBlock):
    def __init__(self, units, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        return invoke(functools.partial(_rms_norm, eps=self._eps),
                      [x, weight])


def _dense(units, in_units, prefix):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units,
                    prefix=prefix)


class LagunaAttention(HybridBlock):
    """Grouped-query attention with ``num_heads`` query heads over
    ``num_kv_heads`` key/value heads, rotary positions from ``rope``,
    a sliding ``window`` (None: full causal attention) and a per-head
    sigmoid gate on the output."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 window=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be a multiple of "
                             "num_kv_heads")
        self._geometry = dict(heads=num_heads, kv_heads=num_kv_heads,
                              head_dim=head_dim, window=window)
        self._rope, self._head_dim = dict(rope), head_dim
        self._tables = {}
        with self.name_scope():
            self.q_proj = _dense(num_heads * head_dim, units, "q_proj_")
            self.k_proj = _dense(num_kv_heads * head_dim, units, "k_proj_")
            self.v_proj = _dense(num_kv_heads * head_dim, units, "v_proj_")
            self.g_proj = _dense(num_heads, units, "g_proj_")
            self.o_proj = _dense(units, num_heads * head_dim, "o_proj_")

    def _rotary(self, t):
        if t not in self._tables:
            self._tables[t] = rotary_tables(t, self._head_dim, self._rope)
        return self._tables[t]

    def hybrid_forward(self, F, x):
        t = x.shape[1]
        cos, sin, turn = self._rotary(t)
        geometry = self._geometry
        backend = default_backend(t, self._head_dim, geometry["window"],
                                  geometry["heads"] // geometry["kv_heads"])
        count_traced({"band": "band", "splash": "kernel",
                      "xla": "dense"}[backend.split("_")[0]])
        fn = functools.partial(_gated_attention, cos=cos, sin=sin,
                               turn=turn, backend=backend, **geometry)
        out = invoke(fn, [self.q_proj(x), self.k_proj(x), self.v_proj(x),
                          self.g_proj(x)])
        return self.o_proj(out)


class LMHead(HybridBlock):
    """The output head: float32 logits whatever the weight's dtype (the
    product accumulates in float32 and is not rounded on the way out)."""

    def __init__(self, vocab_size, units, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight",
                                          shape=(vocab_size, units),
                                          init=None)

    def hybrid_forward(self, F, x, weight):
        return invoke(lambda a, w: jnp.einsum(
            "btc,vc->btv", a, w, preferred_element_type=jnp.float32),
            [x, weight])


class DecoderLayer(HybridBlock):
    """The pre-norm residual layer of the three decoder families here:
    ``x + mixer(norm(x))`` then ``x + ffn(norm(x))``. ``mixer`` and
    ``ffn`` are ``(attribute name, maker)`` pairs, built under the
    layer's name scope and hung under those names (which name them in a
    traced program too); ``norms`` names the two RMSNorms."""

    def __init__(self, units, eps, mixer, ffn,
                 norms=("attn_norm", "mlp_norm"), **kwargs):
        super().__init__(**kwargs)
        self._halves = tuple(zip(norms, (mixer[0], ffn[0])))
        with self.name_scope():
            for norm, (name, make) in zip(norms, (mixer, ffn)):
                setattr(self, norm, RMSNorm(units, eps, prefix=norm + "_"))
                setattr(self, name, make())

    def hybrid_forward(self, F, x):
        for norm, name in self._halves:
            x = x + getattr(self, name)(getattr(self, norm)(x))
        return x


class LagunaDecoderLayer(DecoderLayer):
    """``make_attn`` / ``make_ffn`` build the layer's two halves under
    its name scope; a sparse FFN hangs under ``moe``, a dense one under
    ``mlp``."""

    def __init__(self, units, eps, make_attn, make_ffn, sparse, **kwargs):
        super().__init__(units, eps, ("attn", make_attn),
                         ("moe" if sparse else "mlp", make_ffn), **kwargs)


class LagunaLM(HybridBlock):
    """The decoder over token ids (B, T) -> float32 logits (B, T, V).

    ``layer_types`` / ``mlp_layer_types`` / ``heads_per_layer`` give one
    entry a layer (longer lists are read up to ``num_layers``).
    ``experts_held`` is the range of the ``num_experts_routed`` experts
    this chip holds."""

    def __init__(self, vocab_size, units, num_layers, layer_types,
                 mlp_layer_types, heads_per_layer, num_kv_heads, head_dim,
                 hidden_size, moe_hidden_size, shared_hidden_size,
                 num_experts_routed, experts_held, num_experts_per_tok,
                 routed_scaling, sliding_window, rope_parameters,
                 eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i in range(num_layers):
                    sliding = layer_types[i] == "sliding_attention"
                    sparse = mlp_layer_types[i] == "sparse"
                    make_attn = functools.partial(
                        LagunaAttention, units, heads_per_layer[i],
                        num_kv_heads, head_dim,
                        rope_parameters[layer_types[i]],
                        window=sliding_window if sliding else None,
                        prefix="attn_")
                    if sparse:
                        make_ffn = functools.partial(
                            RoutedExpertsFFN, units, moe_hidden_size,
                            num_experts_routed, num_experts_per_tok,
                            experts_held, routed_scaling,
                            shared_hidden_size, label=f"layers.{i}",
                            prefix="moe_")
                    else:
                        make_ffn = functools.partial(
                            GatedFFN, units, hidden_size, prefix="mlp_")
                    self.layers.add(LagunaDecoderLayer(
                        units, eps, make_attn, make_ffn, sparse))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.head = LMHead(vocab_size, units, prefix="head_")

    @classmethod
    def from_config(cls, config, **kwargs):
        """The net of a ``config.json``-shaped dict. ``num_experts``
        counts the experts held here where ``deployment`` gives
        ``experts_held`` (start, stop) and ``num_experts_published``;
        without a ``deployment`` the layer holds all it routes over."""
        deployment = config.get("deployment", {})
        start, stop = deployment.get("experts_held",
                                     (0, config["num_experts"]))
        n = config["num_hidden_layers"]
        heads = config.get("num_attention_heads_per_layer") \
            or [config["num_attention_heads"]] * n
        return cls(
            vocab_size=config["vocab_size"], units=config["hidden_size"],
            num_layers=n, layer_types=config["layer_types"],
            mlp_layer_types=config["mlp_layer_types"],
            heads_per_layer=heads,
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            hidden_size=config["intermediate_size"],
            moe_hidden_size=config["moe_intermediate_size"],
            shared_hidden_size=config["shared_expert_intermediate_size"],
            num_experts_routed=deployment.get("num_experts_published",
                                              config["num_experts"]),
            experts_held=range(start, stop),
            num_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling=config["moe_routed_scaling_factor"],
            sliding_window=config["sliding_window"],
            rope_parameters=config["rope_parameters"],
            eps=config["rms_norm_eps"], **kwargs)

    def hybrid_forward(self, F, tokens):
        return self.head(self.norm(self.layers(self.embed(tokens))))
