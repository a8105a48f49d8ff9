"""Keye-VL-2.0 language model (``model_type: KeyeVL2``): grouped-query
attention over the keys a learned lightning indexer selects, and experts
routed by softmax scores, in every layer.

The blocks follow the published ``config.json`` of
``Kwai-Keye/Keye-VL-2.0-30B-A3B`` key by key; ``KeyeLM.from_config``
builds the text decoder from such a dict and refuses, by name, what it
would have to guess (the vision tower among them). Layer ``i`` is
``x + attn(norm(x))`` then ``x + moe(norm(x))`` (``laguna.DecoderLayer``:
``mlp_only_layers`` is empty and ``decoder_sparse_step`` 1, so every
layer routes and ``intermediate_size`` builds nothing), a final RMSNorm,
an untied head (``laguna.LMHead``). No bias but the indexer's key norm's.

- ``KeyeAttention``: ``q``, ``k``, ``v`` projections, each head of q and
  k through an RMSNorm over the head (``models/lfm2.py``'s), rotate-half
  rotary positions over the whole head (``laguna.rotary_tables`` at
  ``rope_theta``; text positions: the three streams of
  ``mrope_section`` are equal), then ``ops.sparse_attention`` over the
  ``sa_config.topk`` keys the indexer selects, then ``o_proj``.
- ``KeyeIndexer`` (DeepSeek-V3.2's lightning indexer at ``sa_config``'s
  widths), on the layer's normed input with its gradient stopped:
  ``qi = x W_q`` (``indexer_num_heads`` heads of ``indexer_head_dim``),
  ``ki = LayerNorm(x W_k)`` (one key head, a weight and a bias),
  ``w = x W_w / sqrt(indexer_num_heads)``, rotary positions on the
  first half of ``qi``'s and ``ki``'s dimensions, the rest plain. It
  is trained by the KL loss the sparse attention's backward pass gives
  it and by nothing else.
- the expert layer is ``parallel.moe.RoutedExpertsFFN`` under its
  softmax rule (softmax over all the router's outputs, the k largest
  renormalised, scaling 1, no shared expert), SiLU-gated experts.

Scope names in a traced program: ``layers/<i>/attn/indexer`` around the
indexer's projections, its norm and rotation, and (inside the sparse
attention) its scores, the top-k, the selection and the KL gradient;
``layers/<i>/attn/sparse`` around the attention products, the softmax
and their backward; under ``layers/<i>/moe``: ``route``, ``dispatch``,
``experts``, ``combine``. Every traced attention block bumps
``sparse_attention_traced_total.xla``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import invoke
from ..ops.sparse_attention import count_traced, sparse_attention
from ..parallel.moe import RoutedExpertsFFN
from .laguna import DecoderLayer, LMHead, RMSNorm, _dense, _rotate, \
    rotary_tables

__all__ = ["KeyeIndexer", "KeyeAttention", "KeyeLM"]

# the share of an indexer head's dimensions that turn with the position,
# the first ones (DeepSeek-V3.2's rotary and plain parts, 64 of 128)
INDEXER_ROTARY_SHARE = 0.5


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps"))
def _indexer(x, q_proj, k_proj, norm_weight, norm_bias, w_proj, cos, sin,
             turn, *, heads, head_dim, eps):
    """The indexer's queries (B, T, Hi, Di), key (B, T, Di) and head
    weights (B, T, Hi) float32 of the layer's normed input ``x``, whose
    gradient stops here."""
    f32 = jnp.float32
    x = jax.lax.stop_gradient(x)
    b, t, _ = x.shape
    q = _rotate(jnp.matmul(x, q_proj.T).reshape(b, t, heads, head_dim),
                cos, sin, turn)
    k = jnp.matmul(x, k_proj.T).astype(f32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(var + eps) * norm_weight.astype(f32)
         + norm_bias.astype(f32)).astype(x.dtype)
    k = _rotate(k[:, :, None], cos, sin, turn)[:, :, 0]
    w = jnp.matmul(x, w_proj.T, preferred_element_type=f32) \
        / math.sqrt(heads)
    return q, k, w


class KeyeIndexer(HybridBlock):
    """The lightning indexer: ``num_heads`` query heads of ``head_dim``
    over one key head. Its leaves are its own parameters
    (``q_proj_weight``, ``k_proj_weight``, ``w_proj_weight`` (out, in) as
    ``nn.Dense`` holds them, ``k_norm_weight``, ``k_norm_bias``)."""

    def __init__(self, units, num_heads, head_dim, rope_theta, eps=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        self._static = dict(heads=num_heads, head_dim=head_dim, eps=eps)
        self._rope = {"rope_theta": rope_theta,
                      "partial_rotary_factor": INDEXER_ROTARY_SHARE}
        self._tables = {}
        shapes = {"q_proj_weight": ((num_heads * head_dim, units), None),
                  "k_proj_weight": ((head_dim, units), None),
                  "k_norm_weight": ((head_dim,), "ones"),
                  "k_norm_bias": ((head_dim,), "zeros"),
                  "w_proj_weight": ((num_heads, units), None)}
        with self.name_scope():
            for name, (shape, init) in shapes.items():
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init))

    def hybrid_forward(self, F, x, q_proj_weight, k_proj_weight,
                       k_norm_weight, k_norm_bias, w_proj_weight):
        t = x.shape[1]
        if t not in self._tables:
            self._tables[t] = rotary_tables(t, self._static["head_dim"],
                                            self._rope)
        cos, sin, turn = self._tables[t]
        return invoke(
            functools.partial(_indexer, cos=cos, sin=sin, turn=turn,
                              **self._static),
            [x, q_proj_weight, k_proj_weight, k_norm_weight, k_norm_bias,
             w_proj_weight], n_out=3)


@functools.partial(jax.jit, static_argnames=("topk",))
def _selected_attention(q, k, v, qi, ki, w, cos, sin, turn, *, topk):
    """Normed heads ``q`` (B, T, H, D), ``k`` (B, T, G, D), projected
    ``v`` (B, T, G * D) and the indexer's outputs to the heads' outputs
    (B, T, H * D): rotary positions, then the sparse attention."""
    b, t, h, d = q.shape
    q, k = _rotate(q, cos, sin, turn), _rotate(k, cos, sin, turn)
    v = v.reshape(b, t, k.shape[2], d)
    return sparse_attention(q, k, v, qi, ki, w, topk).reshape(b, t, h * d)


class KeyeAttention(HybridBlock):
    """Grouped-query attention with ``num_heads`` query heads over
    ``num_kv_heads`` key/value heads of ``head_dim``, q and k normed a
    head, rotary positions at ``rope_theta``, over the ``topk`` keys a
    query's indexer (``indexer``: ``(num_heads, head_dim)``) selects."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 indexer, topk, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be a multiple of "
                             "num_kv_heads")
        self._shape = (num_heads, num_kv_heads, head_dim)
        self._rope = {"rope_theta": rope_theta}
        self._topk = int(topk)
        self._tables = {}
        with self.name_scope():
            self.q_proj = _dense(num_heads * head_dim, units, "q_proj_")
            self.k_proj = _dense(num_kv_heads * head_dim, units, "k_proj_")
            self.v_proj = _dense(num_kv_heads * head_dim, units, "v_proj_")
            self.q_norm = RMSNorm(head_dim, eps, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, eps, prefix="k_norm_")
            self.indexer = KeyeIndexer(units, *indexer, rope_theta, eps,
                                       prefix="indexer_")
            self.o_proj = _dense(units, num_heads * head_dim, "o_proj_")

    def hybrid_forward(self, F, x):
        b, t, _ = x.shape
        heads, kv_heads, d = self._shape
        if t not in self._tables:
            self._tables[t] = rotary_tables(t, d, self._rope)
        cos, sin, turn = self._tables[t]
        count_traced("xla")
        q = self.q_norm(self.q_proj(x).reshape((b, t, heads, d)))
        k = self.k_norm(self.k_proj(x).reshape((b, t, kv_heads, d)))
        qi, ki, w = self.indexer(x)
        out = invoke(functools.partial(_selected_attention, cos=cos, sin=sin,
                                       turn=turn, topk=self._topk),
                     [q, k, self.v_proj(x), qi, ki, w])
        return self.o_proj(out)


# what ``from_config`` builds, key by key: any other value would be a
# guess at a mechanism the family does not write down
_BUILT_WITH = {
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "sliding_window": None, "norm_topk_prob": True, "mlp_only_layers": [],
    "decoder_sparse_step": 1, "vision_config": None,
}
# ``sa_config`` and ``rope_scaling`` likewise, under their own keys
_INDEXER_BUILT_WITH = {"indexer_num_kv_heads": 1}
_ROPE_BUILT_WITH = {"rope_type": "default", "type": "default"}


def _refuse_guesses(config):
    """Raise, naming the key, on every value ``from_config`` would have
    to guess at."""
    for key, built in _BUILT_WITH.items():
        if config.get(key, built) != built:
            raise ValueError(f"KeyeVL2 is built with {key} = {built!r}, "
                             f"got {config[key]!r}")
    for group, table in (("sa_config", _INDEXER_BUILT_WITH),
                         ("rope_scaling", _ROPE_BUILT_WITH)):
        given = config.get(group) or {}
        for key, built in table.items():
            if given.get(key, built) != built:
                raise ValueError(f"KeyeVL2 is built with {group}.{key} = "
                                 f"{built!r}, got {given[key]!r}")
    sections = (config.get("rope_scaling") or {}).get("mrope_section")
    if sections is not None and 2 * sum(sections) != config["head_dim"]:
        raise ValueError(
            f"rope_scaling.mrope_section {sections!r} must share out the "
            f"{config['head_dim'] // 2} frequencies of a head")
    if config.get("positions", "text") != "text":
        raise ValueError(f"positions {config['positions']!r}: the text "
                         "decoder is built for text positions (the three "
                         "streams of mrope_section equal), not an image "
                         "grid")


class KeyeLM(HybridBlock):
    """The text decoder over token ids (B, T) -> float32 logits
    (B, T, V). ``experts_held`` is the range of the
    ``num_experts_routed`` experts this chip holds."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 num_kv_heads, head_dim, rope_theta, indexer, topk,
                 moe_hidden_size, num_experts_routed, experts_held,
                 num_experts_per_tok, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i in range(num_layers):
                    make_attn = functools.partial(
                        KeyeAttention, units, num_heads, num_kv_heads,
                        head_dim, rope_theta, indexer, topk, eps,
                        prefix="attn_")
                    make_moe = functools.partial(
                        RoutedExpertsFFN, units, moe_hidden_size,
                        num_experts_routed, num_experts_per_tok,
                        experts_held, label=f"layers.{i}", prefix="moe_")
                    self.layers.add(DecoderLayer(
                        units, eps, ("attn", make_attn), ("moe", make_moe)))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.head = LMHead(vocab_size, units, prefix="head_")

    @classmethod
    def from_config(cls, config, **kwargs):
        """The net of a ``config.json``-shaped dict. ``num_experts``
        counts the experts held here where ``deployment`` gives
        ``experts_held`` (start, stop) and ``num_experts_published``;
        without a ``deployment`` the layer holds all it routes over.
        Raises, naming the key, on every value it would have to guess
        at (``_BUILT_WITH``, ``sa_config``'s key heads, a rotary type
        other than the default, ``mrope_section`` that does not share
        out a head's frequencies, positions other than text)."""
        _refuse_guesses(config)
        deployment = config.get("deployment", {})
        start, stop = deployment.get("experts_held",
                                     (0, config["num_experts"]))
        sa = config["sa_config"]
        return cls(
            vocab_size=config["vocab_size"], units=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], rope_theta=config["rope_theta"],
            indexer=(sa["indexer_num_heads"], sa["indexer_head_dim"]),
            topk=sa["topk"], moe_hidden_size=config["moe_intermediate_size"],
            num_experts_routed=deployment.get("num_experts_published",
                                              config["num_experts"]),
            experts_held=range(start, stop),
            num_experts_per_tok=config["num_experts_per_tok"],
            eps=config["rms_norm_eps"], **kwargs)

    def hybrid_forward(self, F, tokens):
        return self.head(self.norm(self.layers(self.embed(tokens))))
