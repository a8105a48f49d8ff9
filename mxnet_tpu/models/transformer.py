"""Transformer / BERT model family (flagship).

The reference era predates transformers as first-class citizens — its BERT
support lives in GluonNLP built on the kernels listed in SURVEY.md
Appendix C config 3 (Embedding, LayerNorm, GELU, FullyConnected, batch_dot,
softmax, dropout, AdamW, AMP). This module provides the model family
natively, TPU-first:

- attention runs through one switchable backend: dense local attention,
  ring attention over a 'seq' mesh axis (lax.ppermute ring), or Ulysses
  all-to-all (SURVEY.md §5.7 beyond-reference requirement);
- all shapes static, all control flow compiler-friendly;
- tensor-parallel sharding specs for the Dense weights are provided by
  `tensor_parallel_shardings` (Megatron-style column/row split, executed
  by GSPMD from pjit annotations — no hand-written collectives).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import numpy as onp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray, invoke
from ..ops.pallas_kernels import (count_traced, flash_attention,
                                  flash_attention_available)
from ..parallel.ring_attention import local_attention
from ..parallel.mesh import P

__all__ = ["MultiHeadAttention", "TransformerEncoderLayer", "TransformerLM",
           "BERTModel", "tensor_parallel_shardings"]


def _on_tpu() -> bool:
    import jax
    return any(d.platform == "tpu" for d in jax.devices())


def _in_scope(name, fn):
    """``fn`` under ``jax.named_scope(name)`` wherever it is traced: the
    forward, and the tape's replay for the backward pass."""
    import jax

    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return scoped


class MultiHeadAttention(HybridBlock):
    """Self-attention with a pluggable context-parallel backend."""

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        # context-parallel config (set via set_context_parallel)
        self._cp_mesh = None
        self._cp_axis = "seq"
        self._cp_strategy = "ring"
        self._cp_block_size = None
        self._causal = False
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, flatten=False, use_bias=use_bias,
                                prefix="qkv_")
            self.proj = nn.Dense(units, flatten=False, use_bias=use_bias,
                                 prefix="proj_")
            self.drop = nn.Dropout(dropout)

    def set_context_parallel(self, mesh, seq_axis="seq", strategy="ring",
                             block_size=None):
        self._cp_mesh = mesh
        self._cp_axis = seq_axis
        self._cp_strategy = strategy
        self._cp_block_size = block_size
        self._cached = {}

    def hybrid_forward(self, F, x):
        # x: (B, T, C)
        B, T, C = x.shape
        qkv = self.qkv(x)  # (B, T, 3C)
        qkv = qkv.reshape((B, T, 3, self._num_heads, self._head_dim))
        qkv = qkv.transpose((2, 0, 3, 1, 4))  # (3, B, H, T, D)
        q, k, v = qkv[0], qkv[1], qkv[2]

        mesh = self._cp_mesh
        causal = self._causal
        if mesh is not None:
            from ..parallel.ring_attention import context_parallel_attention
            fn = partial(context_parallel_attention, mesh=mesh,
                         seq_axis=self._cp_axis, causal=causal,
                         strategy=self._cp_strategy,
                         block_size=getattr(self, "_cp_block_size", None))
        else:
            # one rule on what this call can see (T, D, dtype, whether
            # GSPMD partitions the trace): the fused kernel where it
            # beats the dense composition, dense elsewhere
            kernel = _on_tpu() and flash_attention_available(
                T, T, self._head_dim, x.dtype)
            count_traced("kernel" if kernel else "dense")
            fn = partial(flash_attention if kernel else local_attention,
                         causal=causal)
        out = invoke(_in_scope("products", fn), [q, k, v])  # (B, H, T, D)
        out = out.transpose((0, 2, 1, 3)).reshape((B, T, C))
        return self.drop(self.proj(out))


class TransformerEncoderLayer(HybridBlock):
    def __init__(self, units, num_heads, hidden_size, dropout=0.0,
                 pre_norm=True, num_experts=0, num_experts_per_tok=2,
                 **kwargs):
        super().__init__(**kwargs)
        self._pre_norm = pre_norm
        self._moe = num_experts > 0
        with self.name_scope():
            self.attn = MultiHeadAttention(units, num_heads, dropout)
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.ln2 = nn.LayerNorm(in_channels=units)
            if self._moe:
                # expert-parallel FFN (SURVEY §2.4 ep axis)
                from ..parallel.moe import MoEFFN
                self.moe = MoEFFN(units, hidden_size,
                                  num_experts=num_experts,
                                  num_experts_per_tok=num_experts_per_tok)
            else:
                self.ffn1 = nn.Dense(hidden_size, flatten=False,
                                     prefix="ffn1_")
                self.ffn2 = nn.Dense(units, flatten=False, prefix="ffn2_")
            self.drop = nn.Dropout(dropout)

    def _ffn(self, F, h):
        if self._moe:
            return self.moe(h)
        return self.ffn2(F.LeakyReLU(self.ffn1(h), act_type="gelu"))

    def hybrid_forward(self, F, x):
        if self._pre_norm:
            x = x + self.attn(self.ln1(x))
            h = self.ln2(x)
            return x + self.drop(self._ffn(F, h))
        x = self.ln1(x + self.attn(x))
        return self.ln2(x + self.drop(self._ffn(F, x)))


class TransformerLM(HybridBlock):
    """Decoder-only / encoder LM over token ids.

    Covers both the BERT-base pretraining config (causal=False + MLM head)
    and a GPT-style causal LM (causal=True)."""

    def __init__(self, vocab_size, units=256, num_layers=4, num_heads=8,
                 hidden_size=1024, max_len=512, dropout=0.0, causal=False,
                 num_experts=0, num_experts_per_tok=2, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._max_len = max_len
        self._causal = causal
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units)
            self.pos_embed = nn.Embedding(max_len, units)
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_layers):
                    self.layers.add(TransformerEncoderLayer(
                        units, num_heads, hidden_size, dropout,
                        num_experts=num_experts,
                        num_experts_per_tok=num_experts_per_tok))
            self.ln_f = nn.LayerNorm(in_channels=units)
            self.head = nn.Dense(vocab_size, flatten=False, prefix="head_")
        for layer in self.layers:
            layer.attn._causal = causal

    def set_context_parallel(self, mesh, seq_axis="seq", strategy="ring",
                             block_size=None):
        for layer in self.layers:
            layer.attn.set_context_parallel(mesh, seq_axis, strategy,
                                            block_size)

    def hybrid_forward(self, F, tokens):
        # tokens: (B, T) int
        B, T = tokens.shape
        from .. import ndarray as nd_ns
        pos = nd_ns.arange(0, T, dtype="int32")
        x = self.embed(tokens)
        x = x + self.pos_embed(pos).expand_dims(0)
        x = self.layers(x)
        x = self.ln_f(x)
        return self.head(x)


class BERTModel(TransformerLM):
    """BERT-base-style encoder (config 3 in BASELINE.json)."""

    def __init__(self, vocab_size=30522, units=768, num_layers=12,
                 num_heads=12, hidden_size=3072, max_len=512, dropout=0.1,
                 **kwargs):
        super().__init__(vocab_size, units, num_layers, num_heads,
                         hidden_size, max_len, dropout, causal=False,
                         **kwargs)


def tensor_parallel_shardings(block, model_axis: str = "model"):
    """Megatron-style PartitionSpecs for a TransformerLM's parameters:
    qkv/ffn1 column-parallel (shard output dim), proj/ffn2 row-parallel
    (shard input dim), embeddings sharded on vocab. Feed to
    ParallelTrainer(param_shardings=...) — GSPMD inserts the all-reduces
    the reference would have hand-coded."""
    specs = {}
    for key, p in block._collect_params_with_prefix().items():
        # match on the Parameter's own name: it carries the Dense
        # prefixes given above ("..._qkv_weight"), where the key is the
        # attribute path ("layers.0.attn.qkv.weight")
        name = p.name
        if p.shape is None:
            spec = P()
        elif "qkv_weight" in name or "ffn1_weight" in name:
            spec = P(model_axis, None)
        elif "qkv_bias" in name or "ffn1_bias" in name:
            spec = P(model_axis)
        elif "proj_weight" in name or "ffn2_weight" in name:
            spec = P(None, model_axis)
        elif "head_weight" in name or \
                "embedding" in name and name.endswith("weight"):
            spec = P(model_axis, None) if len(p.shape) == 2 else P()
        else:
            # leave unmatched params OUT of the dict (ParallelTrainer
            # defaults them to replicated): an explicit P() here would
            # clobber other sharding helpers' specs — e.g.
            # expert_parallel_shardings — depending on merge order
            continue
        specs[key] = spec
    return specs
