"""Mixture-of-Experts layers.

Two layers live here.

``MoEFFN`` is the dense composition: every expert computes every token
and the routing weights zero what was not routed. Its shapes are static
and under pjit the (E, ...) expert parameters shard on the expert axis
(``expert_parallel_shardings``), but its work is tokens x experts, not
tokens x k: it is a small-model convenience, not expert parallelism.

``RoutedExpertsFFN`` is the expert-parallel layer proper, as one chip
of an expert-parallel deployment runs it: it is told which experts it
holds (``experts_held``), routes every token over ALL ``num_experts``
(softmax, top-k, renormalised over the k, times a scaling factor),
sorts the (token, expert) rows by expert, computes the held experts'
SiLU-gated FFNs as grouped products over the sorted rows, and combines
by routing weight. Rows routed to experts held elsewhere are left out
(their exchange belongs to the deployment, and nothing here stands in
for it); the row buffer holds the worst case (tokens x k), so no row is
ever dropped; shapes are static whatever the routing. A shared expert
(``shared_hidden``) runs on every token, unweighted.

    layer = MoEFFN(units=256, hidden_size=1024, num_experts=8,
                   num_experts_per_tok=2)
    specs = expert_parallel_shardings(net, expert_axis="model")

    layer = RoutedExpertsFFN(units=2048, hidden_size=512,
                             num_experts=256, num_experts_per_tok=8,
                             experts_held=range(0, 32),
                             routed_scaling=2.5, shared_hidden=512)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock, _trace_ctx
from ..ndarray.ndarray import invoke
from ..ops.registry import register_op

__all__ = ["MoEFFN", "expert_parallel_shardings", "GatedFFN",
           "RoutedExpertsFFN", "routed_experts", "route_top_k",
           "routing_counts"]


@register_op("_moe_ffn", input_names=("x", "gate_w", "w1", "b1", "w2",
                                      "b2"))
def _moe_ffn(x, gate_w, w1, b1, w2, b2, num_experts_per_tok=2):
    """Dense MoE FFN: route, run every expert, combine by routing weight.

    x: (N, C); gate_w: (E, C); w1: (E, H, C); b1: (E, H);
    w2: (E, C, H); b2: (E, C). Dense-dispatch keeps shapes static (the
    TPU-friendly formulation); with E sharded, XLA turns the masked
    einsums into expert-parallel compute + collectives.
    """
    E = gate_w.shape[0]
    k = min(int(num_experts_per_tok), E)
    probs = jax.nn.softmax(x @ gate_w.T, axis=-1)   # (N, E)
    # top-k mask, renormalized over the selected experts: exactly k a
    # token, the lower index winning a tie (a threshold at the k-th
    # largest would take every expert tied with it)
    if k < E:
        _, top = jax.lax.top_k(probs, k)
        mask = jnp.sum(jax.nn.one_hot(top, E, dtype=probs.dtype), axis=1)
        gates = probs * mask
        gates = gates / jnp.clip(jnp.sum(gates, axis=-1, keepdims=True),
                                 1e-9, None)
    else:
        gates = probs
    # every expert computes on every token; the gate zeroes non-routed
    # contributions. (N,C)x(E,H,C)->(E,N,H). Exact gelu — the same
    # activation as the dense ffn1/gelu/ffn2 path this layer replaces
    # (ops/nn.py leaky_relu act_type='gelu')
    h = jnp.einsum("nc,ehc->enh", x, w1) + b1[:, None, :]
    h = jax.nn.gelu(h, approximate=False)
    out = jnp.einsum("enh,ech->enc", h, w2) + b2[:, None, :]
    return jnp.einsum("enc,ne->nc", out, gates)


@register_op("_moe_load_balance_loss", input_names=("x", "gate_w"))
def _moe_load_balance_loss(x, gate_w):
    """Switch-Transformer auxiliary loss: E * sum_e(f_e * P_e) where
    f_e is the fraction of tokens whose argmax is expert e and P_e the
    mean routing probability (Fedus et al. 2021, eq. 4)."""
    E = gate_w.shape[0]
    probs = jax.nn.softmax(x @ gate_w.T, axis=-1)
    top = jnp.argmax(probs, axis=-1)
    frac = jnp.mean((jnp.arange(E)[None, :] == top[:, None])
                    .astype(probs.dtype), axis=0)
    return E * jnp.sum(frac * jnp.mean(probs, axis=0))


class MoEFFN(HybridBlock):
    """Drop-in replacement for the transformer FFN pair
    (ffn1/gelu/ffn2) with E experts and top-k routing."""

    def __init__(self, units, hidden_size, num_experts=4,
                 num_experts_per_tok=2, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._hidden = hidden_size
        self._E = num_experts
        self._k = num_experts_per_tok
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(num_experts, units),
                init=None)
            self.w1 = self.params.get(
                "w1", shape=(num_experts, hidden_size, units),
                init=None)
            self.b1 = self.params.get(
                "b1", shape=(num_experts, hidden_size), init="zeros")
            self.w2 = self.params.get(
                "w2", shape=(num_experts, units, hidden_size),
                init=None)
            self.b2 = self.params.get(
                "b2", shape=(num_experts, units), init="zeros")
        for p in (self.w1, self.b1, self.w2, self.b2):
            # structural marker consumed by expert_parallel_shardings —
            # leading dim is the expert axis
            p._expert_sharded = True

    def hybrid_forward(self, F, x, gate_weight, w1, b1, w2, b2):
        shape = x.shape
        flat = x.reshape((-1, shape[-1]))
        out = F._moe_ffn(flat, gate_weight, w1, b1, w2, b2,
                         num_experts_per_tok=self._k)
        return out.reshape(shape)

    def load_balance_loss(self, x):
        flat = x.reshape((-1, x.shape[-1]))
        from .. import ndarray as nd_ns
        return nd_ns._moe_load_balance_loss(flat, self.gate_weight.data())


def expert_parallel_shardings(block, expert_axis: str = "model"):
    """PartitionSpecs sharding every MoE expert-stacked parameter on
    its leading (E) dim over `expert_axis` (the ep analog of
    models.tensor_parallel_shardings). Returns {param_name: P(...)}."""
    from jax.sharding import PartitionSpec as P
    specs = {}
    for name, param in block._collect_params_with_prefix().items():
        if getattr(param, "_expert_sharded", False):
            specs[name] = P(expert_axis)
        elif name.rsplit(".", 1)[-1] == "gate_weight":
            specs[name] = P()  # router replicated
    return specs


# ---------------------------------------------------------------------------
# the expert-parallel layer: top-k dispatch over the experts held here
# ---------------------------------------------------------------------------

def route_top_k(x, router_w, k, scale):
    """``(weights, expert ids)``, each (N, k): softmax over all the
    router's outputs in float32, the k largest (the lower index wins a
    tie), renormalised over the k, times ``scale``."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router_w.astype(f32).T,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True) * scale, top_i


def _group_keys(top_i, held_start, held_count):
    """Every (token, choice) row's group: the held expert's local id, or
    ``held_count`` for an expert held elsewhere (sorted last)."""
    local = top_i - held_start
    held = (local >= 0) & (local < held_count)
    return jnp.where(held, local, held_count).reshape(-1), held


def _group_sizes(keys, held_count):
    return jnp.sum(keys[:, None] == jnp.arange(held_count)[None, :],
                   axis=0, dtype=jnp.int32)


def _take_rows(a, index):
    """``a[index]`` along the first axis, zeros where ``index`` is out
    of range: one gather, no pass to mask its result."""
    return a.at[index].get(mode="fill", fill_value=0)


@jax.custom_vjp
def _dispatch_rows(x, order, inv, take, back):
    """The buffer of sorted rows: slot *s* holds token ``order[s] // k``
    where ``take[s]`` (the slot lies inside the held groups), zeros
    elsewhere. ``order`` is the permutation of the (token, choice) rows
    that sorts them by group, ``inv`` its inverse, ``back[r]`` whether
    row *r*'s expert is held. The transpose is a gather by ``inv`` and a
    sum over the k choices, never a scatter; slots outside the groups
    give and get nothing, whatever the grouped product left there."""
    k = order.shape[0] // x.shape[0]
    return _take_rows(x, jnp.where(take, order // k, x.shape[0]))


def _dispatch_rows_fwd(x, order, inv, take, back):
    return _dispatch_rows(x, order, inv, take, back), (inv, back, x.shape[0])


def _dispatch_rows_bwd(res, g):
    inv, back, n = res
    rows = _take_rows(g, jnp.where(back, inv, g.shape[0]))
    return (rows.reshape(n, -1, g.shape[-1]).sum(axis=1),
            None, None, None, None)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _collect_rows(out, order, inv, take, back):
    """The products' rows back in (token, choice) order: row *r* is slot
    ``inv[r]`` of ``out`` where ``back[r]``, zeros for a choice held
    elsewhere. Transposed as a gather by ``order`` under ``take``."""
    return _take_rows(out, jnp.where(back, inv, out.shape[0]))


def _collect_rows_fwd(out, order, inv, take, back):
    return _collect_rows(out, order, inv, take, back), (order, take)


def _collect_rows_bwd(res, g):
    order, take = res
    return (_take_rows(g, jnp.where(take, order, g.shape[0])),
            None, None, None, None)


_collect_rows.defvjp(_collect_rows_fwd, _collect_rows_bwd)


def _grouped_product(rows, w, sizes):
    """``rows[r] @ w[group of r]`` for rows sorted by group, ``sizes``
    rows a group; rows past the groups' total give zeros. A ragged dot:
    the work follows the rows in the groups, not rows x groups (on a
    TPU, XLA lowers it to its grouped-matmul kernels, which visit only
    the row tiles the groups cover)."""
    return jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=rows.dtype)


@jax.checkpoint
def _silu_gate(gate, up):
    """``silu(gate) * up`` in float32, the inputs' dtype out;
    rematerialised in the backward pass, so that the float32 copies are
    not kept."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


@functools.partial(jax.jit, static_argnames=(
    "k", "num_held", "held_start", "scale"))
def routed_experts(x, router_w, w_gate, w_up, w_down, *, k, held_start,
                   num_held, scale):
    """The held experts' part of a top-k routed SiLU-gated FFN.

    x: (N, C); router_w: (E_all, C); w_gate / w_up: (E_held, C, F);
    w_down: (E_held, F, C); the layer holds experts ``held_start ..
    held_start + num_held`` of the router's ``E_all``. Returns (N, C):
    for every token the weighted sum over its choices that are held
    here. The buffer of sorted rows has N * k rows, the worst case."""
    n, c = x.shape
    with jax.named_scope("route"):
        weights, top_i = route_top_k(x, router_w, k, scale)
    with jax.named_scope("dispatch"):
        keys, held = _group_keys(top_i, held_start, num_held)
        sizes = _group_sizes(keys, num_held)
        order = jnp.argsort(keys, stable=True)
        inv = jnp.argsort(order)
        take = jnp.arange(n * k) < jnp.sum(sizes)
        back = held.reshape(-1)
        rows = _dispatch_rows(x, order, inv, take, back)
    with jax.named_scope("experts"):
        gate = _grouped_product(rows, w_gate, sizes)
        up = _grouped_product(rows, w_up, sizes)
        out = _grouped_product(_silu_gate(gate, up), w_down, sizes)
    with jax.named_scope("combine"):
        per_choice = _collect_rows(out, order, inv, take,
                                   back).reshape(n, k, c)
        # the weights meet the rows in the rows' dtype and the sum over
        # the k choices accumulates in float32: a float32 copy of the
        # (N, k, C) rows is neither made nor kept for the backward pass
        weights = jnp.where(held, weights, 0.0).astype(x.dtype)
        return jnp.einsum("nk,nkc->nc", weights, per_choice,
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("k", "num_held",
                                             "held_start"))
def routing_counts(x, router_w, *, k, held_start, num_held):
    """``(rows each held expert gets from the tokens x: (num_held,), the
    expert ids the router chose: (N, k))``."""
    _, top_i = route_top_k(x, router_w, k, 1.0)
    keys, _ = _group_keys(top_i, held_start, num_held)
    return _group_sizes(keys, num_held), top_i


class GatedFFN(HybridBlock):
    """``down(silu(gate(x)) * up(x))``, no bias."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        from ..gluon import nn
        with self.name_scope():
            self.gate_proj = nn.Dense(hidden_size, flatten=False,
                                      use_bias=False, in_units=units,
                                      prefix="gate_proj_")
            self.up_proj = nn.Dense(hidden_size, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="up_proj_")
            self.down_proj = nn.Dense(units, flatten=False, use_bias=False,
                                      in_units=hidden_size,
                                      prefix="down_proj_")

    def hybrid_forward(self, F, x):
        gate, up = self.gate_proj(x), self.up_proj(x)
        return self.down_proj(invoke(_silu_gate, [gate, up]))


class RoutedExpertsFFN(HybridBlock):
    """One chip's share of a top-k routed expert layer (module
    docstring). ``experts_held`` is a contiguous ``range`` of the
    ``num_experts`` the router scores. In an eager forward (not under a
    trace) the layer records, under ``label``, the telemetry gauges
    ``moe_rows_routed.<label>``, ``moe_load_max_over_mean.<label>`` and
    ``moe_rows_dropped.<label>`` (0: the buffer holds the worst case),
    and keeps the expert ids its router chose in ``last_expert_ids``
    (N, k)."""

    def __init__(self, units, hidden_size, num_experts,
                 num_experts_per_tok, experts_held=None,
                 routed_scaling=1.0, shared_hidden=0, label=None,
                 **kwargs):
        super().__init__(**kwargs)
        held = range(num_experts) if experts_held is None \
            else experts_held
        held = list(held)
        if not held or held != list(range(held[0], held[0] + len(held))) \
                or held[0] < 0 or held[-1] >= num_experts:
            raise ValueError("experts_held must be a contiguous range "
                             f"inside 0..{num_experts}, got {held[:4]}..")
        if not 0 < num_experts_per_tok <= num_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             f"1..{num_experts}")
        self._units, self._k = units, int(num_experts_per_tok)
        self._held_start, self._num_held = held[0], len(held)
        self._scale = float(routed_scaling)
        self._label = label
        self.last_expert_ids = None
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), init=None)
            self.w_gate = self.params.get(
                "w_gate", shape=(len(held), units, hidden_size), init=None)
            self.w_up = self.params.get(
                "w_up", shape=(len(held), units, hidden_size), init=None)
            self.w_down = self.params.get(
                "w_down", shape=(len(held), hidden_size, units), init=None)
            self.shared = GatedFFN(units, shared_hidden, prefix="shared_") \
                if shared_hidden else None
        for p in (self.w_gate, self.w_up, self.w_down):
            p._expert_sharded = True

    def hybrid_forward(self, F, x, router_weight, w_gate, w_up, w_down):
        shape = x.shape
        flat = x.reshape((-1, shape[-1]))
        geometry = dict(k=self._k, held_start=self._held_start,
                        num_held=self._num_held)
        if not _trace_ctx.active:
            sizes, self.last_expert_ids = routing_counts(
                flat._data, router_weight._data, **geometry)
            if self._label:
                self._record(sizes, flat.shape[0])
        out = invoke(functools.partial(
            routed_experts, scale=self._scale, **geometry), [flat, router_weight, w_gate, w_up, w_down])
        out = out.reshape(shape)
        if self.shared is not None:
            out = out + self.shared(x)
        return out

    def _record(self, sizes, tokens):
        from ..telemetry import metrics
        sizes = jax.device_get(sizes)
        rows = int(sizes.sum())
        mean = rows / len(sizes)
        metrics.gauge(f"moe_rows_routed.{self._label}").set(rows)
        metrics.gauge(f"moe_load_max_over_mean.{self._label}").set(
            float(sizes.max()) / mean if mean else 0.0)
        metrics.gauge(f"moe_rows_dropped.{self._label}").set(
            max(0, rows - tokens * self._k))
