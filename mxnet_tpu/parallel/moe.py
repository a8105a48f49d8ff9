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
by one of three scoring rules (``route_top_k``; the layer's ``scoring``
and ``groups``): ``"softmax"`` (softmax, top-k, renormalised over the k,
times a scaling factor: ``models.LagunaLM``), ``"sigmoid"`` (sigmoid
scores, the k largest of score plus a selection bias that no optimizer
trains, the unbiased scores renormalised with an epsilon, times the
factor: ``models.Lfm2MoeLM``) or the sigmoid rule limited to groups
(``groups=(n_group, topk_group)``: the experts stand in ``n_group``
groups of consecutive ids, the ``topk_group`` groups with the largest
sum of their two best biased scores stay, and the k are chosen inside
them: ``models.LingHybridLM``); whatever the rule, it
sorts the (token, expert) rows by expert, computes the held experts'
SiLU-gated FFNs as grouped products over the sorted rows, and combines
by routing weight. Rows routed to experts held elsewhere are left out
(their exchange belongs to the deployment, and nothing here stands in
for it). The buffer of sorted rows holds twice the rows that uniform
routing sends to the held experts (``buffer_rows``; never more than
tokens x k, the worst case): every pass over the rows costs what a
share really gets, not what it could get. A batch that routes more rows
here takes further passes of the same buffer under a device-side loop
(``_held_experts``), so no row is ever dropped and shapes are static
whatever the routing; where the layer holds every expert the buffer
holds the worst case and there is no loop. A shared expert
(``shared_hidden``) runs on every token, unweighted, under any of the
rules.

    layer = MoEFFN(units=256, hidden_size=1024, num_experts=8,
                   num_experts_per_tok=2)
    specs = expert_parallel_shardings(net, expert_axis="model")

    layer = RoutedExpertsFFN(units=2048, hidden_size=512,
                             num_experts=256, num_experts_per_tok=8,
                             experts_held=range(0, 32),
                             routed_scaling=2.5, shared_hidden=512)
    layer = RoutedExpertsFFN(units=2048, hidden_size=1792,
                             num_experts=32, num_experts_per_tok=4,
                             experts_held=range(0, 8), scoring="sigmoid")
    layer = RoutedExpertsFFN(units=2560, hidden_size=768,
                             num_experts=512, num_experts_per_tok=8,
                             experts_held=range(0, 8), scoring="sigmoid",
                             groups=(8, 4), routed_scaling=2.5,
                             shared_hidden=768)
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

# what the sigmoid rule adds to the sum of a token's chosen scores
# before it divides by it (LFM2's value; Ling's source writes 1e-20,
# which on a sum of eight sigmoids differs from this by less than 2 ulp
# of float32: one constant serves both)
SIGMOID_ROUTER_EPS = 1e-6


def _inside_best_groups(biased, groups):
    """``biased`` (N, E) with the experts outside a token's best groups
    at minus infinity. ``groups = (n_group, topk_group)``: the E experts
    stand in ``n_group`` groups of consecutive ids; a group's score is
    the sum of its two largest entries; the ``topk_group`` groups with
    the largest scores stay (the lower index wins a tie)."""
    n_group, topk_group = groups
    n, e = biased.shape
    best_two, _ = jax.lax.top_k(biased.reshape(n, n_group, e // n_group), 2)
    _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    stays = jnp.any(kept[..., None] == jnp.arange(n_group), axis=1)
    return jnp.where(jnp.repeat(stays, e // n_group, axis=-1), biased,
                     -jnp.inf)


def route_top_k(x, router_w, k, scale, bias=None, groups=None):
    """``(weights, expert ids)``, each (N, k), from the router's outputs
    over all its experts in float32, by one of three scoring rules.

    Softmax (``bias`` None): softmax over the outputs, the k largest
    (the lower index wins a tie), renormalised over the k, times
    ``scale``.

    Sigmoid with a selection bias (``bias`` (E,), float32, no trained
    weight): ``s = sigmoid(outputs)``; the k experts with the largest
    ``s + bias`` are chosen (the lower index wins a tie); their weights
    are the UNBIASED ``s`` over (their sum + ``SIGMOID_ROUTER_EPS``),
    times ``scale``. The gradient reaches the router through ``s`` of
    the chosen experts and the renormalisation, never through the
    bias.

    The same limited to groups (``groups = (n_group, topk_group)``
    beside a ``bias``): the k are chosen among the experts of the
    token's ``topk_group`` best groups (``_inside_best_groups``, on
    ``s + bias``); the weights as above."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router_w.astype(f32).T,
                     precision=jax.lax.Precision.HIGHEST)
    if bias is None:
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        return top_p / jnp.sum(top_p, axis=-1, keepdims=True) * scale, top_i
    scores = jax.nn.sigmoid(logits)
    biased = scores + jax.lax.stop_gradient(bias.astype(f32))
    if groups is not None:
        biased = _inside_best_groups(biased, groups)
    _, top_i = jax.lax.top_k(biased, k)
    # the chosen scores by a one-hot select over the experts: a pass,
    # and its transpose another, where a gather's is a scatter-add
    chosen = top_i[..., None] == jnp.arange(scores.shape[-1])
    top_s = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    return top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                    + SIGMOID_ROUTER_EPS) * scale, top_i


def _group_keys(top_i, held_start, held_count):
    """Every (token, choice) row's group: the held expert's local id, or
    ``held_count`` for an expert held elsewhere (sorted last)."""
    local = top_i - held_start
    held = (local >= 0) & (local < held_count)
    return jnp.where(held, local, held_count).reshape(-1), held


def _group_sizes(keys, held_count):
    return jnp.sum(keys[:, None] == jnp.arange(held_count)[None, :],
                   axis=0, dtype=jnp.int32)


def _sort_by_group(top_i, held_start, num_held, rows):
    """The (token, choice) rows sorted by group: ``(order, starts,
    held)``. ``order`` is the permutation that sorts them (the groups'
    rows in token order, rows held elsewhere last), padded to whole
    buffers of ``rows``; ``starts`` (num_held + 1,) every group's first
    slot in that order and, last, the rows routed here; ``held`` (N, k)
    whether the choice's expert is held here."""
    keys, held = _group_keys(top_i, held_start, num_held)
    starts = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(_group_sizes(keys, num_held), dtype=jnp.int32)])
    order = jnp.pad(jnp.argsort(keys, stable=True),
                    (0, -keys.shape[0] % rows))
    return order, starts, held


def _take_rows(a, index):
    """``a[index]`` along the first axis, zeros where ``index`` is out
    of range: one gather, no pass to mask its result."""
    return a.at[index].get(mode="fill", fill_value=0)


# The buffer of sorted rows holds this many times the rows that uniform
# routing sends to the experts held here. The rows really routed to a
# chip's share move by about a tenth between batches (the ledger's
# ``moe_experts_roofline``, which follows them, reads 25.7-28.2 over
# its seeds), so 2 leaves the further passes to skewed routing.
BUFFER_FACTOR = 2
# the buffer is a whole number of the grouped products' row tiles, and
# the sums over a token's slots are taken a tile of tokens at a time:
# the matrix unit's side
ROW_TILE = 128


def buffer_rows(rows, num_held, num_experts):
    """Rows of the buffer of sorted rows for ``rows`` (token, choice)
    rows routed over ``num_experts`` of which ``num_held`` are held
    here: ``BUFFER_FACTOR`` times what uniform routing sends here, in
    whole row tiles, and never more than ``rows``, the worst case."""
    expected = -(-rows * num_held // num_experts)
    return min(rows, -(-BUFFER_FACTOR * expected // ROW_TILE) * ROW_TILE)


def _sum_to_tokens(rows, w, slots, n, dtype):
    """``(n, C)`` in ``dtype``: token *t*'s sum of ``w[s] * rows[s]`` over
    the slots *s* of token *t* (``w`` None: the plain sum). ``slots`` is
    ``_buffer_slots``' ``(token, by_token, sorted_token)``: the slots by
    ascending token, so a tile of ``ROW_TILE`` tokens owns a run of
    them, and its sums are one product of the tile's (weighted) one-hot
    matrix with the run's rows: a grouped product whose groups are the
    token tiles. The weights meet the rows in the rows' dtype and the
    product accumulates in float32 (float32 rows at the highest
    precision: a one-hot product must not round them); neither a scatter
    nor an array of ``n * k`` rows is made. Slots whose token is out of
    range (``n``) lie past every run."""
    _, by_token, tok = slots
    tile = min(ROW_TILE, n)
    tiles = -(-n // tile)
    mine = (tok % tile)[:, None] == jnp.arange(tile)[None, :]
    mine = mine & (tok < n)[:, None]
    if w is None:
        hot = mine.astype(rows.dtype)
    else:
        hot = jnp.where(mine, w[by_token][:, None], 0).astype(rows.dtype)
    runs = jnp.sum((tok // tile)[:, None] == jnp.arange(tiles)[None, :],
                   axis=0, dtype=jnp.int32)
    sums = jax.lax.ragged_dot_general(
        hot, rows[by_token], runs, _RUNS_OF_ROWS,
        precision=(jax.lax.Precision.HIGHEST
                   if rows.dtype == jnp.float32 else None),
        preferred_element_type=dtype)
    return sums.reshape(tiles * tile, -1)[:n]


# (slots, tile)^T x (slots, C) a run of slots -> (runs, tile, C)
_RUNS_OF_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


@jax.custom_vjp
def _dispatch_rows(x, slots):
    """The buffer of sorted rows: slot *s* holds token ``token[s]`` of
    ``x`` (``slots[0]``), zeros where that is out of range (a slot past
    the rows routed here). The transpose is the sum over every token's
    slots (``_sum_to_tokens``), never a scatter; slots out of range give
    and get nothing, whatever the grouped product left there."""
    return _take_rows(x, slots[0])


def _dispatch_rows_fwd(x, slots):
    return _take_rows(x, slots[0]), (slots, x.shape[0])


def _dispatch_rows_bwd(res, g):
    slots, n = res
    return _sum_to_tokens(g, None, slots, n, g.dtype), None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_rows(out, w, slots, n):
    """``(n, C)`` in float32: every token's sum of its slots' rows of
    ``out`` by their weights ``w`` (``_sum_to_tokens``). Transposed as
    the gather of the cotangent's rows by token (the dispatch's own
    operation), weighted; the weights' cotangent is a dot a slot."""
    return _combine_rows_fwd(out, w, slots, n)[0]


def _combine_rows_fwd(out, w, slots, n):
    return _sum_to_tokens(out, w, slots, n, jnp.float32), (out, w, slots[0])


def _combine_rows_bwd(n, res, g):
    out, w, token = res
    g = _take_rows(g.astype(out.dtype), token)
    f32 = jnp.float32
    # a slot past the rows routed here gets nothing, whatever the
    # grouped product left in its row of ``out``
    dot = jnp.sum(out.astype(f32) * g.astype(f32), axis=-1)
    return (g * w[:, None],
            jnp.where(token < n, dot, 0).astype(w.dtype), None)


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


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


def _buffer_slots(order, starts, lo, rows, n, k):
    """The slots ``lo .. lo + rows`` of the sorted order: ``(row,
    slots, sizes)``. Slot *s* holds (token, choice) row ``row[s]``; it
    is valid where it lies under the rows routed here, ``starts[-1]``.
    ``slots`` is ``(token, by_token, sorted_token)``, each (rows,):
    every slot's token (``n`` where the slot is not valid), the slots by
    ascending token with those not valid last, and the tokens in that
    order. ``sizes`` (E_held,) are the rows of every group that lie in
    these slots. ``order`` is padded to whole buffers."""
    slot = lo + jnp.arange(rows)
    valid = slot < starts[-1]
    row = jax.lax.dynamic_slice(order, (lo,), (rows,))
    # rows in (token, choice) order are in token order
    sorted_row, by_token = jax.lax.sort_key_val(
        jnp.where(valid, row, n * k), slot - lo)
    return (row,
            (jnp.where(valid, row // k, n), by_token, sorted_row // k),
            jnp.diff(jnp.clip(starts, lo, lo + rows)))


@functools.partial(jax.jit, static_argnames=("rows",))
def _buffer_pass(x, weights, w_gate, w_up, w_down, order, starts, lo, rows):
    """One buffer of ``rows`` sorted rows through the held experts: the
    slots ``lo .. lo + rows`` of the (token, choice) rows sorted by
    group. ``order`` is the permutation that sorts them, ``starts``
    (E_held + 1,) every group's first slot and, last, the rows routed
    here; ``weights`` (N * k,) the routing weights in (token, choice)
    order, 0 for a choice held elsewhere. Returns (N, C) in float32:
    every token's weighted sum over its choices that lie in these
    slots. Jitted: the layers of a model, the first pass and the further
    ones, forward and recomputed, share one trace; and under a
    ``jax.vjp`` the name jax rewrites as ``jvp(..)`` /
    ``transpose(jvp(..))`` is the jit's, so that the phases' names below
    stay whole."""
    n = x.shape[0]
    with jax.named_scope("dispatch"):
        row, slots, sizes = _buffer_slots(order, starts, lo, rows, n,
                                          weights.shape[0] // n)
        sorted_rows = _dispatch_rows(x, slots)
    with jax.named_scope("experts"):
        gate = _grouped_product(sorted_rows, w_gate, sizes)
        up = _grouped_product(sorted_rows, w_up, sizes)
        out = _grouped_product(_silu_gate(gate, up), w_down, sizes)
    with jax.named_scope("combine"):
        # a slot that is not valid holds a row held elsewhere or the
        # padding's row 0 of another group: its weight meets no run
        return _combine_rows(out, weights[row], slots, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _held_experts(x, weights, w_gate, w_up, w_down, order, starts, rows):
    """Every token's weighted sum over its choices held here, (N, C) in
    ``x``'s dtype: the first ``rows`` slots of the sorted order in one
    ``_buffer_pass`` and, where more rows than that were routed here,
    the rest in further passes of the same buffer (``_further_passes``).
    The backward pass keeps the first pass's residuals alone and
    recomputes every further pass where it transposes it: one definition
    of the expert computation, one program with static shapes whatever
    the routing."""
    return _every_pass(
        (x, weights, w_gate, w_up, w_down), order, starts, rows,
        lambda first, *operands: (first(*operands), None))[0]


def _every_pass(operands, order, starts, rows, run_first):
    """``(result, what run_first kept)``: the first buffer pass run by
    ``run_first(pass, *operands) -> (its result, anything)``, then the
    further ones."""
    buffer_at = functools.partial(_buffer_pass, order=order, starts=starts,
                                  rows=rows)
    y, kept = run_first(functools.partial(buffer_at, lo=0), *operands)
    y = _further_passes(lambda lo: buffer_at(*operands, lo=lo), y, rows,
                        operands[1].shape[0], starts[-1])
    return y.astype(operands[0].dtype), kept


def _further_passes(one_more, first, rows, worst, routed):
    """``first`` plus ``one_more(lo)`` for every further buffer's first
    slot ``lo = rows, 2 * rows, ..`` under ``routed``, the rows routed
    here (``worst`` at most, a shape): a device-side loop inside a
    conditional, so that a batch whose rows all fit the first buffer
    pays for neither the loop's state nor what the compiler moves out of
    its body. Nothing where the buffer holds the worst case: the shapes
    say so."""
    if rows >= worst:
        return first

    def loop(first):
        return jax.lax.while_loop(
            lambda c: c[0] < routed,
            lambda c: (c[0] + rows,
                       jax.tree.map(jnp.add, c[1], one_more(c[0]))),
            (rows, first))[1]

    return jax.lax.cond(rows < routed, loop, lambda first: first, first)


def _held_experts_fwd(x, weights, w_gate, w_up, w_down, order, starts, rows):
    operands = (x, weights, w_gate, w_up, w_down)
    y, pull = _every_pass(operands, order, starts, rows, jax.vjp)
    return y, (pull, operands, order, starts)


def _held_experts_bwd(rows, res, g):
    pull, operands, order, starts = res
    g = g.astype(jnp.float32)

    def pulled_at(lo):
        return jax.vjp(functools.partial(
            _buffer_pass, order=order, starts=starts, lo=lo, rows=rows),
            *operands)[1](g)

    return (*_further_passes(pulled_at, pull(g), rows, operands[1].shape[0],
                             starts[-1]), None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


@functools.partial(jax.jit, static_argnames=(
    "k", "num_held", "held_start", "scale", "groups"))
def routed_experts(x, router_w, w_gate, w_up, w_down, bias=None, *, k,
                   held_start, num_held, scale, groups=None):
    """The held experts' part of a top-k routed SiLU-gated FFN.

    x: (N, C); router_w: (E_all, C); w_gate / w_up: (E_held, C, F);
    w_down: (E_held, F, C); the layer holds experts ``held_start ..
    held_start + num_held`` of the router's ``E_all``; ``bias`` (E_all,)
    picks ``route_top_k``'s sigmoid rule and ``groups`` its group limit.
    Returns (N, C):
    for every token the weighted sum over its choices that are held
    here. The buffer of sorted rows has ``buffer_rows`` rows; a batch
    that routes more rows here takes further passes (``_held_experts``),
    so every routed row is computed."""
    rows = buffer_rows(x.shape[0] * k, num_held, router_w.shape[0])
    with jax.named_scope("route"):
        weights, top_i = route_top_k(x, router_w, k, scale, bias, groups)
    with jax.named_scope("dispatch"):
        order, starts, held = _sort_by_group(top_i, held_start, num_held,
                                             rows)
    # the weights meet the rows in the rows' dtype; the sum over a
    # token's choices accumulates in float32
    weights = jnp.where(held, weights, 0.0).astype(x.dtype).reshape(-1)
    return _held_experts(x, weights, w_gate, w_up, w_down, order, starts,
                         rows)


@functools.partial(jax.jit, static_argnames=("k", "num_held",
                                             "held_start", "groups"))
def routing_counts(x, router_w, bias=None, *, k, held_start, num_held,
                   groups=None):
    """``(rows each held expert gets from the tokens x: (num_held,), the
    expert ids the router chose: (N, k))``."""
    _, top_i = route_top_k(x, router_w, k, 1.0, bias, groups)
    keys, _ = _group_keys(top_i, held_start, num_held)
    return _group_sizes(keys, num_held), top_i


def _sets_differ_share(a, b):
    """The share of rows whose expert ids (N, k) differ as sets."""
    return jnp.mean(jnp.any(jnp.sort(a, axis=-1) != jnp.sort(b, axis=-1),
                            axis=-1))


@functools.partial(jax.jit, static_argnames=("k", "groups"))
def bias_changed_share(x, router_w, bias, *, k, groups=None):
    """The share of the tokens ``x`` whose chosen k under the sigmoid
    rule differ, as a set, from what a bias of zero would choose."""
    return _sets_differ_share(
        route_top_k(x, router_w, k, 1.0, bias, groups)[1],
        route_top_k(x, router_w, k, 1.0, jnp.zeros_like(bias), groups)[1])


@functools.partial(jax.jit, static_argnames=("k", "groups"))
def group_limit_changed_share(x, router_w, bias, *, k, groups):
    """The share of the tokens ``x`` whose chosen k under the group
    limit differ, as a set, from the k largest of score plus bias."""
    return _sets_differ_share(
        route_top_k(x, router_w, k, 1.0, bias, groups)[1],
        route_top_k(x, router_w, k, 1.0, bias)[1])


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
    ``num_experts`` the router scores. ``scoring`` is the router's rule
    (``route_top_k``, three in all): ``"softmax"``, or ``"sigmoid"``,
    which adds the parameter ``expert_bias`` (num_experts,), float32,
    ``grad_req`` ``"null"``: the selection bias, which no optimizer
    touches; the sigmoid rule takes ``groups = (n_group, topk_group)``
    for its group limit. ``shared_hidden`` builds a shared expert under
    any rule. In an
    eager forward (not under a trace) the layer records, under
    ``label``, the telemetry gauges ``moe_rows_routed.<label>``,
    ``moe_load_max_over_mean.<label>``, ``moe_buffer_rows.<label>``
    (``buffer_rows``), ``moe_rows_overflow.<label>`` (the routed rows
    past the buffer, which take further passes),
    ``moe_rows_dropped.<label>`` (0: every pass computes its rows) and,
    under the sigmoid rule, ``moe_bias_changed_choice.<label>`` (the
    share of tokens whose chosen experts are not the largest scores
    alone) and, with ``groups``, ``moe_group_limit_changed_choice.<label>``
    (the share whose choice the group limit changed), and keeps the
    expert ids its router chose in ``last_expert_ids`` (N, k)."""

    def __init__(self, units, hidden_size, num_experts,
                 num_experts_per_tok, experts_held=None,
                 routed_scaling=1.0, shared_hidden=0, label=None,
                 scoring="softmax", groups=None, **kwargs):
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
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError("scoring must be 'softmax' or 'sigmoid', "
                             f"got {scoring!r}")
        if groups is not None:
            n_group, topk_group = groups
            if scoring != "sigmoid" or num_experts % n_group \
                    or not 0 < topk_group <= n_group \
                    or num_experts // n_group < 2 \
                    or topk_group * (num_experts // n_group) \
                    < num_experts_per_tok:
                raise ValueError(
                    "groups = (n_group, topk_group) goes with sigmoid "
                    "scoring, n_group whole groups of at least two "
                    "experts and topk_group of them holding at least "
                    f"num_experts_per_tok, got {groups!r}")
            groups = (int(n_group), int(topk_group))
        self._groups = groups
        self._units, self._k = units, int(num_experts_per_tok)
        self._held_start, self._num_held = held[0], len(held)
        self._scale = float(routed_scaling)
        self._label = label
        self.last_expert_ids = None
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), init=None)
            if scoring == "sigmoid":
                self.expert_bias = self.params.get(
                    "expert_bias", shape=(num_experts,), init="zeros",
                    grad_req="null")
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

    def hybrid_forward(self, F, x, router_weight, w_gate, w_up, w_down,
                       expert_bias=None):
        shape = x.shape
        flat = x.reshape((-1, shape[-1]))
        geometry = dict(k=self._k, held_start=self._held_start,
                        num_held=self._num_held, groups=self._groups)
        router = [router_weight] if expert_bias is None \
            else [router_weight, expert_bias]
        if not _trace_ctx.active:
            raw = [a._data for a in [flat] + router]
            sizes, self.last_expert_ids = routing_counts(*raw, **geometry)
            if self._label:
                self._record(sizes, flat.shape[0], router_weight.shape[0])
                if expert_bias is not None:
                    from ..telemetry import metrics
                    choice = dict(k=self._k, groups=self._groups)
                    metrics.gauge(
                        f"moe_bias_changed_choice.{self._label}").set(float(
                            bias_changed_share(*raw, **choice)))
                    if self._groups is not None:
                        metrics.gauge("moe_group_limit_changed_choice."
                                      + self._label).set(float(
                                          group_limit_changed_share(
                                              *raw, **choice)))
        # the bias goes last: ``routed_experts``' one optional operand
        out = invoke(functools.partial(
            routed_experts, scale=self._scale, **geometry),
            [flat, router_weight, w_gate, w_up, w_down] + router[1:])
        out = out.reshape(shape)
        if self.shared is not None:
            out = out + self.shared(x)
        return out

    def _record(self, sizes, tokens, num_experts):
        from ..telemetry import metrics
        sizes = jax.device_get(sizes)
        rows = int(sizes.sum())
        mean = rows / len(sizes)
        buffer = buffer_rows(tokens * self._k, len(sizes), num_experts)
        for name, value in (
                ("moe_rows_routed", rows),
                ("moe_load_max_over_mean",
                 float(sizes.max()) / mean if mean else 0.0),
                ("moe_buffer_rows", buffer),
                # rows that took a further pass of the buffer
                ("moe_rows_overflow", max(0, rows - buffer)),
                # every pass computes its rows: none is ever left out
                ("moe_rows_dropped", 0)):
            metrics.gauge(f"{name}.{self._label}").set(value)
