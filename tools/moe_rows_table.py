#!/usr/bin/env python3
"""The ways of taking the expert layer's buffer of sorted rows back to
its tokens, on the attached chip: the measurement behind
``parallel/moe.py _sum_to_tokens``.

    python tools/moe_rows_table.py [--tokens 8192] [--other DIR]

One JSON line a case, device time of one call in ms (``null``: it does
not fit, or the compiler refuses it). First every candidate for the sum
of a token's weighted slots, ``R`` rows of ``C`` to ``n`` tokens, alone:

- ``tile_product``: the slots in token order, a token tile's one-hot
  matrix times its run of rows (``_sum_to_tokens``, what the layer does);
- ``scatter_add`` / ``segment_sum``: the same rows added at their token,
  indices sorted;
- ``gather_nkc``: the (n, k, C) array of every (token, choice)'s row
  gathered out of the buffer, zeros for a choice held elsewhere, and the
  sum over k (what the layer did while its buffer had n * k rows);

then the buffer's gather from the tokens (the dispatch), then the whole
layer forward and backward: ``routed_experts`` as it is (``layer_ms``),
one buffer pass alone with no loop for further passes round it
(``one_pass_ms``: what the loop costs when it runs no turn), the layer
with a router skewed so that further passes run (``skewed_ms``, with the
rows routed here), and with ``--other DIR`` the same layer of another
checkout (``other_layer_ms``). A CPU run refuses to start.
"""
import argparse
import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from mxnet_tpu.parallel import moe  # noqa: E402
from tools.attention_table import fwd_bwd, time_ms  # noqa: E402

K, ROUTED, HELD, WIDTH, HIDDEN = 8, 256, 32, 2048, 512


def layer_operands(tokens, skew=0.0):
    """x, router, the three expert matrices and a cotangent, as the
    Laguna cell holds them; ``skew`` is added to the held experts'
    router rows along the tokens' mean direction."""
    ks = jax.random.split(jax.random.key(tokens), 6)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (tokens, WIDTH)) + 0.1
    router = 0.02 * jax.random.normal(ks[1], (ROUTED, WIDTH))
    router = router.at[:HELD].add(skew / WIDTH)
    mats = [0.02 * jax.random.normal(k, s) for k, s in (
        (ks[2], (HELD, WIDTH, HIDDEN)), (ks[3], (HELD, WIDTH, HIDDEN)),
        (ks[4], (HELD, HIDDEN, WIDTH)))]
    return ([x.astype(bf16), router] + [m.astype(bf16) for m in mats]
            + [jax.random.normal(ks[5], (tokens, WIDTH)).astype(bf16)])


def buffer_operands(tokens):
    """The buffer's rows, weights and index vectors under a uniform
    router: what ``_buffer_pass`` hands ``_sum_to_tokens``."""
    x, router = layer_operands(tokens)[:2]
    rows = moe.buffer_rows(tokens * K, HELD, ROUTED)
    weights, top_i = moe.route_top_k(x, router, K, 2.5)
    order, starts, held = moe._sort_by_group(top_i, 0, HELD, rows)
    row, slots, _ = moe._buffer_slots(order, starts, 0, rows, tokens, K)
    weights = jnp.where(held, weights, 0).astype(x.dtype)
    w = weights.reshape(-1)[row]
    out = jax.random.normal(jax.random.key(1), (rows, WIDTH)).astype(x.dtype)
    # every (token, choice) row's slot, out of range where held elsewhere
    back = jnp.where(held.reshape(-1), jnp.argsort(order[:tokens * K]), rows)
    print(json.dumps({"tokens": tokens, "buffer_rows": rows,
                      "rows_routed": int(starts[-1])}), flush=True)
    return x, out, w, slots, back, weights


def candidates(n):
    def tile_product(out, w, slots, back, weights):
        return moe._sum_to_tokens(out, w, slots, n, jnp.float32)

    def sorted_updates(out, w, slots):
        return (out * w[:, None])[slots[1]].astype(jnp.float32), slots[2]

    def scatter_add(out, w, slots, back, weights):
        rows, at = sorted_updates(out, w, slots)
        return jnp.zeros((n, out.shape[1]), jnp.float32).at[at].add(
            rows, indices_are_sorted=True, mode="drop")

    def segment_sum(out, w, slots, back, weights):
        rows, at = sorted_updates(out, w, slots)
        return jax.ops.segment_sum(rows, at, num_segments=n,
                                   indices_are_sorted=True)

    def gather_nkc(out, w, slots, back, weights):
        per_choice = moe._take_rows(out, back).reshape(n, K, -1)
        return jnp.einsum("nk,nkc->nc", weights, per_choice,
                          preferred_element_type=jnp.float32)

    return {"tile_product": tile_product, "scatter_add": scatter_add,
            "segment_sum": segment_sum, "gather_nkc": gather_nkc}


def layer(fn):
    return fwd_bwd(functools.partial(fn, k=K, held_start=0, num_held=HELD,
                                     scale=2.5))


def one_pass(*operands, **geometry):
    """``routed_experts`` with its further passes taken out: the first
    buffer alone, neither loop nor conditional round it."""
    kept = moe._further_passes
    moe._further_passes = lambda one_more, first, *sizes: first
    try:
        return moe.routed_experts.__wrapped__(*operands, **geometry)
    finally:
        moe._further_passes = kept


def other_routed_experts(root):
    spec = importlib.util.spec_from_file_location(
        "other_moe", os.path.join(root, "mxnet_tpu", "parallel", "moe.py"),
        submodule_search_locations=None)
    module = importlib.util.module_from_spec(spec)
    module.__package__ = "mxnet_tpu.parallel"
    spec.loader.exec_module(module)
    return module.routed_experts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--skew", type=float, default=10.0)
    ap.add_argument("--other", default=None)
    ap.add_argument("--cpu", type=int, default=0,
                    help="1: run the cases once on the CPU, no times")
    args = ap.parse_args()
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.cpu:
        sys.exit("moe_rows_table.py measures a chip; none is attached")

    def ms(fn, ops):
        if on_chip:
            return time_ms(fn, ops, args.reps)
        jax.block_until_ready(jax.jit(fn)(*ops))
        return None

    n = args.tokens
    x, *ops = buffer_operands(n)
    want = None
    for name, fn in candidates(n).items():
        got = jax.jit(fn)(*ops)
        want = got if want is None else want
        print(json.dumps({
            "sum_to_tokens": name, "ms": ms(fn, ops),
            "gap_to_first": float(jnp.max(jnp.abs(got - want)))}),
            flush=True)
    print(json.dumps({"dispatch": "gather_by_token",
                      "ms": ms(moe._take_rows, (x, ops[2][0]))}), flush=True)
    ops = layer_operands(n)
    row = {"layer_ms": ms(layer(moe.routed_experts), ops),
           "one_pass_ms": ms(layer(one_pass), ops)}
    if args.other:
        row["other_layer_ms"] = ms(layer(other_routed_experts(args.other)),
                                   ops)
    skewed = layer_operands(n, args.skew)
    counts, _ = moe.routing_counts(skewed[0], skewed[1], k=K, held_start=0,
                                   num_held=HELD)
    row["skewed_rows_routed"] = int(counts.sum())
    row["skewed_ms"] = ms(layer(moe.routed_experts), skewed)
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
