"""``parallel/moe.py``'s second scoring rule (sigmoid scores, a
selection bias, the unbiased scores renormalised with an epsilon)
against the plain reference's (``benchmark/families/lfm2_moe.py``), and
the first rule (softmax) held to the bit to what it gave before the
second existed. Small sizes on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ndarray.ndarray import _wrap  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

from benchmark import correctness  # noqa: E402
from benchmark.families import lfm2_moe  # noqa: E402

SIZES = {"num_experts_per_tok": 2, "routed_scaling_factor": 1.5}


def _inputs(seed=3, n=48, c=16, f=8, routed=8, bias_std=0.1):
    ks = jax.random.split(jax.random.key(seed), 6)
    return {
        "x": jax.random.normal(ks[0], (n, c)),
        "router_weight": 0.5 * jax.random.normal(ks[1], (routed, c)),
        "expert_bias": bias_std * jax.random.normal(ks[5], (routed,)),
        "w_gate": 0.3 * jax.random.normal(ks[2], (routed, c, f)),
        "w_up": 0.3 * jax.random.normal(ks[3], (routed, c, f)),
        "w_down": 0.3 * jax.random.normal(ks[4], (routed, f, c))}


def _softmax_rule_as_it_was(x, router_w, k, scale):
    """``route_top_k`` of the commit before the sigmoid rule, verbatim."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router_w.astype(f32).T,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True) * scale, top_i


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_softmax_rule_is_unchanged_to_the_bit(dtype):
    p = _inputs(n=256, c=32, routed=16)
    x, w = p["x"].astype(dtype), p["router_weight"]
    want = jax.jit(_softmax_rule_as_it_was, static_argnums=(2, 3))(
        x, w, 4, 2.5)
    got = jax.jit(moe.route_top_k, static_argnums=(2, 3))(x, w, 4, 2.5)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and onp.array_equal(a, b)
    # and the layer's whole result under it, with and without naming
    # the new operand
    args = (p["x"], p["router_weight"], p["w_gate"][:4], p["w_up"][:4],
            p["w_down"][:4])
    geometry = dict(k=3, held_start=0, num_held=4, scale=2.5)
    assert onp.array_equal(moe.routed_experts(*args, **geometry),
                           moe.routed_experts(*args, None, **geometry))


def test_sigmoid_routing_is_the_references():
    p = _inputs()
    w, ids = moe.route_top_k(p["x"], p["router_weight"], 2, 1.5,
                             p["expert_bias"])
    w_ref, ids_ref = lfm2_moe.routing(SIZES, p["x"], p["router_weight"],
                                      p["expert_bias"])
    assert onp.array_equal(ids, ids_ref)
    assert onp.allclose(w, w_ref, rtol=1e-6, atol=1e-7)
    # by hand: the unbiased scores over their sum plus the epsilon
    s = jax.nn.sigmoid(jnp.dot(p["x"], p["router_weight"].T,
                               precision="highest"))
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    by_hand = 1.5 * chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    assert onp.allclose(w, by_hand, rtol=1e-6)
    assert float(w.sum(-1).max()) < 1.5      # the epsilon is there


def test_the_bias_changes_the_choice_and_never_the_weights():
    """An expert with the lowest score and a large bias is chosen; its
    weight is its own small score renormalised, not score plus bias."""
    p = _inputs(bias_std=0.0)
    s = jax.nn.sigmoid(jnp.dot(p["x"], p["router_weight"].T,
                               precision="highest"))
    bias = jnp.zeros((8,)).at[5].set(10.0)
    w, ids = moe.route_top_k(p["x"], p["router_weight"], 2, 1.0, bias)
    _, plain_ids = moe.route_top_k(p["x"], p["router_weight"], 2, 1.0,
                                   jnp.zeros((8,)))
    assert bool(jnp.all(ids[:, 0] == 5))
    assert not onp.array_equal(jnp.sort(ids, -1), jnp.sort(plain_ids, -1))
    other = jnp.take_along_axis(s, ids[:, 1:], axis=-1)[:, 0]
    want = s[:, 5] / (s[:, 5] + other + 1e-6)
    assert onp.allclose(w[:, 0], want, rtol=1e-6)
    # the other chosen expert is the largest unbiased score but for 5
    rest = s.at[:, 5].set(-1.0)
    assert onp.array_equal(ids[:, 1], jnp.argmax(rest, axis=-1))
    share = moe.bias_changed_share(p["x"], p["router_weight"], bias, k=2)
    assert float(share) == pytest.approx(float(jnp.mean(
        jnp.any(jnp.sort(ids, -1) != jnp.sort(plain_ids, -1), -1))))
    assert float(moe.bias_changed_share(
        p["x"], p["router_weight"], jnp.zeros((8,)), k=2)) == 0.0


def test_a_tie_goes_to_the_lower_index():
    x = jnp.ones((4, 8))
    router = jnp.zeros((6, 8))            # every score 0.5
    _, ids = moe.route_top_k(x, router, 3, 1.0, jnp.zeros((6,)))
    assert onp.array_equal(ids, jnp.tile(jnp.arange(3), (4, 1)))
    bias = jnp.zeros((6,)).at[4].set(0.25).at[5].set(0.25)
    _, ids = moe.route_top_k(x, router, 3, 1.0, bias)
    assert onp.array_equal(ids, jnp.tile(jnp.array([4, 5, 0]), (4, 1)))


def test_the_gradient_reaches_the_router_and_never_the_bias():
    p = _inputs()
    geometry = dict(k=2, held_start=0, num_held=8, scale=1.5)

    def program(x, router, bias, gate, up, down):
        return jnp.sum(jnp.square(moe.routed_experts(
            x, router, gate, up, down, bias, **geometry)))

    def reference(x, router, bias, gate, up, down):
        out, _ = lfm2_moe._experts(
            SIZES, x, {"router_weight": router, "expert_bias": bias,
                       "w_gate": gate, "w_up": up, "w_down": down},
            correctness.Rounding, held=(0, 8))
        return jnp.sum(jnp.square(out))

    args = [p[k] for k in ("x", "router_weight", "expert_bias", "w_gate",
                           "w_up", "w_down")]
    with jax.default_matmul_precision("highest"):
        got = jax.grad(program, range(6))(*args)
        want = jax.grad(reference, range(6))(*args)
    assert float(jnp.abs(got[1]).max()) > 1e-3        # the router's
    assert not onp.any(onp.asarray(got[2]))           # the bias: nothing
    assert not onp.any(onp.asarray(want[2]))
    for a, b in zip(got, want):
        assert onp.allclose(a, b, rtol=2e-4, atol=2e-5)


def test_four_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: four chips, each with 2 of the 8
    experts, the router and the bias whole; their outputs added up are
    the uncut reference's whole expert layer."""
    p = _inputs()
    router = {k: p[k] for k in ("router_weight", "expert_bias")}
    whole, _ = lfm2_moe._experts(SIZES, p["x"], p, correctness.Rounding,
                                 held=(0, 8))
    total_program = total_reference = 0.0
    for lo in range(0, 8, 2):
        held = {k: p[k][lo:lo + 2] for k in ("w_gate", "w_up", "w_down")}
        total_program = total_program + moe.routed_experts(
            p["x"], p["router_weight"], held["w_gate"], held["w_up"],
            held["w_down"], p["expert_bias"], k=2, held_start=lo,
            num_held=2, scale=1.5)
        part, _ = lfm2_moe._experts(SIZES, p["x"], {**held, **router},
                                    correctness.Rounding, held=(lo, 2))
        total_reference = total_reference + part
    assert onp.allclose(total_reference, whole, atol=1e-5)
    assert onp.allclose(total_program, whole, atol=1e-5)
    assert not onp.allclose(part, whole, atol=1e-3)


def test_the_block_holds_the_bias_out_of_training_and_fills_the_gauge():
    from mxnet_tpu.telemetry import metrics
    p = _inputs(bias_std=0.3)
    blk = moe.RoutedExpertsFFN(16, 8, 8, 2, range(0, 4), 1.5,
                               label="test.sigmoid", scoring="sigmoid")
    blk.initialize()
    assert blk.expert_bias.grad_req == "null"
    assert str(blk.expert_bias.data().dtype) == "float32"
    assert not onp.any(blk.expert_bias.data().asnumpy())   # ships as zeros
    for name in ("w_gate", "w_up", "w_down"):
        getattr(blk, name).set_data(_wrap(p[name][:4]))
    blk.router_weight.set_data(_wrap(p["router_weight"]))
    blk.expert_bias.set_data(_wrap(p["expert_bias"]))
    try:
        out = blk(_wrap(p["x"].reshape(2, 24, 16)))._data
        want, ids = lfm2_moe._experts(SIZES, p["x"], p,
                                      correctness.Rounding, held=(0, 4))
        assert onp.allclose(out.reshape(48, 16), want, atol=1e-5)
        assert onp.array_equal(blk.last_expert_ids, ids)
        share = metrics.gauge("moe_bias_changed_choice.test.sigmoid").value()
        assert 0.0 < share < 1.0
        assert metrics.gauge("moe_rows_routed.test.sigmoid").value() == \
            int(jnp.sum(ids < 4))
    finally:
        for name in list(metrics.all_metrics()):
            if name.endswith("test.sigmoid"):
                metrics.unregister(name)
    # the softmax layer has no such parameter and keeps no such gauge
    plain = moe.RoutedExpertsFFN(16, 8, 8, 2, range(0, 4), 1.5)
    assert "expert_bias" not in plain._collect_params_with_prefix()
    with pytest.raises(ValueError, match="scoring"):
        moe.RoutedExpertsFFN(16, 8, 8, 2, scoring="tanh")
