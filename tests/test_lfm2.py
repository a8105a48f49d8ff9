"""``models.Lfm2MoeLM`` at the configuration's ``tiny`` sizes against the
family's plain reference (``benchmark/families/lfm2_moe.py``) on seeded
weights: logits, loss, every leaf's gradient, three Adam steps through
``Trainer.fuse_step``; the head is tied, and the embedding's gradient
holds both of its uses."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, models  # noqa: E402
from mxnet_tpu.ndarray.ndarray import _wrap  # noqa: E402

from benchmark import correctness  # noqa: E402
from benchmark.families import lfm2_moe  # noqa: E402

SEED = 2 ** 31 + 17
pytestmark = pytest.mark.usefixtures("layer_gauges_cleaned")


@pytest.fixture(scope="module")
def sizes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    return {**config, **config["tiny"]["sizes"]}


@pytest.fixture(scope="module")
def traffic():
    return {"batch": 2, "seq": 32, "n_batches": 4}


def program(sizes, traffic):
    weights = lfm2_moe.make_weights(sizes, "f32", SEED)
    batches = lfm2_moe.make_batches(sizes, "f32", traffic, SEED)
    net, loss_fn = lfm2_moe.build_program(sizes, "f32", weights,
                                          mx.cpu(0), batches[0][0])
    return net, loss_fn, weights, batches


@pytest.fixture(scope="module")
def built(sizes, traffic):
    """One net for the tests that only read it."""
    return program(sizes, traffic)


@pytest.fixture(scope="module")
def reference_grads(sizes, built):
    """``grads(zero_bias)``: the plain reference's gradient of the summed
    loss on the first batch for every trained leaf, with the selection
    bias as drawn or left at zero; one compiled function for both."""
    _, _, weights, batches = built
    x, y = batches[0]
    ref = reference_params(weights)
    trained = {n: v for n, v in ref.items() if not lfm2_moe.is_state(n)}
    fixed = {n: v for n, v in ref.items() if lfm2_moe.is_state(n)}

    @jax.jit
    def grad(trained, fixed):
        return jax.grad(lambda t: jnp.sum(lfm2_moe.reference_loss(
            sizes, {**fixed, **t}, x, y, correctness.Rounding, None)[0]))(
                trained)

    @functools.lru_cache(maxsize=None)
    def grads(zero_bias):
        with jax.default_matmul_precision("highest"):
            return grad(trained, {n: v * 0 for n, v in fixed.items()}
                        if zero_bias else fixed)

    return grads


def reference_params(weights):
    return {n: v for n, v in weights.items() if n != lfm2_moe.TIED}


def test_the_net_is_the_published_stack_with_a_tied_head(built):
    net, _, weights, _ = built
    params = net._collect_params_with_prefix()
    assert params["head.weight"] is params["embed.weight"]
    assert set(params) == set(weights)
    kinds = [("conv" if hasattr(layer, "conv") else "attn",
              "moe" if hasattr(layer, "moe") else "mlp")
             for layer in net.layers]
    assert kinds == [("conv", "mlp"), ("conv", "mlp"), ("attn", "moe"),
                     ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    assert params["layers.2.moe.expert_bias"].grad_req == "null"
    assert params["layers.2.attn.q_norm.weight"].shape == (16,)
    assert params["layers.0.conv.filter"].shape == (64, 3)
    # one Parameter, once in what a Trainer is given
    assert len(net.collect_params()) == len(params) - 1


def test_logits_and_loss_are_the_references(sizes, built):
    net, loss_fn, weights, batches = built
    x, y = batches[0]
    with autograd.pause():
        logits = net(_wrap(x))
        loss = loss_fn(logits, _wrap(y))._data
    with jax.default_matmul_precision("highest"):
        want = lfm2_moe.reference_logits(sizes, reference_params(weights),
                                         x, correctness.Rounding)
        want_loss, _ = lfm2_moe.reference_loss(
            sizes, reference_params(weights), x, y, correctness.Rounding,
            None)
    assert logits.shape == (2, 32, 128)
    assert str(logits.dtype) == "float32"
    assert onp.allclose(logits._data, want, rtol=1e-4, atol=1e-5)
    assert onp.allclose(loss, want_loss, rtol=1e-5)


def test_every_leafs_gradient_is_the_references(built, reference_grads):
    net, loss_fn, weights, batches = built
    x, y = batches[0]
    with autograd.record():
        loss = loss_fn(net(_wrap(x)), _wrap(y))
    loss.backward()
    want = reference_grads(False)
    params = net._collect_params_with_prefix()
    assert len(want) == 57
    for name, g in want.items():
        got = params[name].grad()._data
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, name
        assert onp.allclose(got, g, rtol=2e-3, atol=2e-4 * scale), name
    # the embedding's gradient holds both uses: the rows looked up AND
    # the head's product, which alone reaches rows no token looked up
    rows_used = onp.zeros(128, bool)
    rows_used[onp.asarray(x).reshape(-1)] = True
    grad = params["embed.weight"].grad().asnumpy()
    assert (~rows_used).any() and onp.abs(grad[~rows_used]).max() > 0
    head_only = onp.asarray(want["embed.weight"])[~rows_used]
    assert onp.allclose(grad[~rows_used], head_only, rtol=2e-3,
                        atol=2e-4 * onp.abs(head_only).max())


def test_three_adam_steps_through_fuse_step_follow_the_reference(
        sizes, traffic):
    net, loss_fn, weights, batches = program(sizes, traffic)
    opt = sizes["optimizer"]
    trainer = gluon.Trainer(net.collect_params(), opt["name"],
                            {k: v for k, v in opt.items() if k != "name"})
    fused = trainer.fuse_step(net, loss_fn)
    trainable = [n for n, p in net._collect_params_with_prefix().items()
                 if p.grad_req != "null"]
    readings = correctness.ProgramReadings(opt, net, trainer, trainable)
    for i in range(correctness.N_STEPS):
        x, y = batches[i]
        loss = fused.step(_wrap(x), _wrap(y))._data
        readings.after_step(loss, weights if i == 2 else None)
    # one leaf for the tied weight, updated once a step
    assert "head.weight" not in fused._trainable
    assert "embed.weight" in fused._trainable
    index = trainer._param2idx[net.embed.weight.name]
    assert trainer._optimizer._index_update_count[index] == 3
    assert "layers.2.moe.expert_bias" not in fused._trainable
    bias = net._collect_params_with_prefix()["layers.2.moe.expert_bias"]
    assert onp.array_equal(bias.data()._data,
                           weights["layers.2.moe.expert_bias"])
    ref = correctness.reference_follow(
        lfm2_moe, sizes, opt, weights, batches,
        correctness.step_keys(SEED), "reference")
    ok, compared, detail = correctness.compare(
        readings.readings(), ref,
        {"loss_gap": 1e-5, "grad_gap": 1e-3, "delta_gap": 1e-3})
    assert ok, (compared, detail)
    assert detail["leaves"] == 57


def test_a_missing_bias_is_another_model(reference_grads):
    """What the chip's ``correct`` must tell apart, at the tiny sizes:
    the same weights with the selection bias left at zero route other
    rows to the held experts and give other first gradients."""
    base, zero_bias = reference_grads(False), reference_grads(True)
    moved = [abs(float(jnp.linalg.norm(zero_bias[n]))
                 / float(jnp.linalg.norm(base[n])) - 1)
             for n in base if ".moe.w_" in n]
    assert max(moved) > 0.01
