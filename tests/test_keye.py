"""``models.KeyeLM`` at the configuration's ``tiny`` sizes against the
family's plain reference (``benchmark/families/keye_vl2.py``) on seeded
weights: the stack, logits and loss, three Adam steps through
``Trainer.fuse_step`` (the indexer's leaves trained by its KL loss
alone); what ``from_config`` refuses; the softmax expert layer's shares
adding up to the uncut layer; the planted faults giving another
model."""
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
from mxnet_tpu.parallel import moe  # noqa: E402

from benchmark import correctness  # noqa: E402
from benchmark.families import keye_vl2  # noqa: E402

SEED = 2 ** 31 + 42
INDEXER = ("q_proj_weight", "k_proj_weight", "w_proj_weight",
           "k_norm_weight", "k_norm_bias")
pytestmark = pytest.mark.usefixtures("layer_gauges_cleaned")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return {**config, **config["tiny"]["sizes"]}


@pytest.fixture(scope="module")
def traffic():
    # 64 tokens over a top 16: three quarters of the queries select
    return {"batch": 2, "seq": 64, "n_batches": 4}


@pytest.fixture(scope="module")
def built(sizes, traffic):
    weights = keye_vl2.make_weights(sizes, "f32", SEED)
    batches = keye_vl2.make_batches(sizes, "f32", traffic, SEED)
    net, loss_fn = keye_vl2.build_program(sizes, "f32", weights, mx.cpu(0),
                                          batches[0][0])
    return net, loss_fn, weights, batches


def test_the_net_is_the_published_stack(built):
    net, _, weights, _ = built
    params = net._collect_params_with_prefix()
    assert set(params) == set(weights)
    assert len(net.layers) == 2
    for layer in net.layers:
        assert isinstance(layer.attn, models.KeyeAttention)
        assert isinstance(layer.moe, moe.RoutedExpertsFFN)
    assert params["layers.0.attn.q_proj.weight"].shape == (64, 64)
    assert params["layers.0.attn.k_proj.weight"].shape == (32, 64)
    assert params["layers.0.attn.q_norm.weight"].shape == (16,)
    assert params["layers.0.attn.indexer.q_proj_weight"].shape == (32, 64)
    assert params["layers.0.attn.indexer.k_proj_weight"].shape == (8, 64)
    assert params["layers.0.attn.indexer.w_proj_weight"].shape == (4, 64)
    assert params["layers.0.attn.indexer.k_norm_bias"].shape == (8,)
    assert params["layers.1.moe.router_weight"].shape == (16, 64)
    assert params["layers.1.moe.w_gate"].shape == (4, 64, 32)
    assert "layers.1.moe.expert_bias" not in params
    assert not any(".shared." in n for n in params)
    assert params["head.weight"] is not params["embed.weight"]
    f32 = {n for n in weights if str(onp.dtype(keye_vl2.param_dtype(
        n, "bf16_norm_router_f32"))) == "float32"}
    assert f32 == {n for n in weights if n.endswith(
        ("norm.weight", "norm_weight", "norm_bias", "router_weight"))}


def test_logits_and_loss_are_the_references(sizes, built):
    net, loss_fn, weights, batches = built
    x, y = batches[0]
    with autograd.pause():
        logits = net(_wrap(x))
        loss = loss_fn(logits, _wrap(y))._data
    with jax.default_matmul_precision("highest"):
        ids = {}
        want = keye_vl2.reference_logits(sizes, weights, x,
                                         correctness.Rounding, ids)
        want_loss, _ = keye_vl2.reference_loss(
            sizes, weights, x, y, correctness.Rounding, None)
    assert logits.shape == (2, 64, 128)
    assert str(logits.dtype) == "float32"
    assert onp.allclose(logits._data, want, rtol=1e-4, atol=1e-5)
    # the loss read is the LM loss: L_I enters its gradient alone
    assert onp.allclose(loss, want_loss, rtol=1e-5)
    assert sorted(ids) == ["layers.0", "layers.1"]
    for name, chosen in ids.items():
        assert onp.array_equal(
            onp.sort(keye_vl2.PROGRAM_EXPERT_IDS[name], axis=-1),
            onp.sort(chosen, axis=-1)), name


def test_three_adam_steps_through_fuse_step_follow_the_reference(
        sizes, built):
    from mxnet_tpu.telemetry import metrics
    traced = metrics.counter("sparse_attention_traced_total.xla")
    before = traced.value()
    _, _, weights, batches = built
    net, loss_fn = keye_vl2.build_program(sizes, "f32", weights, mx.cpu(0),
                                          batches[0][0])
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
    # two layers, each traced by the shape-resolving forward and by the
    # step
    assert traced.value() - before == 4
    ref = correctness.reference_follow(
        keye_vl2, sizes, opt, weights, batches,
        correctness.step_keys(SEED), "reference")
    # float32 on both sides: the gaps are rounding's, 1e-6 or less
    ok, compared, detail = correctness.compare(
        readings.readings(), ref,
        {"loss_gap": 1e-5, "grad_gap": 1e-3, "delta_gap": 1e-3})
    assert ok, (compared, detail)
    assert detail["leaves"] == len(trainable) == 2 * 17 + 3
    got = readings.readings()["grad_norms"]
    for leaf in INDEXER:
        name = f"layers.1.attn.indexer.{leaf}"
        assert ref["grad_norms"][name] > 0 and ref["delta_norms"][name] > 0
        assert got[name] == pytest.approx(ref["grad_norms"][name], rel=1e-3)


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("use_sliding_window", True),
    ("sliding_window", 4096), ("norm_topk_prob", False),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("vision_config", {"depth": 27}), ("positions", "grid"),
    ("sa_config", {"indexer_num_kv_heads": 2}),
    ("rope_scaling", {"rope_type": "yarn"}),
    ("rope_scaling", {"mrope_section": [16, 24, 16]})])
def test_from_config_refuses_what_it_would_have_to_guess(config, key, value):
    name = {"sa_config": "indexer_num_kv_heads"}.get(key, key)
    if key == "rope_scaling":
        name = "rope_type" if "rope_type" in value else "mrope_section"
        value = {**config["rope_scaling"], **value}
    if key == "sa_config":
        value = {**config["sa_config"], **value}
    with pytest.raises(ValueError, match=name):
        models.KeyeLM.from_config({**config, key: value})


# ---------------------------------------------------------------------------
# the softmax expert layer
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: four chips, each with 4 of the 16
    SiLU-gated experts, the router whole on every chip and no shared
    expert. What the four blocks give adds up to the uncut reference's
    whole layer."""
    from mxnet_tpu.telemetry import metrics
    rng = onp.random.RandomState(5)

    def normal(scale, *shape):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    p = {"x": normal(1.0, 96, 16), "router_weight": normal(0.5, 16, 16),
         "w_gate": normal(0.3, 16, 16, 8), "w_up": normal(0.3, 16, 16, 8),
         "w_down": normal(0.3, 16, 8, 16)}
    layer = {"num_experts_per_tok": 3}
    q = correctness.Rounding
    whole, _ = keye_vl2._experts(layer, p["x"], p, q, held=(0, 16))
    total = 0.0
    try:
        for lo in range(0, 16, 4):
            blk = moe.RoutedExpertsFFN(16, 8, 16, 3, range(lo, lo + 4),
                                       label="test.keye")
            blk.initialize(mx.init.Zero())  # every leaf is set below
            for name in ("w_gate", "w_up", "w_down"):
                getattr(blk, name).set_data(_wrap(p[name][lo:lo + 4]))
            blk.router_weight.set_data(_wrap(p["router_weight"]))
            out = blk(_wrap(p["x"].reshape(2, 48, 16)))._data
            part, _ = keye_vl2._experts(
                layer, p["x"], {**p, **{k: p[k][lo:lo + 4] for k in
                                        ("w_gate", "w_up", "w_down")}},
                q, held=(lo, 4))
            assert onp.allclose(out.reshape(96, 16), part, atol=1e-5)
            total = total + out.reshape(96, 16)
    finally:
        for name in list(metrics.all_metrics()):
            if name.endswith("test.keye"):
                metrics.unregister(name)
    assert onp.allclose(total, whole, atol=2e-5)
    assert not onp.allclose(part, whole, atol=1e-3)


def test_a_planted_fault_is_another_model(sizes, built):
    """What the chip's ``correct`` must tell apart, at the tiny sizes:
    the latest keys for the indexer's and the scores without their ReLU
    each give another attention block, tensor for tensor, which the
    witness compares; the KL loss left out gives the same loss and
    another gradient (the test of the three steps)."""
    _, _, weights, batches = built
    x = batches[0][0]
    own = keye_vl2._own(weights, "layers.0.attn.")
    h = jax.random.normal(jax.random.key(3), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        sound, _ = keye_vl2._attention_block(sizes, h, own,
                                             correctness.Rounding)
        for fault in ("window_selection", "indexer_no_relu"):
            moved, _ = keye_vl2._attention_block(
                {**sizes, "planted_fault": fault}, h, own,
                correctness.Rounding)
            assert float(jnp.linalg.norm(moved - sound)) \
                > 1e-2 * float(jnp.linalg.norm(sound)), fault
    read = keye_vl2.block_witness(sizes, "f32", weights, batches, mx.cpu(0))
    assert read["sparse_block_gap"] < 3e-5
    assert read["selection_differ_share"] == 0.0
    for fault in ("window_selection", "indexer_no_relu"):
        read = keye_vl2.block_witness({**sizes, "planted_fault": fault},
                                      "f32", weights, batches, mx.cpu(0))
        assert read["selection_differ_share"] > 0.1, fault
    assert x.shape == (2, 64)
    assert set(keye_vl2.FAULTS) == {"window_selection", "indexer_no_relu",
                                    "no_kl_gradient"}
