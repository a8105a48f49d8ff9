"""``models.NemotronHLM`` at the configuration's ``tiny`` sizes against
the family's plain reference (``benchmark/families/nemotron_h.py``) on
seeded weights: the stack, logits and loss, three Adam steps through
``Trainer.fuse_step``; what ``from_config`` refuses; the squared-ReLU
expert layer's shares adding up to the uncut layer, the shared expert
counted once; the cut's parameters to the parameter; the planted faults
giving another model."""
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
from benchmark.families import nemotron_h  # noqa: E402

SEED = 2 ** 31 + 40
pytestmark = pytest.mark.usefixtures("layer_gauges_cleaned")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return {**config, **config["tiny"]["sizes"]}


@pytest.fixture(scope="module")
def traffic():
    # one chunk of 128 and a padded second one
    return {"batch": 2, "seq": 160, "n_batches": 4}


@pytest.fixture(scope="module")
def built(sizes, traffic):
    weights = nemotron_h.make_weights(sizes, "f32", SEED)
    batches = nemotron_h.make_batches(sizes, "f32", traffic, SEED)
    net, loss_fn = nemotron_h.build_program(sizes, "f32", weights,
                                            mx.cpu(0), batches[0][0])
    return net, loss_fn, weights, batches


def test_the_net_is_the_published_stack(built):
    net, _, weights, _ = built
    params = net._collect_params_with_prefix()
    assert set(params) == set(weights)
    blocks = [next(n for n in ("mamba", "attn", "moe")
                   if getattr(layer, n, None) is not None)
              for layer in net.layers]
    assert blocks == ["mamba", "moe", "mamba", "attn", "moe"]
    # one block and one norm a layer
    assert not any(hasattr(layer, "mlp_norm") for layer in net.layers)
    assert params["layers.1.moe.expert_bias"].grad_req == "null"
    assert params["layers.1.moe.router_weight"].shape == (16, 64)
    assert params["layers.1.moe.w_up"].shape == (4, 64, 32)
    assert params["layers.1.moe.w_down"].shape == (4, 32, 64)
    assert "layers.1.moe.w_gate" not in params
    assert params["layers.1.moe.shared.up_proj.weight"].shape == (48, 64)
    assert "layers.1.moe.shared.gate_proj.weight" not in params
    # in_proj: z (64) + x, B, C (64 + 2 x 2 x 16) + dt (8)
    assert params["layers.0.mamba.in_proj_weight"].shape == (200, 64)
    assert params["layers.0.mamba.conv_weight"].shape == (128, 4)
    assert params["layers.0.mamba.conv_bias"].shape == (128,)
    assert params["layers.0.mamba.norm_weight"].shape == (64,)
    for name in ("A_log", "dt_bias", "D"):
        assert params[f"layers.0.mamba.{name}"].shape == (8,)
    assert params["layers.3.attn.q_proj.weight"].shape == (64, 64)
    assert params["layers.3.attn.k_proj.weight"].shape == (32, 64)
    assert "layers.3.attn.g_proj.weight" not in params
    assert params["head.weight"] is not params["embed.weight"]
    f32 = {n for n in weights if str(onp.dtype(nemotron_h.param_dtype(
        n, "bf16_norm_router_f32"))) == "float32"}
    assert f32 == {n for n in weights if n.endswith(
        ("norm.weight", "norm_weight", "router_weight", "expert_bias",
         "A_log", "dt_bias", ".D"))}
    # the step sizes lie in [1e-3, 1e-1] through the softplus, the rates
    # in [1, 16], the skip about one
    dt = onp.asarray(jax.nn.softplus(weights["layers.0.mamba.dt_bias"]))
    assert 1e-3 <= dt.min() and dt.max() <= 0.1 + 1e-6
    rate = onp.exp(onp.asarray(weights["layers.0.mamba.A_log"]))
    assert 1 <= rate.min() and rate.max() <= 16
    assert onp.asarray(weights["layers.0.mamba.D"]).std() > 0.1


def test_logits_and_loss_are_the_references(sizes, built):
    net, loss_fn, weights, batches = built
    x, y = batches[0]
    with autograd.pause():
        logits = net(_wrap(x))
        loss = loss_fn(logits, _wrap(y))._data
    with jax.default_matmul_precision("highest"):
        ids = {}
        want = nemotron_h.reference_logits(sizes, weights, x,
                                           correctness.Rounding, ids)
        want_loss, _ = nemotron_h.reference_loss(
            sizes, weights, x, y, correctness.Rounding, None)
    assert logits.shape == (2, 160, 128)
    assert str(logits.dtype) == "float32"
    assert onp.allclose(logits._data, want, rtol=1e-4, atol=1e-5)
    assert onp.allclose(loss, want_loss, rtol=1e-5)
    # the program's routers chose what the reference's chose
    assert sorted(ids) == ["layers.1", "layers.4"]
    for name, chosen in ids.items():
        assert onp.array_equal(
            onp.sort(nemotron_h.PROGRAM_EXPERT_IDS[name], axis=-1),
            onp.sort(chosen, axis=-1)), name


def test_three_adam_steps_through_fuse_step_follow_the_reference(
        sizes, built):
    from mxnet_tpu.telemetry import metrics
    traced = metrics.counter("ssd_traced_total.chunked")
    before = traced.value()
    _, _, weights, batches = built
    net, loss_fn = nemotron_h.build_program(sizes, "f32", weights,
                                            mx.cpu(0), batches[0][0])
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
    # two mixers, each traced by the shape-resolving forward and by the
    # step
    assert traced.value() - before == 4
    assert "layers.1.moe.expert_bias" not in fused._trainable
    bias = net._collect_params_with_prefix()["layers.1.moe.expert_bias"]
    assert onp.array_equal(bias.data()._data,
                           weights["layers.1.moe.expert_bias"])
    ref = correctness.reference_follow(
        nemotron_h, sizes, opt, weights, batches,
        correctness.step_keys(SEED), "reference")
    ok, compared, detail = correctness.compare(
        readings.readings(), ref,
        {"loss_gap": 1e-5, "grad_gap": 1e-3, "delta_gap": 1e-3})
    assert ok, (compared, detail)
    assert detail["leaves"] == len(trainable) == 38
    # the scan's own leaves have a gradient and move
    for leaf in ("layers.0.mamba.A_log", "layers.0.mamba.dt_bias",
                 "layers.0.mamba.D", "layers.0.mamba.conv_bias",
                 "layers.2.mamba.norm_weight"):
        assert ref["grad_norms"][leaf] > 0 and ref["delta_norms"][leaf] > 0


@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "MEM-E"),
    ("hybrid_override_pattern", "MEM*"),
    ("mlp_hidden_act", "silu"), ("mamba_hidden_act", "gelu"),
    ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("attention_bias", True), ("mlp_bias", True), ("mamba_proj_bias", True),
    ("use_conv_bias", False), ("tie_word_embeddings", True),
    ("residual_in_fp32", True), ("n_shared_experts", 2),
    ("moe_latent_size", 1024), ("num_nextn_predict_layers", 1),
    ("mtp_hybrid_override_pattern", "*E"), ("sliding_window", 4096),
    ("time_step_limit", [0.0, 0.1])])
def test_from_config_refuses_what_it_would_have_to_guess(sizes, key, value):
    with pytest.raises(ValueError, match=key):
        models.NemotronHLM.from_config({**sizes, key: value})


def test_the_cut_has_its_parameters_to_the_parameter(config):
    """666,963,456 parameters: four Mamba layers of 38,744,896, one
    attention layer of 23,399,040, four expert layers of 100,125,440,
    embedding and head of 44,040,192 each, the final norm; and the
    program's net builds exactly these leaves (abstractly: nothing is
    allocated at the cut's size)."""
    shapes = nemotron_h.param_shapes(config)

    def count(prefix):
        return sum(int(onp.prod(s)) for n, (s, _) in shapes.items()
                   if n.startswith(prefix))

    c = 2688
    mamba = c * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * c + c
    attention = c * 4096 + 2 * c * 256 + 4096 * c + c
    experts = 8 * 2 * c * 1856 + 2 * c * 3712 + 128 * c + 128 + c
    assert (mamba, attention, experts) == (38744896, 23399040, 100125440)
    for i in (0, 2, 4, 7):
        assert count(f"layers.{i}.") == mamba
    assert count("layers.5.") == attention
    for i in (1, 3, 6, 8):
        assert count(f"layers.{i}.") == experts
    assert count("embed.") == count("head.") == 16384 * c == 44040192
    assert count("") == 4 * mamba + attention + 4 * experts \
        + 2 * 16384 * c + c == 666963456
    net = models.NemotronHLM.from_config(config)
    params = net._collect_params_with_prefix()
    assert {n: tuple(p.shape) for n, p in params.items()} == \
        {n: s for n, (s, _) in shapes.items()}


# ---------------------------------------------------------------------------
# the squared-ReLU expert layer
# ---------------------------------------------------------------------------

def _layer_inputs(seed=5, n=96, c=16, f=8, fs=12, routed=16):
    rng = onp.random.RandomState(seed)

    def normal(scale, *shape):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    return {"x": normal(1.0, n, c),
            "router_weight": normal(0.5, routed, c),
            "expert_bias": normal(0.1, routed),
            "w_up": normal(0.3, routed, c, f),
            "w_down": normal(0.3, routed, f, c),
            "shared.up_proj.weight": normal(0.3, fs, c),
            "shared.down_proj.weight": normal(0.3, c, fs)}


LAYER = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5}


def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once():
    """The share ties to the model: four chips, each with 4 of the 16
    squared-ReLU experts, the router, its bias and the shared expert
    whole on every chip. What the four blocks give, less the shared
    expert's part on three of them, is the uncut reference's whole
    layer."""
    from mxnet_tpu.telemetry import metrics
    p = _layer_inputs()
    q = correctness.Rounding
    routed_whole, _ = nemotron_h._experts(LAYER, p["x"], p, q, held=(0, 16))
    shared = nemotron_h._shared_expert(LAYER, p["x"], p, q)
    total = 0.0
    try:
        for lo in range(0, 16, 4):
            blk = moe.RoutedExpertsFFN(
                16, 8, 16, 3, range(lo, lo + 4), 2.5, shared_hidden=12,
                label="test.nemotron", scoring="sigmoid", activation="relu2")
            blk.initialize(mx.init.Zero())  # every leaf is set below
            for name in ("w_up", "w_down"):
                getattr(blk, name).set_data(_wrap(p[name][lo:lo + 4]))
            blk.router_weight.set_data(_wrap(p["router_weight"]))
            blk.expert_bias.set_data(_wrap(p["expert_bias"]))
            for name in ("up_proj", "down_proj"):
                getattr(blk.shared, name).weight.set_data(
                    _wrap(p[f"shared.{name}.weight"]))
            out = blk(_wrap(p["x"].reshape(2, 48, 16)))._data
            part, _ = nemotron_h._experts(
                LAYER, p["x"], {**p, **{k: p[k][lo:lo + 4] for k in
                                        ("w_up", "w_down")}},
                q, held=(lo, 4))
            assert onp.allclose(out.reshape(96, 16), part + shared,
                                atol=1e-5)
            total = total + out.reshape(96, 16)
        changed = metrics.gauge("moe_bias_changed_choice.test.nemotron")
        assert 0.0 < changed.value() < 1.0
    finally:
        for name in list(metrics.all_metrics()):
            if name.endswith("test.nemotron"):
                metrics.unregister(name)
    assert onp.allclose(total - 3 * shared, routed_whole + shared,
                        atol=2e-5)
    assert not onp.allclose(part, routed_whole, atol=1e-3)
    with pytest.raises(ValueError, match="activation"):
        moe.RoutedExpertsFFN(16, 8, 16, 3, activation="gelu")


def test_the_squared_relu_layer_takes_further_passes_and_its_gradient():
    """Every expert held here and a buffer under what the router sends:
    the further passes of the buffer and the backward pass through them
    give the masked loop's result and gradient."""
    p = _layer_inputs(n=64)
    # a bias that sends nearly every token's three choices here
    p["expert_bias"] = p["expert_bias"].at[:4].add(2.0)
    q = correctness.Rounding
    weights, ids = nemotron_h.routing(LAYER, p["x"], p["router_weight"],
                                      p["expert_bias"])

    def program(x, up, down):
        return moe.routed_experts(x, p["router_weight"], None, up, down,
                                  p["expert_bias"], k=3, held_start=0,
                                  num_held=4, scale=2.5)

    def reference(x, up, down):
        return nemotron_h._experts(LAYER, x, {**p, "w_up": up,
                                              "w_down": down}, q,
                                   held=(0, 4))[0]

    args = (p["x"], p["w_up"][:4], p["w_down"][:4])
    routed = int(jnp.sum(ids < 4))
    assert routed > moe.buffer_rows(64 * 3, 4, 16)
    assert onp.allclose(program(*args), reference(*args), atol=1e-5)
    g1 = jax.grad(lambda *a: jnp.sum(program(*a) ** 2), argnums=(0, 1, 2))(
        *args)
    g2 = jax.grad(lambda *a: jnp.sum(reference(*a) ** 2),
                  argnums=(0, 1, 2))(*args)
    for a, b in zip(g1, g2):
        assert onp.allclose(a, b, rtol=1e-4, atol=1e-5)


def test_a_planted_fault_is_another_model(sizes, built):
    """What the chip's ``correct`` must tell apart, at the tiny sizes:
    the decay left at 1, the skip left out and the experts' square left
    out each give another loss on the same weights; a head reading
    another group's B and C gives another mixer output tensor for
    tensor, which the witness compares."""
    _, _, weights, batches = built
    x, y = batches[0]

    def loss(fault):
        with jax.default_matmul_precision("highest"):
            return float(jnp.mean(nemotron_h.reference_loss(
                {**sizes, "planted_fault": fault}, weights, x, y,
                correctness.Rounding, None)[0]))

    sound = loss(None)
    for fault in ("decay_one", "no_skip", "relu_not_squared"):
        assert abs(loss(fault) - sound) > 1e-5 * sound, fault
    own = nemotron_h._own(weights, "layers.0.mamba.")
    h = jax.random.normal(jax.random.key(3), (2, 160, 64))
    with jax.default_matmul_precision("highest"):
        sound = nemotron_h._mamba_mixer(sizes, h, own, correctness.Rounding)
        moved = nemotron_h._mamba_mixer(
            {**sizes, "planted_fault": "groups_by_modulo"}, h, own,
            correctness.Rounding)
    # 2e-3 of the output at the tiny widths, 70 times the tiny witness
    # limit: the skip D x carries most of an untrained mixer's output
    assert float(jnp.linalg.norm(moved - sound)) \
        > 1e-3 * float(jnp.linalg.norm(sound))
    assert set(nemotron_h.FAULTS) == {"decay_one", "no_skip",
                                      "groups_by_modulo", "relu_not_squared"}
