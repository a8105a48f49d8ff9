"""``models.LingHybridLM`` at the configuration's ``tiny`` sizes against
the family's plain reference (``benchmark/families/ling_hybrid.py``) on
seeded weights: the stack, logits and loss, three Adam steps through
``Trainer.fuse_step``; what ``from_config`` refuses; the group-limited
routing rule on a hand-made case and against the reference's; the shares
of the expert layer adding up to the uncut layer, the shared expert
counted once; the planted faults giving another model."""
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
from benchmark.families import ling_hybrid  # noqa: E402

SEED = 2 ** 31 + 35
pytestmark = pytest.mark.usefixtures("layer_gauges_cleaned")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling_3_flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return {**config, **config["tiny"]["sizes"]}


@pytest.fixture(scope="module")
def traffic():
    # one chunk and a padded second one
    return {"batch": 2, "seq": 80, "n_batches": 4}


@pytest.fixture(scope="module")
def built(sizes, traffic):
    weights = ling_hybrid.make_weights(sizes, "f32", SEED)
    batches = ling_hybrid.make_batches(sizes, "f32", traffic, SEED)
    net, loss_fn = ling_hybrid.build_program(sizes, "f32", weights,
                                             mx.cpu(0), batches[0][0])
    return net, loss_fn, weights, batches


def test_the_net_is_the_published_stack(built, sizes):
    net, _, weights, _ = built
    params = net._collect_params_with_prefix()
    assert set(params) == set(weights)
    kinds = [("attn" if hasattr(layer, "attn") else "kda",
              "moe" if hasattr(layer, "moe") else "mlp")
             for layer in net.layers]
    # layer_group_size 3 at the tiny sizes: the latent layer closes a
    # group; one leading dense FFN
    assert kinds == [("kda", "mlp"), ("kda", "moe"), ("attn", "moe"),
                     ("kda", "moe")]
    assert params["layers.1.moe.expert_bias"].grad_req == "null"
    assert params["layers.1.moe.router_weight"].shape == (16, 64)
    assert params["layers.1.moe.w_gate"].shape == (4, 64, 32)
    assert params["layers.1.moe.shared.gate_proj.weight"].shape == (32, 64)
    assert params["layers.0.kda.q_filter"].shape == (64, 4)
    assert params["layers.0.kda.f_proj_weight"].shape == (64, 64)
    assert params["layers.0.kda.b_proj_weight"].shape == (4, 64)
    assert params["layers.0.kda.A_log"].shape == (4,)
    assert params["layers.0.kda.dt_bias"].shape == (64,)
    assert params["layers.0.kda.o_norm_weight"].shape == (16,)
    assert params["layers.2.attn.q_proj.weight"].shape == (4 * 24, 64)
    assert params["layers.2.attn.kv_a_proj.weight"].shape == (32 + 8, 64)
    assert params["layers.2.attn.kv_b_proj.weight"].shape == (4 * 32, 32)
    assert params["layers.2.attn.kv_norm.weight"].shape == (32,)
    assert params["head.weight"] is not params["embed.weight"]
    f32 = {n for n in weights if str(onp.dtype(ling_hybrid.param_dtype(
        n, "bf16_norm_router_f32"))) == "float32"}
    assert f32 == {n for n in weights if n.endswith(
        ("norm.weight", "o_norm_weight", "router_weight", "expert_bias",
         "A_log", "dt_bias"))}
    # the decay is drawn away from zero and from one head to the next
    a_log = onp.asarray(weights["layers.0.kda.A_log"])
    assert onp.abs(a_log).max() > 0.1
    assert onp.asarray(weights["layers.0.kda.dt_bias"]).mean() < -3


def test_logits_and_loss_are_the_references(sizes, built):
    net, loss_fn, weights, batches = built
    x, y = batches[0]
    with autograd.pause():
        logits = net(_wrap(x))
        loss = loss_fn(logits, _wrap(y))._data
    with jax.default_matmul_precision("highest"):
        ids = {}
        want = ling_hybrid.reference_logits(sizes, weights, x,
                                            correctness.Rounding, ids)
        want_loss, _ = ling_hybrid.reference_loss(
            sizes, weights, x, y, correctness.Rounding, None)
    assert logits.shape == (2, 80, 128)
    assert str(logits.dtype) == "float32"
    assert onp.allclose(logits._data, want, rtol=1e-4, atol=1e-5)
    assert onp.allclose(loss, want_loss, rtol=1e-5)
    # the program's routers chose what the reference's chose
    assert sorted(ids) == ["layers.1", "layers.2", "layers.3"]
    for name, chosen in ids.items():
        assert onp.array_equal(
            onp.sort(ling_hybrid.PROGRAM_EXPERT_IDS[name], axis=-1),
            onp.sort(chosen, axis=-1)), name


def test_three_adam_steps_through_fuse_step_follow_the_reference(
        sizes, traffic):
    weights = ling_hybrid.make_weights(sizes, "f32", SEED)
    batches = ling_hybrid.make_batches(sizes, "f32", traffic, SEED)
    net, loss_fn = ling_hybrid.build_program(sizes, "f32", weights,
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
    assert "layers.1.moe.expert_bias" not in fused._trainable
    bias = net._collect_params_with_prefix()["layers.1.moe.expert_bias"]
    assert onp.array_equal(bias.data()._data,
                           weights["layers.1.moe.expert_bias"])
    ref = correctness.reference_follow(
        ling_hybrid, sizes, opt, weights, batches,
        correctness.step_keys(SEED), "reference")
    ok, compared, detail = correctness.compare(
        readings.readings(), ref,
        {"loss_gap": 1e-5, "grad_gap": 1e-3, "delta_gap": 1e-3})
    assert ok, (compared, detail)
    assert detail["leaves"] == len(trainable) == 80
    # the decay's own leaves have a gradient and move
    for leaf in ("layers.0.kda.A_log", "layers.0.kda.dt_bias",
                 "layers.0.kda.q_filter", "layers.2.attn.kv_norm.weight"):
        assert ref["grad_norms"][leaf] > 0 and ref["delta_norms"][leaf] > 0


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("use_kda_lora", True),
    ("num_nextn_predict_layers", 1), ("value_norm", True),
    ("up_proj_norm", True), ("use_nGPT", True),
    ("scale_router_input", True), ("topk_method", "greedy"),
    ("expert_swiglu_limit_list", [0, 0, 4] + [0] * 39),
    ("share_expert_swiglu_limit_list", [5] + [0] * 41),
    ("num_kv_heads_for_linear_attn", 2), ("rope_scaling", {"factor": 4}),
    ("score_function", "softmax")])
def test_from_config_refuses_what_it_would_have_to_guess(sizes, key, value):
    with pytest.raises(ValueError, match=key):
        models.LingHybridLM.from_config({**sizes, key: value})


def test_from_config_reads_past_the_layers_kept(sizes):
    """A SwiGLU limit beyond the layers kept (the source's 35-41) is no
    layer of this net: the published lists are copied whole."""
    limits = [0] * 35 + [4] * 7
    models.LingHybridLM.from_config(
        {**sizes, "expert_swiglu_limit_list": limits})
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        models.LingHybridLM.from_config(
            {**sizes, "expert_swiglu_limit_list": limits,
             "num_hidden_layers": 36})


# ---------------------------------------------------------------------------
# the third routing rule
# ---------------------------------------------------------------------------

def _logit(p):
    return onp.log(p) - onp.log1p(-p)


def test_the_group_limit_changes_the_choice_on_a_hand_made_case():
    """8 experts in 4 groups of 2, the 2 best groups stay, 2 experts a
    token. Scores: group 0 (0.9, 0.1) sums 1.0, group 1 (0.6, 0.6) 1.2,
    group 2 (0.7, 0.55) 1.25, group 3 (0.2, 0.2) 0.4: groups 2 and 1
    stay, so the token takes experts 4 and 2 (a tie inside group 1: the
    lower index), where the plain rule takes 0 and 4."""
    scores = onp.array([0.9, 0.1, 0.6, 0.6, 0.7, 0.55, 0.2, 0.2])
    router_w = jnp.asarray(_logit(scores))[:, None]        # (8, 1)
    x = jnp.ones((1, 1))
    zero = jnp.zeros((8,))
    w, ids = moe.route_top_k(x, router_w, 2, 2.5, zero, (4, 2))
    assert ids.tolist() == [[4, 2]]
    assert onp.allclose(w, 2.5 * onp.array([[0.7, 0.6]]) / (1.3 + 1e-6),
                        rtol=1e-6)
    _, plain = moe.route_top_k(x, router_w, 2, 2.5, zero)
    assert plain.tolist() == [[0, 4]]
    # the bias moves the groups' scores and never the weights
    bias = zero.at[0].set(0.3)           # group 0: 1.2 + 0.1 = 1.3
    w, ids = moe.route_top_k(x, router_w, 2, 2.5, bias, (4, 2))
    assert ids.tolist() == [[0, 4]]
    assert onp.allclose(w, 2.5 * onp.array([[0.9, 0.7]]) / (1.6 + 1e-6),
                        rtol=1e-6)
    # two groups tied for the last place: the lower index stays
    tied = onp.array([0.5, 0.5, 0.9, 0.3, 0.6, 0.6, 0.2, 0.2])
    _, ids = moe.route_top_k(x, jnp.asarray(_logit(tied))[:, None], 2, 1.0,
                             zero, (4, 1))
    assert ids.tolist() == [[2, 3]]      # groups 1 and 2 both sum 1.2
    share = moe.group_limit_changed_share(
        x, router_w, zero, k=2, groups=(4, 2))
    assert float(share) == 1.0


def _layer_inputs(seed=5, n=96, c=16, f=8, routed=16):
    ks = jax.random.split(jax.random.key(seed), 9)
    ffn = {"gate_proj": (f, c), "up_proj": (f, c), "down_proj": (c, f)}
    p = {"x": jax.random.normal(ks[0], (n, c)),
         "router_weight": 0.5 * jax.random.normal(ks[1], (routed, c)),
         "expert_bias": 0.1 * jax.random.normal(ks[2], (routed,)),
         "w_gate": 0.3 * jax.random.normal(ks[3], (routed, c, f)),
         "w_up": 0.3 * jax.random.normal(ks[4], (routed, c, f)),
         "w_down": 0.3 * jax.random.normal(ks[5], (routed, f, c))}
    for key, (name, shape) in zip(ks[6:], ffn.items()):
        p[f"shared.{name}.weight"] = 0.3 * jax.random.normal(key, shape)
    return p


LAYER = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
         "n_group": 4, "topk_group": 2}


def test_grouped_routing_is_the_references():
    p = _layer_inputs()
    want_w, want_i = ling_hybrid.routing(LAYER, p["x"], p["router_weight"],
                                         p["expert_bias"])
    got_w, got_i = moe.route_top_k(p["x"], p["router_weight"], 3, 2.5,
                                   p["expert_bias"], (4, 2))
    assert onp.array_equal(got_i, want_i)
    # (the reference adds the source's 1e-20 under the sum of three
    # scores, the program its one constant 1e-6)
    assert onp.allclose(got_w, want_w, rtol=3e-6)
    # every choice lies in two groups of four, and the limit matters
    assert int(jnp.max(jnp.sum(jnp.any(
        (got_i // 4)[..., None] == jnp.arange(4), axis=1), axis=-1))) <= 2
    share = float(moe.group_limit_changed_share(
        p["x"], p["router_weight"], p["expert_bias"], k=3, groups=(4, 2)))
    assert 0.2 < share < 1.0
    loose, _ = ling_hybrid.routing({**LAYER, "planted_fault":
                                    "no_group_limit"}, p["x"],
                                   p["router_weight"], p["expert_bias"])
    assert not onp.allclose(loose, want_w)
    with pytest.raises(ValueError, match="groups"):
        moe.RoutedExpertsFFN(16, 8, 16, 3, groups=(4, 2))   # softmax
    with pytest.raises(ValueError, match="groups"):
        moe.RoutedExpertsFFN(16, 8, 16, 9, scoring="sigmoid",
                             groups=(4, 2))   # 8 candidates for 9


def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once():
    """The share ties to the model: four chips, each with 4 of the 16
    experts (one group each), the router, its bias and the shared expert
    whole on every chip. What the four blocks give, less the shared
    expert's part on three of them, is the uncut reference's whole
    layer."""
    from mxnet_tpu.telemetry import metrics
    p = _layer_inputs()
    q = correctness.Rounding
    routed_whole, _ = ling_hybrid._experts(LAYER, p["x"], p, q, held=(0, 16))
    shared = ling_hybrid._shared_expert(p["x"], p, q)
    total = 0.0
    try:
        for lo in range(0, 16, 4):
            blk = moe.RoutedExpertsFFN(
                16, 8, 16, 3, range(lo, lo + 4), 2.5, shared_hidden=8,
                label="test.ling", scoring="sigmoid", groups=(4, 2))
            blk.initialize()
            for name in ("w_gate", "w_up", "w_down"):
                getattr(blk, name).set_data(_wrap(p[name][lo:lo + 4]))
            blk.router_weight.set_data(_wrap(p["router_weight"]))
            blk.expert_bias.set_data(_wrap(p["expert_bias"]))
            for name in ("gate_proj", "up_proj", "down_proj"):
                getattr(blk.shared, name).weight.set_data(
                    _wrap(p[f"shared.{name}.weight"]))
            out = blk(_wrap(p["x"].reshape(2, 48, 16)))._data
            part, _ = ling_hybrid._experts(
                LAYER, p["x"], {**p, **{k: p[k][lo:lo + 4] for k in
                                        ("w_gate", "w_up", "w_down")}},
                q, held=(lo, 4))
            assert onp.allclose(out.reshape(96, 16), part + shared,
                                atol=1e-5)
            total = total + out.reshape(96, 16)
        changed = metrics.gauge(
            "moe_group_limit_changed_choice.test.ling").value()
        assert 0.0 < changed < 1.0
    finally:
        for name in list(metrics.all_metrics()):
            if name.endswith("test.ling"):
                metrics.unregister(name)
    assert onp.allclose(total - 3 * shared, routed_whole + shared,
                        atol=2e-5)
    assert not onp.allclose(part, routed_whole, atol=1e-3)


def test_a_planted_fault_is_another_model(sizes, built):
    """What the chip's ``correct`` must tell apart, at the tiny sizes:
    the decay left at 1 and the group limit left out each give another
    loss on the same weights; the rotary key left unrotated gives
    another latent block (at the tiny widths its scores are too flat to
    move the loss beyond rounding, so the block is asked itself, on
    weights ten times as large)."""
    _, _, weights, batches = built
    x, y = batches[0]

    def loss(fault):
        with jax.default_matmul_precision("highest"):
            return float(jnp.mean(ling_hybrid.reference_loss(
                {**sizes, "planted_fault": fault}, weights, x, y,
                correctness.Rounding, None)[0]))

    sound = loss(None)
    for fault in ("decay_one", "no_group_limit"):
        assert abs(loss(fault) - sound) > 1e-5 * sound, fault
    own = {n[len("layers.2.attn."):]: 10 * w for n, w in weights.items()
           if n.startswith("layers.2.attn.")}
    h = jax.random.normal(jax.random.key(3), (2, 80, 64))
    with jax.default_matmul_precision("highest"):
        turned = ling_hybrid._latent_mixer(sizes, h, own,
                                           correctness.Rounding)
        left = ling_hybrid._latent_mixer(
            {**sizes, "planted_fault": "rope_key_unrotated"}, h, own,
            correctness.Rounding)
    assert float(jnp.max(jnp.abs(turned - left))) \
        > 1e-3 * float(jnp.max(jnp.abs(turned)))
    assert set(ling_hybrid.FAULTS) == {"decay_one", "no_group_limit",
                                       "rope_key_unrotated"}
