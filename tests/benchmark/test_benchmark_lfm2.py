"""The ``lfm2_moe`` family's configuration against the source's row, its
counts against its shapes by hand, its seed rule, and the cell's new
per-layer readers on a hand-made run: scope paths as the step's HLO
writes them with made-up event times. The ``--tiny`` cell itself (sound
run, control, faults) runs under the tests that take every cell of the
manifest (``test_benchmark_correct.py``, ``test_benchmark_faults.py``);
the traced tiny run and what it puts on record are here.
"""
import importlib

import numpy as onp
import pytest

from bench_helpers import load, manifest, run_harness
from benchmark import scope_paths
from benchmark.families import lfm2_moe

CELL = "lfm2_8b_a1b_train_b1_t8192"
NEW_METRICS = ("conv_ms_per_step", "short_conv_ms_per_step",
               "conv_roofline", "full_attention_roofline")
# the source's config.json as the catalog of public architectures gives
# it (LiquidAI/LFM2-8B-A1B), every key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"]}


@pytest.fixture(scope="module")
def config():
    return load("benchmark", "configs", "lfm2_8b_a1b.json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmark", "traffic", "train_b1_t8192.json")


def tiny(config, mix):
    over = config["tiny"]
    return {**config, **over["sizes"]}, {**mix, **over["traffic"]}


def test_the_file_is_the_sources_config_but_for_the_cut(config):
    cut = {"num_hidden_layers": 6, "num_experts": 8, "vocab_size": 16384}
    assert config["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        assert config[key] == cut.get(key, value), key
    dep = config["deployment"]
    assert dep["chips_per_layer"] == 4
    assert dep["experts_held"] == [0, 8]
    assert dep["num_experts_published"] == PUBLISHED["num_experts"] \
        == 4 * config["num_experts"]
    assert dep["vocab_size_published"] == PUBLISHED["vocab_size"] \
        == 4 * config["vocab_size"]
    assert dep["num_hidden_layers_published"] == 24
    assert set(config["assumed"]) >= {
        "tie", "expert_bias", "router", "qk_norm", "short_conv",
        "final_norm", "initializer", "loss", "data", "rng"}
    assert config["dtype_policy"] == "bf16_norm_router_f32"
    assert config["control_precision"] == "fp8"
    # the floors: both dense layers, a whole period and four layers
    # after them, 8 experts, an eighth of the vocabulary
    plan = lfm2_moe.layer_plan(config)
    assert [(p["conv"], p["sparse"]) for p in plan] == [
        (True, False), (True, False), (False, True), (True, True),
        (True, True), (True, True)]
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "lfm2_8b_a1b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_parameters_by_hand(config):
    """568.6 M here: a dense conv layer 60.8 M, the attention layer with
    its experts 98.6 M, a conv layer with its experts 104.9 M, the tied
    embedding 33.6 M."""
    shapes = lfm2_moe.param_shapes(config)

    def count(prefix):
        return sum(int(onp.prod(s)) for n, (s, _) in shapes.items()
                   if n.startswith(prefix))

    c = 2048
    conv = c * 3 * c + c * 3 + c * c
    attn = 2 * c * 32 * 64 + 2 * c * 8 * 64 + 2 * 64
    experts = 32 * c + 32 + 8 * 3 * c * 1792
    assert count("layers.0.") == conv + 3 * c * 7168 + 2 * c
    assert count("layers.1.") == count("layers.0.")
    assert count("layers.2.") == attn + experts + 2 * c
    assert count("layers.3.") == conv + experts + 2 * c
    assert count("layers.4.") == count("layers.5.") == count("layers.3.")
    assert count("embed.") == 16384 * c and "head.weight" not in shapes
    total = 2 * count("layers.0.") + count("layers.2.") \
        + 3 * count("layers.3.") + 16384 * c + c
    assert count("") == total == 568647936
    assert total == pytest.approx(568.6e6, rel=1e-4)
    assert count("layers.0.") == pytest.approx(60.8e6, rel=1e-3)
    assert count("layers.2.") == pytest.approx(98.6e6, rel=1e-3)
    assert count("layers.3.") == pytest.approx(104.9e6, rel=1e-3)
    f32 = {n for n in shapes if str(onp.dtype(lfm2_moe.param_dtype(
        n, "bf16_norm_router_f32"))) == "float32"}
    assert f32 == {n for n in shapes if n.endswith(
        ("norm.weight", "router_weight", "expert_bias"))}
    assert lfm2_moe.is_state("layers.2.moe.expert_bias")
    assert lfm2_moe.is_state("head.weight")
    assert not lfm2_moe.is_state("embed.weight")


def test_needed_work_by_hand(config, mix):
    t, c = 8192, 2048
    assert lfm2_moe.expected_rows(config, mix) == 8192    # 8192 x 4 / 4
    assert lfm2_moe.work_units(config, mix) == {"tokens": 8192}
    layers = {n: (f, b) for n, f, b in lfm2_moe.matrix_layers(config, mix)}
    assert layers["layers.0.conv.in_proj"] == (
        3 * 2 * t * c * 3 * c, 3 * 2 * (t * 4 * c + 3 * c * c))
    assert layers["layers.0.conv.out_proj"][0] == 3 * 2 * t * c * c
    assert layers["layers.1.mlp.down_proj"] == (
        3 * 2 * t * 7168 * c, 3 * 2 * (t * (7168 + c) + 7168 * c))
    assert layers["layers.2.attn.qkv"][0] == 3 * 2 * t * c * (32 + 16) * 64
    # the causal half, 32 heads of 64, forward once and backward twice
    assert layers["layers.2.attn.products"] == (
        3 * 2 * 2 * 32 * (t * (t + 1) // 2) * 64,
        3 * 2 * t * 64 * (2 * 32 + 2 * 8))
    assert layers["layers.2.moe.router"][0] == 3 * 2 * t * c * 32
    assert layers["layers.3.moe.experts"] == (
        3 * 3 * 2 * 8192 * c * 1792,
        3 * 2 * (3 * 8192 * (c + 1792) + 3 * 8 * c * 1792))
    assert layers["head"][0] == 3 * 2 * t * c * 16384
    assert "layers.2.conv.in_proj" not in layers
    assert "layers.0.moe.experts" not in layers
    total = lfm2_moe.needed_flops(config, mix)
    assert total == sum(f for f, _ in layers.values())
    # 6 FLOPs a token over 260.3 M active parameters, and the attention
    products = 6 * 8192 * (2 * 60.82e6 + 10.49e6 + 0.26e6 + 4 * 11.01e6
                           + 3 * 16.78e6 + 33.55e6)
    assert total - layers["layers.2.attn.products"][0] == \
        pytest.approx(products, rel=2e-3)
    assert total == pytest.approx(13.6e12, rel=0.01)
    assert lfm2_moe.attention_products(config, mix, windowed=True) == (0, 0)
    assert lfm2_moe.attention_products(config, mix, windowed=False) == \
        layers["layers.2.attn.products"]
    # the five mixers, each whole: 3 x 2 x T x 4 C^2 FLOPs; x, y, the
    # filter and the two matrices once a pass
    assert lfm2_moe.conv_block_work(config, mix) == (
        5 * 3 * 2 * t * 4 * c * c,
        5 * 3 * 2 * (2 * t * c + 3 * c + 4 * c * c))


def test_same_seed_same_batches_and_weights(config, mix):
    sizes, traffic = tiny(config, mix)
    policy = sizes["dtype_policy"]
    big = 2 ** 31 + 5  # more than 32 signed bits hold
    a = lfm2_moe.make_batches(sizes, policy, traffic, big)
    b = lfm2_moe.make_batches(sizes, policy, traffic, big)
    c = lfm2_moe.make_batches(sizes, policy, traffic, 5)
    assert len(a) == traffic["n_batches"]
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert onp.array_equal(xa, xb) and onp.array_equal(ya, yb)
        assert not onp.array_equal(xa, xc)
        assert int(xa.max()) < sizes["vocab_size"] and int(xa.min()) >= 0
        assert int(ya.max()) < sizes["vocab_size"]
    assert not onp.array_equal(a[0][0][0], a[0][0][1])
    assert not onp.array_equal(a[0][0], a[1][0])
    wa = lfm2_moe.make_weights(sizes, policy, big)
    wb = lfm2_moe.make_weights(sizes, policy, big)
    wc = lfm2_moe.make_weights(sizes, policy, 5)
    assert all(onp.array_equal(wa[n], wb[n]) for n in wa)
    assert not onp.array_equal(wa["embed.weight"], wc["embed.weight"])
    assert set(wa) == set(lfm2_moe.param_shapes(sizes)) | {"head.weight"}
    assert wa["head.weight"] is wa["embed.weight"]
    # the selection bias is drawn, not left at its shipped zeros
    bias = onp.asarray(wa["layers.2.moe.expert_bias"])
    assert bias.shape == (8,) and onp.abs(bias).min() > 0
    assert onp.abs(bias).max() < 5 * lfm2_moe.BIAS_STD
    assert not onp.array_equal(bias, wc["layers.2.moe.expert_bias"])


# ---------------------------------------------------------------------------
# the readers on a hand-made run
# ---------------------------------------------------------------------------

PRE = "jit(pure_step)/jvp(forward)/layers/"
BWD = "jit(pure_step)/transpose(jvp(forward))/layers/"
PATHS = {
    "fusion.1": PRE + "0/conv/in_proj/dot_general",
    "fusion.2": PRE + "0/conv/mix/jit(short_conv)/mul",
    "fusion.3": BWD + "0/conv/mix/jit(short_conv)/reduce_sum",
    "fusion.4": BWD + "3/conv/out_proj/dot_general",
    "fusion.5": PRE + "2/attn/jit(_causal_attention)/full/"
                      "jit(banded_attention)/pallas_call",
    "fusion.6": BWD + "2/attn/jit(_causal_attention)/full/"
                      "jit(banded_attention)/pallas_call",
    "fusion.7": PRE + "2/attn/q_norm/jit(_rms_norm)/mul",
    "fusion.8": PRE + "2/moe/jit(routed_experts)/route/logistic",
    "fusion.9": "jit(pure_step)/optimizer/mul",
    "fusion.10": PRE + "0/mlp/gate_proj/dot_general",
}
HLO = "\n".join(f'  %{name} = f32[8]{{0}} fusion(), kind=kLoop, '
                f'metadata={{op_name="{path}"}}'
                for name, path in PATHS.items())
# seconds over a window of two steps
SECONDS = {"fusion.1": 0.010, "fusion.2": 0.001, "fusion.3": 0.003,
           "fusion.4": 0.006, "fusion.5": 0.012, "fusion.6": 0.028,
           "fusion.7": 0.002, "fusion.8": 0.001, "fusion.9": 0.002,
           "fusion.10": 0.020}


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


class FakeRun:
    def __init__(self, config, mix, seed=1):
        self.result = {"hlo_text": HLO, "detail": {}}
        self.summary = {"op_seconds": dict(SECONDS), "steps": 2,
                        "busy_s": sum(SECONDS.values())}
        self.sizes, self.traffic, self.family = config, mix, lfm2_moe
        self.policy, self.seed = config["dtype_policy"], seed
        self.device, self.peaks = Device, load("benchmark", "peaks.json")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_device_time_under_the_new_scopes(config, mix):
    run = FakeRun(config, mix)
    assert reader("conv_ms_per_step")(run) == pytest.approx(1e3 * 0.020 / 2)
    assert reader("short_conv_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.004 / 2)
    # the full layer under the name Laguna's full layers use: the
    # accepted reader finds it; its norms and the router lie outside
    assert reader("full_attention_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.040 / 2)
    assert reader("moe_ms_per_step")(run) == pytest.approx(1e3 * 0.001 / 2)
    assert scope_paths.ms_per_step(run, ("attn",)) == \
        pytest.approx(1e3 * 0.042 / 2)


def test_rooflines_take_the_familys_counts(config, mix):
    run = FakeRun(config, mix)
    flops, nbytes = lfm2_moe.conv_block_work(config, mix)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12     # the products bind the mixer
    assert least == pytest.approx(20.93e-3, rel=1e-3)
    assert reader("conv_roofline")(run) == \
        pytest.approx(100 * least / 0.010)
    flops, nbytes = lfm2_moe.attention_products(config, mix, False)
    assert flops / 197e12 > nbytes / 819e9
    assert reader("full_attention_roofline")(run) == \
        pytest.approx(100 * (flops / 197e12) / 0.020)
    # a family without mixers of this kind reports nothing
    run.family = importlib.import_module("benchmark.families.laguna")
    assert reader("conv_roofline")(run) is None


def test_the_programs_counters_go_on_record(config, mix):
    from mxnet_tpu.telemetry import metrics
    run = FakeRun(config, mix)
    kernel = metrics.counter("attention_traced_total.kernel")
    before = kernel.value()
    kernel.inc()
    metrics.gauge("moe_bias_changed_choice.layers.2").set(0.25)
    try:
        reader("full_attention_roofline")(run)
        detail = run.result["detail"]
        assert detail["attention_backend"]["kernel"] == before + 1
        assert detail["moe_bias_changed_choice"] == {"layers.2": 0.25}
    finally:
        metrics.unregister("moe_bias_changed_choice.layers.2")
        metrics.unregister("attention_traced_total.kernel")


def test_without_scope_names_the_readers_give_nothing(config, mix):
    run = FakeRun(config, mix)
    run.result["hlo_text"] = HLO.replace("metadata=", "meta=")
    for name in NEW_METRICS:
        assert reader(name)(run) is None


def test_the_manifest_has_the_cell_and_its_metrics():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lfm2_8b_a1b", "train_b1_t8192", 1)
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    by_name = {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}
    for name in ("tokens_per_s", "moe_ms_per_step", "moe_experts_roofline",
                 "moe_load_max_over_mean", "moe_overflow_layers",
                 "full_attention_ms_per_step"):
        assert by_name[name]["workloads"][-1] == CELL
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "Kernels"
        assert by_name[name]["moves"] == "step_ms"
    assert CELL not in by_name["window_attention_ms_per_step"]["workloads"]


def test_the_traced_tiny_run_reports_the_gauges_and_the_flips():
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 91), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert last["metrics"]["moe_overflow_layers"]["value"] == 0
    assert last["metrics"]["recompiles_in_window"]["value"] == 0
    # float32 at the tiny sizes: the program's and the reference's
    # routers agree on every row
    assert last["detail"]["top_k_sets_differ_share"] == 0.0
    # the four expert layers, each with its share of changed choices;
    # the full layer was traced as the composition: there is no chip
    changed = last["detail"]["moe_bias_changed_choice"]
    assert sorted(changed) == ["layers.2", "layers.3", "layers.4",
                               "layers.5"]
    assert all(0.0 <= v <= 1.0 for v in changed.values())
    assert max(changed.values()) > 0.0
    assert last["detail"]["attention_backend"] == {"dense": 2}
    # nothing of a device trace under a device metric's name on the CPU
    for name in NEW_METRICS + ("step_mfu", "mxu_roofline", "moe_ms_per_step",
                               "full_attention_ms_per_step"):
        assert name not in last["metrics"]
