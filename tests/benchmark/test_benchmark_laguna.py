"""The ``laguna`` family's counts against its shapes, its seed rule, and
the new per-layer readers on a hand-made run: scope paths as the step's
HLO writes them with made-up event times, and the program's gauges.
The ``--tiny`` cell itself (sound run, control, faults) runs under the
tests that take every cell of the manifest
(``test_benchmark_correct.py``, ``test_benchmark_faults.py``); the
traced tiny run and its new metric are here.
"""
import importlib

import numpy as onp
import pytest

from bench_helpers import load, run_harness
from benchmark import scope_paths
from benchmark.families import laguna

CELL = "laguna_xs2_train_b1_t8192"
NEW_METRICS = ("window_attention_ms_per_step", "full_attention_ms_per_step",
               "window_attention_roofline", "moe_ms_per_step",
               "moe_experts_roofline", "moe_load_max_over_mean")


@pytest.fixture(scope="module")
def config():
    return load("benchmark", "configs", "laguna_xs2.json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmark", "traffic", "train_b1_t8192.json")


def tiny(config, mix):
    over = config["tiny"]
    return {**config, **over["sizes"]}, {**mix, **over["traffic"]}


def test_the_file_is_the_catalogs_row_but_for_the_cut(config):
    """Every number of the source's config under its key, but for the
    three keys listed in ``reduced``; the lists a layer are whole."""
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 32, 12544)
    published = {"hidden_size": 2048, "intermediate_size": 8192,
                 "num_attention_heads": 48, "num_key_value_heads": 8,
                 "head_dim": 128, "max_position_embeddings": 262144,
                 "rms_norm_eps": 1e-06, "num_experts_per_tok": 8,
                 "moe_intermediate_size": 512,
                 "shared_expert_intermediate_size": 512,
                 "sliding_window": 512, "moe_routed_scaling_factor": 2.5,
                 "partial_rotary_factor": 0.5}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["layer_types"]) == 40
    assert config["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64,
                                                           48]
    dep = config["deployment"]
    assert dep["chips_per_layer"] == 8
    assert dep["num_experts_published"] == 8 * config["num_experts"]
    assert dep["vocab_size_published"] == 8 * config["vocab_size"]
    assert set(config["assumed"]) >= {"gating", "router", "hidden_act",
                                      "qk_norm", "rope", "initializer"}


def test_parameters_by_hand(config):
    """691.6 M here: layer 0 79.8 M, a sliding expert layer 142.2 M, the
    full expert layer 133.8 M, embedding and head 51.4 M."""
    shapes = laguna.param_shapes(config)

    def count(prefix):
        return sum(onp.prod(s) for n, (s, _) in shapes.items()
                   if n.startswith(prefix))

    c, d = 2048, 128
    attn = lambda h: c * (h * d) * 2 + 2 * c * 8 * d + c * h
    expert_layer = 256 * c + 3 * c * 512 + 32 * 3 * c * 512 + 2 * c
    assert count("layers.0.") == attn(48) + 3 * c * 8192 + 2 * c
    assert count("layers.1.") == attn(64) + expert_layer
    assert count("layers.4.") == attn(48) + expert_layer
    assert count("layers.0.") == pytest.approx(79.8e6, rel=2e-3)
    assert count("layers.1.") == pytest.approx(142.2e6, rel=2e-3)
    assert count("layers.4.") == pytest.approx(133.8e6, rel=2e-3)
    assert count("embed.") + count("head.") == 2 * 12544 * c
    assert count("") == pytest.approx(691.6e6, rel=1e-3)
    assert laguna.param_dtype("layers.1.moe.router_weight",
                              "bf16_norm_router_f32") == onp.float32
    assert laguna.param_dtype("layers.1.attn_norm.weight",
                              "bf16_norm_router_f32") == onp.float32
    assert str(onp.dtype(laguna.param_dtype(
        "layers.1.moe.w_gate", "bf16_norm_router_f32"))) == "bfloat16"


def test_needed_work_by_hand(config, mix):
    t = 8192
    assert laguna.allowed_pairs(t, None) == t * (t + 1) // 2
    assert laguna.allowed_pairs(t, 512) == 512 * 513 // 2 + (t - 512) * 512
    assert laguna.allowed_pairs(8, 512) == 36          # window over T
    assert laguna.expected_rows(config, mix) == 8192   # 8192 x 8 / 8
    assert laguna.work_units(config, mix) == {"tokens": 8192}
    layers = {n: (f, b) for n, f, b in laguna.matrix_layers(config, mix)}
    # by hand: a sliding layer's two products over the band, 64 heads
    assert layers["layers.1.attn.products"][0] == \
        3 * 2 * 2 * 64 * (512 * 513 // 2 + 7680 * 512) * 128
    # a full layer's over the causal half, 48 heads: 6.2 times a
    # sliding layer's
    assert layers["layers.0.attn.products"][0] == \
        3 * 2 * 2 * 48 * (8192 * 8193 // 2) * 128
    assert layers["layers.0.attn.products"][0] \
        / layers["layers.1.attn.products"][0] == pytest.approx(6.2, 0.01)
    # the three grouped products at the expected rows, bf16
    assert layers["layers.2.moe.experts"] == (
        3 * 3 * 2 * 8192 * 2048 * 512,
        3 * 2 * (3 * 8192 * (2048 + 512) + 3 * 32 * 2048 * 512))
    assert layers["layers.0.mlp.gate_proj"] == (
        3 * 2 * 8192 * 2048 * 8192,
        3 * 2 * (8192 * (2048 + 8192) + 2048 * 8192))
    assert layers["head"][0] == 3 * 2 * 8192 * 2048 * 12544
    assert "layers.0.moe.experts" not in layers
    total = laguna.needed_flops(config, mix)
    assert total == sum(f for f, _ in layers.values())
    assert total == pytest.approx(19.0e12, rel=0.05)
    f_w, b_w = laguna.attention_products(config, mix, windowed=True)
    f_f, _ = laguna.attention_products(config, mix, windowed=False)
    assert f_w == 3 * layers["layers.1.attn.products"][0]
    assert f_f == layers["layers.0.attn.products"][0] \
        + layers["layers.4.attn.products"][0]
    assert b_w == 3 * 3 * 2 * 8192 * 128 * (2 * 64 + 2 * 8)


def test_same_seed_same_batches_and_weights(config, mix):
    sizes, traffic = tiny(config, mix)
    policy = sizes["dtype_policy"]
    big = 2 ** 31 + 5  # more than 32 signed bits hold
    a = laguna.make_batches(sizes, policy, traffic, big)
    b = laguna.make_batches(sizes, policy, traffic, big)
    c = laguna.make_batches(sizes, policy, traffic, 5)
    assert len(a) == traffic["n_batches"]
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert onp.array_equal(xa, xb) and onp.array_equal(ya, yb)
        assert not onp.array_equal(xa, xc)
        assert int(xa.max()) < sizes["vocab_size"] and int(xa.min()) >= 0
        assert int(ya.max()) < sizes["vocab_size"]
    assert not onp.array_equal(a[0][0][0], a[0][0][1])
    assert not onp.array_equal(a[0][0], a[1][0])
    wa = laguna.make_weights(sizes, policy, big)
    wb = laguna.make_weights(sizes, policy, big)
    wc = laguna.make_weights(sizes, policy, 5)
    assert all(onp.array_equal(wa[n], wb[n]) for n in wa)
    assert not onp.array_equal(wa["head.weight"], wc["head.weight"])
    assert set(wa) == set(laguna.param_shapes(sizes))


# ---------------------------------------------------------------------------
# the readers on a hand-made run
# ---------------------------------------------------------------------------

PRE = "jit(pure_step)/jvp(forward)/layers/"
BWD = "jit(pure_step)/transpose(jvp(forward))/layers/"
PATHS = {
    "fusion.1": PRE + "1/attn/jit(_gated_attention)/window/"
                      "jit(banded_attention)/dot_general",
    "fusion.2": BWD + "1/attn/jit(_gated_attention)/window/"
                      "jit(banded_attention)/transpose",
    "fusion.3": PRE + "0/attn/jit(_gated_attention)/full/while/body/dot",
    "fusion.4": PRE + "1/attn/q_proj/dot_general",
    "fusion.5": PRE + "1/moe/jit(routed_experts)/experts/ragged_dot",
    "fusion.6": BWD + "1/moe/jit(routed_experts)/experts/ragged_dot",
    "fusion.7": PRE + "1/moe/jit(routed_experts)/dispatch/sort",
    "fusion.8": PRE + "1/moe/shared/gate_proj/dot_general",
    "fusion.9": "jit(pure_step)/optimizer/mul",
    # the compiler's kernel in a ragged dot's place: no scope of its
    # own, its consumer's path (under moe, in combine)
    "ragged-dot-none.3": PRE + "1/moe/jit(routed_experts)/combine/gather",
}
HLO = "\n".join(f'  %{name} = f32[8]{{0}} fusion(), kind=kLoop, '
                f'metadata={{op_name="{path}"}}'
                for name, path in PATHS.items())
# seconds over a window of two steps
SECONDS = {"fusion.1": 0.010, "fusion.2": 0.030, "fusion.3": 0.100,
           "fusion.4": 0.008, "fusion.5": 0.002, "fusion.6": 0.004,
           "fusion.7": 0.001, "fusion.8": 0.003, "fusion.9": 0.002,
           "ragged-dot-none.3": 0.005}


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


class FakeRun:
    def __init__(self, config, mix, seed=1):
        self.result = {"hlo_text": HLO, "detail": {}}
        self.summary = {"op_seconds": dict(SECONDS), "steps": 2,
                        "busy_s": sum(SECONDS.values())}
        self.sizes, self.traffic, self.family = config, mix, laguna
        self.policy, self.seed = config["dtype_policy"], seed
        self.device, self.peaks = Device, load("benchmark", "peaks.json")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_names_are_matched_in_order_not_side_by_side():
    assert scope_paths.holds(PATHS["fusion.1"], ("attn", "window"))
    assert scope_paths.holds(PATHS["fusion.1"], ("layers", "1", "attn"))
    assert not scope_paths.holds(PATHS["fusion.1"], ("window", "attn"))
    assert not scope_paths.holds(PATHS["fusion.4"], ("attn", "window"))
    assert not scope_paths.holds(PATHS["fusion.3"], ("attn", "window"))


def test_device_time_under_the_new_scopes(config, mix):
    run = FakeRun(config, mix)
    assert reader("window_attention_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.040 / 2)
    assert reader("full_attention_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.100 / 2)
    assert reader("moe_ms_per_step")(run) == pytest.approx(1e3 * 0.015 / 2)
    # the optimizer's events and the projections lie under neither
    assert scope_paths.ms_per_step(run, ("attn",)) == \
        pytest.approx(1e3 * 0.148 / 2)


def test_rooflines_take_the_familys_counts_and_the_programs_gauges(
        config, mix):
    from mxnet_tpu.telemetry import metrics
    run = FakeRun(config, mix)
    flops, nbytes = laguna.attention_products(config, mix, windowed=True)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12            # compute binds the band
    assert reader("window_attention_roofline")(run) == \
        pytest.approx(100 * least / 0.020)
    for name in list(metrics.all_metrics()):
        if name.startswith("moe_"):
            metrics.unregister(name)
    assert reader("moe_experts_roofline")(run) is None
    assert reader("moe_load_max_over_mean")(run) is None
    metrics.gauge("moe_rows_routed.layers.1").set(8000)
    metrics.gauge("moe_rows_routed.layers.2").set(8400)
    metrics.gauge("moe_load_max_over_mean.layers.1").set(1.25)
    metrics.gauge("moe_load_max_over_mean.layers.2").set(1.5)
    try:
        f = sum(laguna.expert_products(config, n)[0] for n in (8000, 8400))
        b = sum(laguna.expert_products(config, n)[1] for n in (8000, 8400))
        assert f == 18 * 16400 * 2048 * 512
        assert reader("moe_experts_roofline")(run) == pytest.approx(
            100 * max(f / 197e12, b / 819e9) / 0.0055)
        # no program's ids are kept in this process: nothing is compared
        laguna.PROGRAM_EXPERT_IDS.clear()
        assert reader("moe_load_max_over_mean")(run) == 1.5
        assert "top_k_sets_differ_share" not in run.result["detail"]
    finally:
        for name in list(metrics.all_metrics()):
            if name.startswith("moe_"):
                metrics.unregister(name)


def test_without_scope_names_the_readers_give_nothing(config, mix):
    run = FakeRun(config, mix)
    run.result["hlo_text"] = HLO.replace("metadata=", "meta=")
    for name in NEW_METRICS[:5]:
        assert reader(name)(run) is None


def test_the_traced_tiny_run_reports_the_counter_and_the_flips():
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 91), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert last["metrics"]["recompiles_in_window"]["value"] == 0
    # float32 at the tiny sizes: the program's and the reference's
    # routers agree on every row
    assert last["detail"]["top_k_sets_differ_share"] == 0.0
    # nothing of a device trace under a device metric's name on the CPU
    for name in NEW_METRICS[:5] + ("step_mfu", "mxu_roofline"):
        assert name not in last["metrics"]
