"""The ``ling_hybrid`` family's configuration against the source's row,
its counts against its shapes by hand, its seed rule, and the cell's new
per-layer readers on a hand-made run: scope paths as the step's HLO
writes them with made-up event times. The ``--tiny`` cell itself (sound
run, control, faults) runs under the tests that take every cell of the
manifest (``test_benchmark_correct.py``, ``test_benchmark_faults.py``);
the traced tiny run and what it puts on record are here.
"""
import importlib
import json
import os

import numpy as onp
import pytest

from bench_helpers import load, manifest, run_harness
from benchmark import scope_paths
from benchmark.families import ling_hybrid

CELL = "ling_3_flash_train_b1_t4096"
NEW_METRICS = ("kda_ms_per_step", "kda_scan_ms_per_step", "kda_roofline",
               "kda_scan_roofline", "kda_mix_ms_per_step")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
       "num_experts": 8, "vocab_size": 19648,
       "num_nextn_predict_layers": 0}
# the widths of the source's config.json, which no cut may touch
WIDTHS = {
    "hidden_size": 2560, "head_dim": 128, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
    "v_head_dim": 128, "intermediate_size": 6144,
    "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "num_experts_per_tok": 8,
    "n_group": 8, "topk_group": 4, "short_conv_kernel_size": 4,
    "layer_group_size": 6, "routed_scaling_factor": 2.5,
    "num_attention_heads": 32, "kda_lower_bound": -5,
    "rms_norm_eps": 1e-06, "rope_theta": 6000000}


@pytest.fixture(scope="module")
def config():
    return load("benchmark", "configs", "ling_3_flash.json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmark", "traffic", "train_b1_t4096.json")


def tiny(config, mix):
    over = config["tiny"]
    return {**config, **over["sizes"]}, {**mix, **over["traffic"]}


def test_the_file_is_the_sources_config_but_for_the_cut(config):
    assert config["reduced"] == list(CUT)
    for key, value in WIDTHS.items():
        assert config[key] == value and key not in CUT, key
    if os.path.exists(CATALOG):
        # the catalog's row, key by key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == CUT.get(key, value), key
        assert {k for k in CUT if row["config"][k] != CUT[k]} == set(CUT)
    dep = config["deployment"]
    assert dep["chips_per_layer"] == 64
    assert dep["experts_held"] == [0, 8]
    assert dep["num_experts_published"] == 512 == 64 * config["num_experts"]
    assert dep["vocab_size_published"] == 157184 == 8 * config["vocab_size"]
    assert dep["num_hidden_layers_published"] == 42
    assert dep["first_k_dense_replace_published"] == 2
    assert set(config["assumed"]) >= {
        "layer_pattern", "kda_heads", "kda_projections", "kda_conv",
        "kda_qk_norm", "kda_decay", "kda_state", "kda_output",
        "latent_attention", "use_qk_norm", "rope", "router", "expert_bias",
        "shared_expert", "swiglu_limits", "mtp", "initializer", "loss",
        "data", "rng"}
    assert config["dtype_policy"] == "bf16_norm_router_f32"
    assert config["control_precision"] == "fp8"
    # the floors: the dense layer once, a whole period of six with its
    # one latent layer and the layer after it, 8 experts, an eighth of
    # the vocabulary
    plan = ling_hybrid.layer_plan(config)
    assert [(p["latent"], p["sparse"]) for p in plan] == [
        (False, False), (False, True), (False, True), (False, True),
        (False, True), (True, True), (False, True)]
    assert not any(config["expert_swiglu_limit_list"][:7])
    assert not any(config["share_expert_swiglu_limit_list"][:7])
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "ling_3_flash")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/ling_3_flash.json"
    # the limits stand on readings, each with its reason
    assert set(config["limits_why"]) >= set(config["limits"])


def test_parameters_by_hand(config):
    """822.0 M here: a delta-rule mixer 52.65 M, the latent mixer
    31.97 M, the dense FFN 47.19 M, an expert layer 54.40 M, embedding
    and head 50.30 M each."""
    shapes = ling_hybrid.param_shapes(config)

    def count(prefix):
        return sum(int(onp.prod(s)) for n, (s, _) in shapes.items()
                   if n.startswith(prefix))

    c, w = 2560, 32 * 128
    kda = 5 * c * w + 2 * c * 32 + 3 * w * 4 + 32 + w + 128
    latent = c * 32 * 192 + c * 576 + 512 + 512 * 32 * 256 + c * 32 \
        + w * c
    dense = 3 * c * 6144
    experts = 512 * c + 512 + 3 * c * 768 + 8 * 3 * c * 768
    assert count("layers.0.kda.") == kda == 52646048
    assert count("layers.5.attn.") == latent == 31965696
    assert count("layers.0.mlp.") == dense == 47185920
    assert count("layers.1.moe.") == experts == 54395392
    assert count("layers.1.moe.shared.") == 3 * c * 768
    assert count("layers.0.") == kda + dense + 2 * c
    assert count("layers.5.") == latent + experts + 2 * c
    for i in (1, 2, 3, 4, 6):
        assert count(f"layers.{i}.") == kda + experts + 2 * c
    assert count("embed.") == count("head.") == 19648 * c == 50298880
    total = 6 * kda + latent + dense + 6 * experts + 2 * 19648 * c \
        + 7 * 2 * c + c
    assert count("") == total == 822036416
    f32 = {n for n in shapes if str(onp.dtype(ling_hybrid.param_dtype(
        n, "bf16_norm_router_f32"))) == "float32"}
    assert f32 == {n for n in shapes if n.endswith(
        ("norm.weight", "o_norm_weight", "router_weight", "expert_bias",
         "A_log", "dt_bias"))}
    assert ling_hybrid.is_state("layers.2.moe.expert_bias")
    assert not ling_hybrid.is_state("head.weight")
    assert not ling_hybrid.is_state("layers.0.kda.A_log")


def test_needed_work_by_hand(config, mix):
    t, c, w = 4096, 2560, 4096
    assert ling_hybrid.expected_rows(config, mix) == 512  # 4096 x 8 / 64
    assert ling_hybrid.work_units(config, mix) == {"tokens": 4096}
    layers = {n: (f, b) for n, f, b in
              ling_hybrid.matrix_layers(config, mix)}
    assert layers["layers.0.kda.qkvf_proj"] == (
        3 * 2 * t * c * 4 * w, 3 * 2 * (t * (c + 4 * w) + c * 4 * w))
    assert layers["layers.0.kda.o_proj"][0] == 3 * 2 * t * w * c
    assert layers["layers.0.kda.bg_proj"][0] == 3 * 2 * t * c * 64
    # the recurrence: 4 d^2 multiply-adds a token a head forward, twice
    # that backward; q, k, v (bf16), log a, beta (f32) in and o out
    # forward, they, do and five gradients backward
    rows = t * 32
    operands = 3 * 128 * 2 + 128 * 4 + 4
    assert layers["layers.0.kda.scan"] == (
        rows * 3 * 2 * 4 * 128 * 128,
        rows * (operands + 256 + 2 * operands + 256))
    assert ling_hybrid.kda_scan_work(config, mix) == tuple(
        6 * x for x in layers["layers.0.kda.scan"])
    assert layers["layers.0.mlp.down_proj"] == (
        3 * 2 * t * 6144 * c, 3 * 2 * (t * (6144 + c) + 6144 * c))
    assert layers["layers.5.attn.q_proj"][0] == 3 * 2 * t * c * 32 * 192
    assert layers["layers.5.attn.kv_a_proj"][0] == 3 * 2 * t * c * 576
    assert layers["layers.5.attn.kv_b_proj"][0] == \
        3 * 2 * t * 512 * 32 * 256
    # the causal half, 32 heads: scores 192 wide, values 128 wide
    assert layers["layers.5.attn.products"] == (
        3 * 2 * 32 * (t * (t + 1) // 2) * (192 + 128),
        3 * 2 * t * 32 * 2 * (192 + 128))
    assert layers["layers.5.attn.products"] == \
        ling_hybrid.attention_products(config, mix, windowed=False)
    assert ling_hybrid.attention_products(config, mix, windowed=True) == \
        (0, 0)
    assert layers["layers.1.moe.router"][0] == 3 * 2 * t * c * 512
    assert layers["layers.1.moe.experts"] == (
        3 * 3 * 2 * 512 * c * 768,
        3 * 2 * (3 * 512 * (c + 768) + 3 * 8 * c * 768))
    assert layers["layers.1.moe.shared.up_proj"][0] == 3 * 2 * t * c * 768
    assert layers["head"][0] == 3 * 2 * t * c * 19648
    assert "layers.5.kda.scan" not in layers
    assert "layers.0.moe.experts" not in layers
    total = ling_hybrid.needed_flops(config, mix)
    assert total == sum(f for f, _ in layers.values())
    # 6 FLOPs a token over the parameters a token meets (the mixers, the
    # dense FFN, six routers and shared experts, the head) plus the 512
    # routed rows a layer, the scan and the attention
    met = 6 * (5 * c * w + 64 * c) + (c * 32 * 192 + c * 576
                                      + 512 * 32 * 256 + c * 32 + w * c) \
        + 3 * c * 6144 + 6 * (512 * c + 3 * c * 768) + 19648 * c
    assert met == pytest.approx(488.3e6, rel=1e-3)
    products = 6 * t * met + 6 * 6 * 512 * 3 * c * 768
    assert total - ling_hybrid.kda_scan_work(config, mix)[0] \
        - layers["layers.5.attn.products"][0] == products
    assert products == pytest.approx(12.1e12, rel=0.01)
    assert ling_hybrid.kda_scan_work(config, mix)[0] == \
        pytest.approx(0.31e12, rel=0.01)
    assert layers["layers.5.attn.products"][0] == \
        pytest.approx(0.52e12, rel=0.01)
    # the six mixers, each whole: 3 x 2 x T x 5 C W FLOPs; x, y and the
    # five matrices once a pass
    assert ling_hybrid.kda_block_work(config, mix) == (
        6 * 3 * 2 * t * 5 * c * w, 6 * 3 * 2 * (2 * t * c + 5 * c * w))


def test_same_seed_same_batches_and_weights(config, mix):
    sizes, traffic = tiny(config, mix)
    policy = sizes["dtype_policy"]
    big = 2 ** 31 + 5  # more than 32 signed bits hold
    a = ling_hybrid.make_batches(sizes, policy, traffic, big)
    b = ling_hybrid.make_batches(sizes, policy, traffic, big)
    c = ling_hybrid.make_batches(sizes, policy, traffic, 5)
    assert len(a) == traffic["n_batches"]
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert onp.array_equal(xa, xb) and onp.array_equal(ya, yb)
        assert not onp.array_equal(xa, xc)
        assert int(xa.max()) < sizes["vocab_size"] and int(xa.min()) >= 0
        assert int(ya.max()) < sizes["vocab_size"]
    assert not onp.array_equal(a[0][0][0], a[0][0][1])
    wa = ling_hybrid.make_weights(sizes, policy, big)
    wb = ling_hybrid.make_weights(sizes, policy, big)
    wc = ling_hybrid.make_weights(sizes, policy, 5)
    assert all(onp.array_equal(wa[n], wb[n]) for n in wa)
    assert not onp.array_equal(wa["embed.weight"], wc["embed.weight"])
    assert not onp.array_equal(wa["embed.weight"], wa["head.weight"])
    assert set(wa) == set(ling_hybrid.param_shapes(sizes))
    # the selection bias and the decay's leaves are drawn, not left at
    # their shipped values
    bias = onp.asarray(wa["layers.1.moe.expert_bias"])
    assert bias.shape == (16,) and onp.abs(bias).min() > 0
    assert onp.abs(bias).max() < 5 * ling_hybrid.BIAS_STD
    dt = onp.asarray(wa["layers.0.kda.dt_bias"])
    assert dt.shape == (64,) and -7 < dt.min() and dt.max() < -1
    assert not onp.array_equal(wa["layers.0.kda.A_log"],
                               wc["layers.0.kda.A_log"])


# ---------------------------------------------------------------------------
# the readers on a hand-made run
# ---------------------------------------------------------------------------

PRE = "jit(pure_step)/jvp(forward)/layers/"
BWD = "jit(pure_step)/transpose(jvp(forward))/layers/"
PATHS = {
    "fusion.1": PRE + "0/kda/jit(_kda_mixer)/checkpoint/dot_general",
    "fusion.2": PRE + "0/kda/jit(_kda_mixer)/checkpoint/scan/jit(_kda)/"
                      "while/body/dot_general",
    "fusion.3": BWD + "0/kda/jit(_kda_mixer)/checkpoint/scan/jit(_kda)/"
                      "while/body/dot_general",
    "fusion.4": BWD + "3/kda/jit(_kda_mixer)/checkpoint/dot_general",
    "fusion.5": PRE + "5/attn/jit(_latent_attention)/full/"
                      "jit(banded_attention)/pallas_call",
    "fusion.6": BWD + "5/attn/jit(_latent_attention)/full/"
                      "jit(banded_attention)/pallas_call",
    "fusion.7": PRE + "5/attn/kv_norm/jit(_rms_norm)/mul",
    "fusion.8": PRE + "2/moe/jit(routed_experts)/route/logistic",
    "fusion.9": "jit(pure_step)/optimizer/mul",
    "fusion.10": PRE + "0/mlp/gate_proj/dot_general",
    "fusion.11": BWD + "1/kda/jit(_kda_mixer)/checkpoint/mix/logistic",
}
HLO = "\n".join(f'  %{name} = f32[8]{{0}} fusion(), kind=kLoop, '
                f'metadata={{op_name="{path}"}}'
                for name, path in PATHS.items())
# seconds over a window of two steps
SECONDS = {"fusion.1": 0.060, "fusion.2": 0.030, "fusion.3": 0.070,
           "fusion.4": 0.050, "fusion.5": 0.006, "fusion.6": 0.014,
           "fusion.7": 0.002, "fusion.8": 0.001, "fusion.9": 0.002,
           "fusion.10": 0.020, "fusion.11": 0.010}


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


class FakeRun:
    def __init__(self, config, mix, seed=1):
        self.result = {"hlo_text": HLO, "detail": {}}
        self.summary = {"op_seconds": dict(SECONDS), "steps": 2,
                        "busy_s": sum(SECONDS.values())}
        self.sizes, self.traffic, self.family = config, mix, ling_hybrid
        self.policy, self.seed = config["dtype_policy"], seed
        self.device, self.peaks = Device, load("benchmark", "peaks.json")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_device_time_under_the_new_scopes(config, mix):
    run = FakeRun(config, mix)
    assert reader("kda_ms_per_step")(run) == pytest.approx(1e3 * 0.220 / 2)
    assert reader("kda_scan_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.100 / 2)
    # the filters, norms and gate between the projections and the scan
    assert reader("kda_mix_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.010 / 2)
    # the latent layer under the name both decoder families use: the
    # accepted reader finds it; its norm and the router lie outside
    assert reader("full_attention_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.020 / 2)
    assert reader("moe_ms_per_step")(run) == pytest.approx(1e3 * 0.001 / 2)
    assert scope_paths.ms_per_step(run, ("attn",)) == \
        pytest.approx(1e3 * 0.022 / 2)


def test_rooflines_take_the_familys_counts(config, mix):
    run = FakeRun(config, mix)
    flops, nbytes = ling_hybrid.kda_block_work(config, mix)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12     # the products bind the mixer
    assert least == pytest.approx(39.24e-3, rel=1e-3)
    assert reader("kda_roofline")(run) == \
        pytest.approx(100 * least / 0.110)
    flops, nbytes = ling_hybrid.kda_scan_work(config, mix)
    assert nbytes / 819e9 > flops / 197e12   # the operands bind the scan
    assert nbytes / 819e9 == pytest.approx(4.19e-3, rel=1e-2)
    assert reader("kda_scan_roofline")(run) == \
        pytest.approx(100 * (nbytes / 819e9) / 0.050)
    flops, nbytes = ling_hybrid.attention_products(config, mix, False)
    assert flops / 197e12 > nbytes / 819e9
    assert reader("full_attention_roofline")(run) == \
        pytest.approx(100 * (flops / 197e12) / 0.010)
    # a family without such mixers reports nothing
    run.family = importlib.import_module("benchmark.families.laguna")
    assert reader("kda_roofline")(run) is None
    assert reader("kda_scan_roofline")(run) is None


def test_the_programs_counters_go_on_record(config, mix):
    from mxnet_tpu.telemetry import metrics
    run = FakeRun(config, mix)
    chunked = metrics.counter("kda_traced_total.chunked")
    before = chunked.value()
    chunked.inc()
    metrics.gauge("moe_group_limit_changed_choice.layers.2").set(0.75)
    try:
        reader("kda_scan_roofline")(run)
        detail = run.result["detail"]
        assert detail["kda_backend"]["chunked"] == before + 1
        # (an earlier test of this worker may have left other layers')
        assert detail["moe_group_limit_changed_choice"]["layers.2"] == 0.75
    finally:
        metrics.unregister("moe_group_limit_changed_choice.layers.2")
        metrics.unregister("kda_traced_total.chunked")


def test_without_scope_names_the_readers_give_nothing(config, mix):
    run = FakeRun(config, mix)
    run.result["hlo_text"] = HLO.replace("metadata=", "meta=")
    for name in NEW_METRICS:
        assert reader(name)(run) is None


def test_the_manifest_has_the_cell_and_its_metrics():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ling_3_flash", "train_b1_t4096", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    by_name = {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}
    for name in ("tokens_per_s", "full_attention_ms_per_step",
                 "full_attention_roofline", "moe_ms_per_step",
                 "moe_experts_roofline", "moe_load_max_over_mean",
                 "moe_overflow_layers"):
        # a later cell may follow it on a list: no "last" is asserted
        assert CELL in by_name[name]["workloads"]
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["layer"] == "Kernels"
        assert by_name[name]["moves"] == "step_ms"
        assert by_name[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(
            os.path.dirname(ling_hybrid.__file__), os.pardir,
            "layer_metrics", name + ".py"))
    for name in ("window_attention_ms_per_step", "conv_ms_per_step",
                 "window_attention_band_calls"):
        assert CELL not in by_name[name]["workloads"]


def test_the_traced_tiny_run_reports_the_counters_and_the_gauges():
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 35), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert last["metrics"]["moe_overflow_layers"]["value"] == 0
    assert last["metrics"]["recompiles_in_window"]["value"] == 0
    # float32 at the tiny sizes: the program's and the reference's
    # routers agree on every row
    assert last["detail"]["top_k_sets_differ_share"] == 0.0
    # three delta-rule layers, each traced by the shape-resolving
    # forward and by the step, all chunked; the latent layer as the
    # composition: there is no chip
    assert last["detail"]["kda_backend"] == {"chunked": 6}
    assert last["detail"]["attention_backend"] == {"dense": 2}
    for gauge in ("moe_group_limit_changed_choice",
                  "moe_bias_changed_choice"):
        changed = last["detail"][gauge]
        assert sorted(changed) == ["layers.1", "layers.2", "layers.3"]
        assert all(0.0 <= v <= 1.0 for v in changed.values())
        assert max(changed.values()) > 0.0
    # nothing of a device trace under a device metric's name on the CPU
    for name in NEW_METRICS + ("step_mfu", "mxu_roofline", "moe_ms_per_step",
                               "full_attention_ms_per_step",
                               "full_attention_roofline"):
        assert name not in last["metrics"]


def test_the_faults_a_family_plants_fail_the_tiny_cell():
    """``benchmark/calibrate_faults.py`` on the tiny cell: the decay
    left at 1, the group limit left out and the rotary key left
    unrotated each read not correct against the sound reference."""
    from bench_helpers import ROOT
    rc, last, err = run_harness(
        ["--workload", CELL, "--seeds", str(2 ** 31 + 36), "--tiny",
         "--out", os.path.join(ROOT, "benchmark", "_state", "calibrate")],
        script=os.path.join(ROOT, "benchmark", "calibrate_faults.py"))
    assert rc == 0 and last == {"misjudged": []}, err[-3000:]
