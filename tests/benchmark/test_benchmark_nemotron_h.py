"""The ``nemotron_h`` family's configuration against the source's
``config.json`` (its keys are written out below, so the test reads
nothing from outside the repository), its counts against its shapes by
hand, its seed rule, and the cell's four new per-layer readers on a
hand-made run: scope paths as the step's HLO writes them with made-up
event times. The ``--tiny`` cell itself (sound run, control, faults)
runs under the tests that take every cell of the manifest
(``test_benchmark_correct.py``, ``test_benchmark_faults.py``); the
traced tiny run is here, the planted faults in
``test_benchmark_nemotron_h_faults.py``.
"""
import importlib
import os

import numpy as onp
import pytest

from bench_helpers import ROOT, load, manifest, run_harness
from benchmark.families import nemotron_h

CELL = "nemotron3_nano_train_b1_t8192"
NEW_METRICS = ("mamba_ms_per_step", "ssd_scan_ms_per_step", "mamba_roofline",
               "ssd_scan_roofline")
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
          "/blob/main/config.json")
# the source's config.json, key by key (nvidia/NVIDIA-Nemotron-3-Nano-
# 30B-A3B-BF16)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
       "n_routed_experts": 8, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    return load("benchmark", "configs", "nemotron3_nano.json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmark", "traffic", "train_b1_t8192.json")


def tiny(config, mix):
    over = config["tiny"]
    return {**config, **over["sizes"]}, {**mix, **over["traffic"]}


def test_the_file_is_the_sources_config_but_for_the_cut(config):
    assert config["source"] == SOURCE
    assert config["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    assert {k for k in CUT if PUBLISHED[k] != CUT[k]} == set(CUT)
    # the pattern's first 9 characters: one whole period, 4 : 4 : 1
    pattern = config["hybrid_override_pattern"]
    assert PUBLISHED["hybrid_override_pattern"].startswith(pattern)
    assert [pattern.count(ch) for ch in "ME*"] == [4, 4, 1]
    assert [PUBLISHED["hybrid_override_pattern"].count(ch)
            for ch in "ME*"] == [23, 23, 6]
    dep = config["deployment"]
    assert dep["chips_per_layer"] == 16
    assert dep["experts_held"] == [0, 8]
    assert dep["num_experts_published"] == 128 == 16 * config[
        "n_routed_experts"]
    assert dep["vocab_size_published"] == 131072 == 8 * config["vocab_size"]
    assert dep["num_hidden_layers_published"] == 52
    assert dep["hybrid_override_pattern_published"] == \
        PUBLISHED["hybrid_override_pattern"]
    assert set(config["assumed"]) >= {
        "layer", "d_inner", "mamba_conv", "mamba_groups", "mamba_dt",
        "mamba_norm", "attention", "positions", "router", "expert_bias",
        "experts", "initializer", "switches", "dtype_policy", "loss",
        "data"}
    assert config["dtype_policy"] == "bf16_norm_router_f32"
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "nemotron3_nano")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/nemotron3_nano.json"
    # the limits stand on readings, each with its reason
    assert set(config["limits_why"]) >= set(config["limits"])
    assert set(config["witness_limits"]) == {"mamba_block_gap"}


def test_needed_work_by_hand(config, mix):
    """17.6 TFLOP a step: per layer forward, a Mamba mixer's projections
    634.2 G and its scan 27.9 G, an expert layer 394 G, the attention
    layer 933 G, the head 722 G; x 3 for the backward pass."""
    t, c = 8192, 2688
    assert nemotron_h.expected_rows(config, mix) == 3072  # 8192 x 6 / 16
    assert nemotron_h.work_units(config, mix) == {"tokens": t}
    layers = {n: (f, b) for n, f, b in nemotron_h.matrix_layers(config, mix)}
    assert layers["layers.0.mamba.in_proj"][0] == 3 * 2 * t * c * 10304
    assert layers["layers.0.mamba.out_proj"][0] == 3 * 2 * t * 4096 * c
    assert (layers["layers.0.mamba.in_proj"][0]
            + layers["layers.0.mamba.out_proj"][0]) / 3 == \
        pytest.approx(634.2e9, rel=1e-4)
    # the chunk algebra a token: C B^T (L N G), the decayed scores by the
    # inputs (L H P), the chunk's state and its read (H P N each)
    macs = 128 * (128 * 8 + 64 * 64) + 2 * 64 * 64 * 128
    operands = (4096 + 2 * 8 * 128) * 2 + 4 * 64
    assert layers["layers.0.mamba.scan"] == (
        t * 3 * 2 * macs, t * (3 * operands + 2 * 4096 * 2))
    assert layers["layers.0.mamba.scan"][0] / 3 == \
        pytest.approx(27.9e9, rel=1e-3)
    assert nemotron_h.ssd_scan_work(config, mix) == tuple(
        4 * x for x in layers["layers.0.mamba.scan"])
    assert layers["layers.5.attn.q_proj"][0] == 3 * 2 * t * c * 4096
    assert layers["layers.5.attn.kv_proj"][0] == 3 * 2 * t * c * 512
    # the causal half, 32 heads of 128
    assert layers["layers.5.attn.products"] == (
        3 * 2 * 32 * (t * (t + 1) // 2) * 2 * 128,
        3 * 2 * t * 32 * 4 * 128)
    assert layers["layers.5.attn.products"] == \
        nemotron_h.attention_products(config, mix, windowed=False)
    assert nemotron_h.attention_products(config, mix, windowed=True) == \
        (0, 0)
    attention = sum(f for n, (f, _) in layers.items()
                    if n.startswith("layers.5."))
    assert attention / 3 == pytest.approx(933e9, rel=2e-3)
    assert layers["layers.1.moe.router"][0] == 3 * 2 * t * c * 128
    # two grouped products (up, down) at the rows uniform routing sends
    assert layers["layers.1.moe.experts"] == (
        3 * 2 * 2 * 3072 * c * 1856,
        3 * 2 * (2 * 3072 * (c + 1856) + 2 * 8 * c * 1856))
    assert layers["layers.1.moe.experts"] == \
        nemotron_h.expert_products(config, 3072)
    assert layers["layers.1.moe.shared.up_proj"][0] == 3 * 2 * t * c * 3712
    experts = sum(f for n, (f, _) in layers.items()
                  if n.startswith("layers.1."))
    assert experts / 3 == pytest.approx(394e9, rel=2e-3)
    assert layers["head"][0] == 3 * 2 * t * c * 16384
    assert "layers.0.moe.experts" not in layers
    assert "layers.1.mamba.scan" not in layers
    total = nemotron_h.needed_flops(config, mix)
    assert total == sum(f for f, _ in layers.values())
    assert total == pytest.approx(17.64e12, rel=2e-3)
    # the four mixers, each whole: their two projections; the input, the
    # output and the two matrices once a pass
    weights = c * 10304 + 4096 * c
    assert nemotron_h.mamba_block_work(config, mix) == (
        4 * 3 * 2 * t * weights, 4 * 3 * 2 * (2 * t * c + weights))
    mixers = nemotron_h.mamba_block_work(config, mix)[0] \
        + nemotron_h.ssd_scan_work(config, mix)[0]
    assert mixers / total == pytest.approx(0.45, abs=0.01)


def test_same_seed_same_batches_and_weights(config, mix):
    sizes, traffic = tiny(config, mix)
    traffic = {**traffic, "n_batches": 3}
    policy = sizes["dtype_policy"]
    big = 2 ** 31 + 7  # more than 32 signed bits hold
    a = nemotron_h.make_batches(sizes, policy, traffic, big)
    b = nemotron_h.make_batches(sizes, policy, traffic, big)
    c = nemotron_h.make_batches(sizes, policy, traffic, 7)
    assert len(a) == 3
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert onp.array_equal(xa, xb) and onp.array_equal(ya, yb)
        assert not onp.array_equal(xa, xc)
        assert 0 <= int(xa.min()) and int(xa.max()) < sizes["vocab_size"]
        assert int(ya.max()) < sizes["vocab_size"]
    wa = nemotron_h.make_weights(sizes, policy, big)
    wb = nemotron_h.make_weights(sizes, policy, big)
    wc = nemotron_h.make_weights(sizes, policy, 7)
    assert all(onp.array_equal(wa[n], wb[n]) for n in wa)
    assert not onp.array_equal(wa["embed.weight"], wc["embed.weight"])
    assert not onp.array_equal(wa["embed.weight"], wa["head.weight"])
    assert set(wa) == set(nemotron_h.param_shapes(sizes))
    bias = onp.asarray(wa["layers.1.moe.expert_bias"])
    assert bias.shape == (16,) and onp.abs(bias).min() > 0
    assert not onp.array_equal(bias, wc["layers.1.moe.expert_bias"])
    # the filter at PyTorch's Conv1d default, not at the matrices' 0.02
    conv = onp.asarray(wa["layers.0.mamba.conv_weight"])
    assert 0.2 < onp.abs(conv).max() <= nemotron_h.FILTER_BOUND
    assert not onp.array_equal(wa["layers.0.mamba.A_log"],
                               wc["layers.0.mamba.A_log"])


def test_the_selection_bias_spreads_other_tokens_evenly(config, mix):
    """The biases the auxiliary-loss-free rule leaves on the seed's own
    tokens spread tokens it never saw evenly over the experts, where
    the router alone, without them, sends the rows of a few experts'
    share to one: the fullest of 16 experts against the mean, both
    expert layers of the tiny cut, on 2048 other tokens."""
    import jax
    from benchmark import correctness
    sizes, _ = tiny(config, mix)
    weights = nemotron_h.make_weights(sizes, "f32", 2 ** 31 + 9)
    unbiased = {n: 0 * v if nemotron_h.is_state(n) else v
                for n, v in weights.items()}
    x = jax.random.randint(jax.random.key(9), (2, 1024), 0,
                           sizes["vocab_size"])

    def fullest(weights):
        ids = {}
        with jax.default_matmul_precision("highest"):
            nemotron_h.reference_logits(sizes, weights, x,
                                        correctness.Rounding, ids)
        loads = [onp.bincount(onp.asarray(i).ravel(), minlength=16)
                 for i in ids.values()]
        assert len(loads) == 2
        return max(load.max() / load.mean() for load in loads)

    assert fullest(weights) < 1.3 < fullest(unbiased)


# ---------------------------------------------------------------------------
# the readers on a hand-made run
# ---------------------------------------------------------------------------

PRE = "jit(pure_step)/jvp(forward)/layers/"
BWD = "jit(pure_step)/transpose(jvp(forward))/layers/"
MIXER = "mamba/jit(_mamba_mixer)/"
PATHS = {
    "fusion.1": PRE + "0/" + MIXER + "dot_general",
    "fusion.2": PRE + "0/" + MIXER + "scan/jit(_ssd)/bcgrls,bcgrsp->bcgrlp/"
                                     "dot_general",
    "fusion.3": BWD + "2/" + MIXER + "checkpoint/rematted_computation/scan/"
                                     "jit(_ssd)/while/body/add",
    "fusion.4": BWD + "4/" + MIXER + "checkpoint/dot_general",
    "fusion.5": PRE + "0/" + MIXER + "conv/jit(silu)/mul",
    "fusion.6": BWD + "7/" + MIXER + "checkpoint/norm/rsqrt",
    "fusion.7": PRE + "5/attn/jit(_gated_attention)/full/"
                      "jit(banded_attention)/pallas_call",
    "fusion.8": PRE + "1/moe/jit(routed_experts)/route/logistic",
    "fusion.9": "jit(pure_step)/optimizer/mul",
}
HLO = "\n".join(f'  %{name} = f32[8]{{0}} fusion(), kind=kLoop, '
                f'metadata={{op_name="{path}"}}'
                for name, path in PATHS.items())
# seconds over a window of two steps
SECONDS = {"fusion.1": 0.050, "fusion.2": 0.010, "fusion.3": 0.030,
           "fusion.4": 0.060, "fusion.5": 0.004, "fusion.6": 0.006,
           "fusion.7": 0.008, "fusion.8": 0.001, "fusion.9": 0.002}


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


class FakeRun:
    def __init__(self, config, mix):
        self.result = {"hlo_text": HLO, "detail": {}}
        self.summary = {"op_seconds": dict(SECONDS), "steps": 2,
                        "busy_s": sum(SECONDS.values())}
        self.sizes, self.traffic, self.family = config, mix, nemotron_h
        self.device, self.peaks = Device, load("benchmark", "peaks.json")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_device_time_under_the_new_scopes(config, mix):
    run = FakeRun(config, mix)
    # the projections, the filter, the scan and the norm, both passes
    assert reader("mamba_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.160 / 2)
    # the scan alone, its recomputation under the backward pass too
    assert reader("ssd_scan_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.040 / 2)
    # the attention layer and the expert layer under the accepted readers
    assert reader("full_attention_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.008 / 2)
    assert reader("moe_ms_per_step")(run) == pytest.approx(1e3 * 0.001 / 2)


def test_rooflines_take_the_familys_counts(config, mix):
    run = FakeRun(config, mix)
    flops, nbytes = nemotron_h.mamba_block_work(config, mix)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12     # the products bind the mixer
    assert least == pytest.approx(38.63e-3, rel=1e-3)
    assert reader("mamba_roofline")(run) == \
        pytest.approx(100 * least / 0.080)
    flops, nbytes = nemotron_h.ssd_scan_work(config, mix)
    assert nbytes / 819e9 > flops / 197e12   # the operands bind the scan
    assert nbytes / 819e9 == pytest.approx(2.161e-3, rel=1e-3)
    assert reader("ssd_scan_roofline")(run) == \
        pytest.approx(100 * (nbytes / 819e9) / 0.020)
    # a family without such mixers reports nothing
    run.family = importlib.import_module("benchmark.families.laguna")
    assert reader("mamba_roofline")(run) is None
    assert reader("ssd_scan_roofline")(run) is None


def test_the_programs_counters_go_on_record(config, mix):
    from mxnet_tpu.telemetry import metrics
    run = FakeRun(config, mix)
    chunked = metrics.counter("ssd_traced_total.chunked")
    before = chunked.value()
    chunked.inc()
    try:
        reader("ssd_scan_roofline")(run)
        assert run.result["detail"]["ssd_backend"]["chunked"] == before + 1
    finally:
        metrics.unregister("ssd_traced_total.chunked")
    # nothing on record where the program keeps no such counter
    run = FakeRun(config, mix)
    reader("ssd_scan_roofline")(run)
    assert "ssd_backend" not in run.result["detail"]


def test_without_scope_names_the_readers_give_nothing(config, mix):
    run = FakeRun(config, mix)
    run.result["hlo_text"] = HLO.replace("metadata=", "meta=")
    for name in NEW_METRICS:
        assert reader(name)(run) is None


def test_the_manifest_has_the_cell_and_its_metrics():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron3_nano", "train_b1_t8192", 1)
    assert len(cell["why"]) <= 200
    by_name = {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}
    for name in ("tokens_per_s", "full_attention_ms_per_step",
                 "full_attention_roofline", "moe_ms_per_step",
                 "moe_experts_roofline", "moe_load_max_over_mean",
                 "moe_overflow_layers"):
        assert CELL in by_name[name]["workloads"]
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "Kernels"
        assert by_name[name]["moves"] == "step_ms"
        assert by_name[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(ROOT, "benchmark",
                                           "layer_metrics", name + ".py"))
    for name in ("window_attention_ms_per_step", "conv_ms_per_step",
                 "kda_ms_per_step", "kda_mix_kernel_calls"):
        assert CELL not in by_name[name]["workloads"]


def test_the_traced_tiny_run_reports_the_counters_and_the_gauges():
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert last["metrics"]["moe_overflow_layers"]["value"] == 0
    assert last["metrics"]["recompiles_in_window"]["value"] == 0
    # float32 at the tiny sizes: the program's and the reference's
    # routers agree on every row
    assert last["detail"]["top_k_sets_differ_share"] == 0.0
    # two mixers, each traced by the shape-resolving forward and by the
    # step; the attention layer as the composition: there is no chip
    assert last["detail"]["ssd_backend"] == {"chunked": 4}
    assert last["detail"]["attention_backend"] == {"dense": 2}
    changed = last["detail"]["moe_bias_changed_choice"]
    assert sorted(changed) == ["layers.1", "layers.4"]
    assert all(0.0 <= v <= 1.0 for v in changed.values())
    # nothing of a device trace under a device metric's name on the CPU
    for name in NEW_METRICS + ("step_mfu", "mxu_roofline", "moe_ms_per_step",
                               "full_attention_ms_per_step"):
        assert name not in last["metrics"]
