"""The ``keye_vl2`` family's configuration against the catalog's row of
the source's ``config.json`` (a copy under ``data/``, so the test reads
nothing from outside the repository), its counts against its shapes by
hand, its seed rule, and the cell's four new per-layer readers on a
hand-made run: scope paths as the step's HLO writes them with made-up
event times. The ``--tiny`` cell itself (sound run, control, faults)
runs under the tests that take every cell of the manifest
(``test_benchmark_correct.py``, ``test_benchmark_faults.py``); the
planted faults in ``test_benchmark_keye_faults.py``.
"""
import importlib
import json
import os

import numpy as onp
import pytest

from bench_helpers import ROOT, load, manifest
from benchmark.families import keye_vl2

CELL = "keye_vl2_train_b1_t16384"
NEW_METRICS = ("indexer_ms_per_step", "indexer_roofline",
               "sparse_attention_ms_per_step", "sparse_attention_roofline")
CUT = {"num_hidden_layers": 4, "num_experts": 16, "num_local_experts": 16,
       "vocab_size": 18992}


@pytest.fixture(scope="module")
def config():
    return load("benchmark", "configs", "keye_vl2_30b_a3b.json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmark", "traffic", "train_b1_t16384.json")


@pytest.fixture(scope="module")
def row():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "keye_vl2_catalog_row.json")) as f:
        return json.load(f)


def test_the_file_is_the_catalogs_row_but_for_the_cut(config, row):
    published = row["config"]
    assert config["source"] == row["source_url"]
    assert config["reduced"] == list(CUT)
    for key, value in published.items():
        assert config[key] == CUT.get(key, value), key
    assert {k for k in CUT if published[k] != CUT[k]} == set(CUT)
    dep = config["deployment"]
    assert dep["chips_per_layer"] == 8
    assert dep["experts_held"] == [0, 16]
    assert dep["num_experts_published"] == 128 == 8 * config["num_experts"]
    assert dep["vocab_size_published"] == 151936 == published["vocab_size"]
    # rows 0-18991 of 151936: an eighth, whole rows
    assert 8 * config["vocab_size"] == 151936
    assert dep["num_hidden_layers_published"] == 48
    assert set(config["assumed"]) >= {
        "layer", "attention", "positions", "indexer", "indexer_rotary",
        "indexer_scores", "selection", "sparse_attention", "indexer_loss",
        "router", "experts", "vision", "initializer", "dtype_policy",
        "optimizer", "loss", "data"}
    assert config["dtype_policy"] == "bf16_norm_router_f32"
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "keye_vl2_30b_a3b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/keye_vl2_30b_a3b.json"
    assert set(config["limits_why"]) >= set(config["limits"])
    assert set(config["witness_limits"]) == {"sparse_block_gap",
                                             "selection_differ_share"}


def test_the_cut_has_its_parameters_to_the_parameter(config):
    """465,391,104 parameters: four layers of 96,899,456 (attention
    18,874,368, its q and k norms 256, the two norms 4,096, the indexer
    2,261,120, the router 262,144, 16 experts 75,497,472), embedding and
    head of 38,895,616 each, the final norm; and the program's net
    builds exactly these leaves (abstractly: nothing is allocated at the
    cut's size)."""
    from mxnet_tpu import models
    shapes = keye_vl2.param_shapes(config)

    def count(prefix):
        return sum(int(onp.prod(s)) for n, (s, _) in shapes.items()
                   if n.startswith(prefix))

    c = 2048
    attention = 2 * c * 4096 + 2 * c * 512
    indexer = c * 1024 + c * 64 + c * 16 + 2 * 64
    experts = 16 * 3 * c * 768
    assert (attention, indexer, experts) == (18874368, 2261120, 75497472)
    layer = attention + 256 + 2 * c + indexer + 128 * c + experts
    assert layer == 96899456
    for i in range(4):
        assert count(f"layers.{i}.") == layer
        assert count(f"layers.{i}.attn.indexer.") == indexer
    assert count("embed.") == count("head.") == 18992 * c
    assert count("") == 4 * layer + 2 * 18992 * c + c == 465391104
    net = models.KeyeLM.from_config(config)
    params = net._collect_params_with_prefix()
    assert {n: tuple(p.shape) for n, p in params.items()} == \
        {n: s for n, (s, _) in shapes.items()}


def test_needed_work_by_hand(config, mix):
    """Per token a layer forward, MFLOP: attention projections 37.75,
    the indexer's 4.52, the router 0.52, the held experts at one row a
    token 9.44, the index scores 1024 x (T + 1) = 16.78 (causal pairs),
    the attention over the selection 31.45 (1920 keys a query on
    average); the head 77.8. The indexer's backward runs over the
    selected pairs alone."""
    t = 16384
    assert keye_vl2.expected_rows(config, mix) == t  # 16 x 1024
    assert keye_vl2.work_units(config, mix) == {"tokens": t}
    causal, selected = keye_vl2._pairs(config, mix)
    assert causal == t * (t + 1) // 2
    assert selected == 2048 * 2049 // 2 + (t - 2048) * 2048 == 31458304
    layers = {n: (f, b) for n, f, b in keye_vl2.matrix_layers(config, mix)}

    def per_token(name):
        return layers[name][0] / 3 / t / 1e6

    assert per_token("layers.0.attn.q_proj") + per_token(
        "layers.0.attn.kv_proj") + per_token("layers.0.attn.o_proj") == \
        pytest.approx(37.75, abs=0.01)
    assert per_token("layers.0.attn.indexer.proj") == \
        pytest.approx(4.52, abs=0.01)
    assert per_token("layers.0.moe.router") == pytest.approx(0.52, abs=0.01)
    assert per_token("layers.0.moe.experts") == pytest.approx(9.44, abs=0.01)
    assert per_token("head") == pytest.approx(77.8, abs=0.05)
    flops, nbytes = keye_vl2.indexer_work(config, mix)
    assert flops == 4 * 2 * 1024 * (causal + 2 * selected)
    assert 2 * 1024 * causal / t / 1e6 == pytest.approx(16.78, abs=0.01)
    assert layers["layers.0.attn.indexer.scores"] == (flops / 4, nbytes / 4)
    flops, nbytes = keye_vl2.sparse_attention_work(config, mix)
    assert flops == 4 * 3 * 4 * 4096 * selected
    assert flops / 12 / t / 1e6 == pytest.approx(31.45, abs=0.01)
    assert nbytes == 4 * 2 * (t * 72 * 128 * 2 + 4 * selected)
    # the indexer's backward over the selected pairs alone: 21.89 TFLOP
    # where three forwards would be 23.6; its scores and the sparse
    # products are 36% of it
    total = keye_vl2.needed_flops(config, mix)
    assert total == sum(f for f, _ in layers.values())
    assert total == pytest.approx(21.89e12, rel=1e-3)
    share = (keye_vl2.indexer_work(config, mix)[0] + flops) / total
    assert share == pytest.approx(0.356, abs=0.001)


def test_same_seed_same_batches_and_weights(config, mix):
    over = config["tiny"]
    sizes = {**config, **over["sizes"]}
    traffic = {**mix, **over["traffic"], "n_batches": 3}
    policy = sizes["dtype_policy"]
    big = 2 ** 31 + 7  # more than 32 signed bits hold
    a = keye_vl2.make_batches(sizes, policy, traffic, big)
    b = keye_vl2.make_batches(sizes, policy, traffic, big)
    c = keye_vl2.make_batches(sizes, policy, traffic, 7)
    assert len(a) == 3
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert onp.array_equal(xa, xb) and onp.array_equal(ya, yb)
        assert not onp.array_equal(xa, xc)
        assert 0 <= int(xa.min()) and int(xa.max()) < sizes["vocab_size"]
    wa = keye_vl2.make_weights(sizes, policy, big)
    wb = keye_vl2.make_weights(sizes, policy, big)
    wc = keye_vl2.make_weights(sizes, policy, 7)
    assert all(onp.array_equal(wa[n], wb[n]) for n in wa)
    assert not onp.array_equal(wa["embed.weight"], wc["embed.weight"])
    assert set(wa) == set(keye_vl2.param_shapes(sizes))
    assert not onp.any(wa["layers.0.attn.indexer.k_norm_bias"])


# ---------------------------------------------------------------------------
# the readers on a hand-made run
# ---------------------------------------------------------------------------

PRE = "jit(pure_step)/jvp(forward)/layers/"
BWD = "jit(pure_step)/transpose(jvp(forward))/layers/"
OP = "attn/jit(_selected_attention)/while/body/closed_call/"
PATHS = {
    "fusion.1": PRE + "0/attn/indexer/jit(_indexer)/dot_general",
    "fusion.2": PRE + "0/" + OP + "indexer/top_k",
    "fusion.3": BWD + "2/" + OP + "indexer/reduce_sum",
    "fusion.4": PRE + "1/" + OP + "sparse/bqgrd,bkgd->bgrqk/dot_general",
    "fusion.5": BWD + "3/" + OP + "sparse/bgrqk,bqgrd->bkgd/dot_general",
    "fusion.6": PRE + "0/attn/q_proj/dot_general",
    "fusion.7": PRE + "1/moe/jit(routed_experts)/experts/mul",
    "fusion.8": "jit(pure_step)/optimizer/mul",
}
HLO = "\n".join(f'  %{name} = f32[8]{{0}} fusion(), kind=kLoop, '
                f'metadata={{op_name="{path}"}}'
                for name, path in PATHS.items())
# seconds over a window of two steps
SECONDS = {"fusion.1": 0.010, "fusion.2": 0.300, "fusion.3": 0.090,
           "fusion.4": 0.200, "fusion.5": 0.400, "fusion.6": 0.004,
           "fusion.7": 0.050, "fusion.8": 0.002}


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


class FakeRun:
    def __init__(self, config, mix):
        self.result = {"hlo_text": HLO, "detail": {}}
        self.summary = {"op_seconds": dict(SECONDS), "steps": 2,
                        "busy_s": sum(SECONDS.values())}
        self.sizes, self.traffic, self.family = config, mix, keye_vl2
        self.device, self.peaks = Device, load("benchmark", "peaks.json")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_device_time_under_the_new_scopes(config, mix):
    run = FakeRun(config, mix)
    # the projections, the scores, the top-k and the KL gradient
    assert reader("indexer_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.400 / 2)
    # the products and the softmax, both passes
    assert reader("sparse_attention_ms_per_step")(run) == \
        pytest.approx(1e3 * 0.600 / 2)
    assert reader("moe_ms_per_step")(run) == pytest.approx(1e3 * 0.050 / 2)
    assert reader("full_attention_ms_per_step")(run) is None


def test_rooflines_take_the_familys_counts(config, mix):
    run = FakeRun(config, mix)
    flops, nbytes = keye_vl2.indexer_work(config, mix)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12     # the products bind the scores
    assert reader("indexer_roofline")(run) == \
        pytest.approx(100 * least / 0.200)
    flops, nbytes = keye_vl2.sparse_attention_work(config, mix)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12 == pytest.approx(31.4e-3, rel=1e-2)
    assert reader("sparse_attention_roofline")(run) == \
        pytest.approx(100 * least / 0.300)
    # a family without an indexer reports nothing
    run.family = importlib.import_module("benchmark.families.laguna")
    assert reader("indexer_roofline")(run) is None
    assert reader("sparse_attention_roofline")(run) is None


def test_the_programs_counters_go_on_record(config, mix):
    from mxnet_tpu.telemetry import metrics
    run = FakeRun(config, mix)
    xla = metrics.counter("sparse_attention_traced_total.xla")
    before = xla.value()
    xla.inc()
    try:
        reader("sparse_attention_roofline")(run)
        assert run.result["detail"]["sparse_attention_backend"]["xla"] == \
            before + 1
    finally:
        metrics.unregister("sparse_attention_traced_total.xla")
    run = FakeRun(config, mix)
    reader("indexer_roofline")(run)
    assert "sparse_attention_backend" not in run.result["detail"]


def test_without_scope_names_the_readers_give_nothing(config, mix):
    run = FakeRun(config, mix)
    run.result["hlo_text"] = HLO.replace("metadata=", "meta=")
    for name in NEW_METRICS:
        assert reader(name)(run) is None


def test_the_manifest_has_the_cell_and_its_metrics():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("keye_vl2_30b_a3b", "train_b1_t16384", 1)
    assert len(cell["why"]) <= 200
    by_name = {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}
    for name in ("tokens_per_s", "moe_ms_per_step", "moe_experts_roofline",
                 "moe_load_max_over_mean", "moe_overflow_layers"):
        assert CELL in by_name[name]["workloads"]
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "Kernels"
        assert by_name[name]["moves"] == "step_ms"
        assert by_name[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(ROOT, "benchmark",
                                           "layer_metrics", name + ".py"))
    for name in ("full_attention_ms_per_step", "full_attention_roofline",
                 "window_attention_ms_per_step", "mamba_ms_per_step"):
        assert CELL not in by_name[name]["workloads"]

