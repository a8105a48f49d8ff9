"""BENCHMARK.json against the files it names, the needed-FLOPs
functions against hand counts, the seed rule, and one end-to-end
rehearsal: a cell, a traffic mix and a per-layer metric are added to a
copy with new files and new manifest entries only, and ``run.py --tiny``
on the CPU reports them in a traced run.
"""
import importlib
import json
import os
import re
import shutil

import jax
import numpy as onp
import pytest

from bench_helpers import ROOT, load, manifest, run_harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def family_of(config):
    return importlib.import_module(f"benchmark.families.{config['family']}")


def test_every_entry_has_its_files_and_names_hold():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    for p in m["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmark/")
        body = load(c["file"])
        assert body["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "families", body["family"] + ".py"))
        for key in ("source", "assumed", "optimizer", "dtype_policy",
                    "limits", "control_precision", "tiny"):
            assert key in body, (c["name"], key)
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        mix = load("benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic_kinds", mix["kind"] + ".py"))
    for e in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
        for w in e.get("workloads", []):
            assert w in cells
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", e["name"] + ".py"))
        moved = e2e[e["moves"]]
        for w in e.get("workloads", list(cells)):
            assert "workloads" not in moved or w in moved["workloads"]
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    for w in cells:
        mine = [e for e in m["end_to_end"]
                if "workloads" not in e or w in e["workloads"]]
        assert len(mine) >= 2
        assert any("workloads" not in e or w in e["workloads"]
                   for e in m["per_layer"])


def test_resnet50_needs_what_its_shapes_say():
    from benchmark.families import resnet_v1
    config = load("benchmark", "configs", "resnet50_v1.json")
    mix = load("benchmark", "traffic", "train_b256.json")
    per_image = resnet_v1.needed_flops(config, mix) / mix["batch"]
    # He et al. give 3.8e9 multiply-adds for the 50-layer net at 224
    # with the stride on a block's first 1x1 convolution, as the zoo's v1
    # has it (the often quoted 4.09e9 is of the variant that strides the
    # 3x3). Two FLOPs a multiply-add, forward once and backward twice,
    # but for the stem, whose input gradient nobody needs.
    assert per_image == pytest.approx(3 * 2 * 3.8e9, rel=0.02)
    layers = {n: (f, b) for n, f, b in
              resnet_v1.matrix_layers(config, mix)}
    # by hand: the 7x7 stem, 3 -> 64 channels, 224 -> 112 pixels a side,
    # forward and the weights' gradient
    assert layers["features.0"][0] == \
        2 * 2 * 256 * 112 * 112 * 64 * 3 * 7 * 7
    # by hand: stage 1's first 3x3, 64 -> 64 channels at 56 x 56, bf16
    assert layers["features.4.0.body.3"] == (
        3 * 2 * 256 * 56 * 56 * 64 * 64 * 9,
        3 * 2 * (256 * 2 * 64 * 56 * 56 + 64 * 64 * 9))
    assert len(layers) == 54


def test_bert_base_needs_what_its_shapes_say():
    from benchmark.families import bert
    config = load("benchmark", "configs", "bert_base.json")
    mix = load("benchmark", "traffic", "train_b16_t512.json")
    tokens = mix["batch"] * mix["seq"]
    total = bert.needed_flops(config, mix)
    # 6 FLOPs a matrix parameter a token over 108.4 M of them (84.9 M in
    # the layers, 23.4 M in the head), and 0.46 TFLOP of attention
    products = 6 * 108.4e6 * tokens
    attention = 12 * 3 * 2 * 2 * 512 * 512 * 768 * 16
    assert attention == pytest.approx(0.46e12, rel=0.02)
    assert total == pytest.approx(products + attention, rel=0.02)
    assert total == pytest.approx(5.79e12, rel=0.02)
    layers = {n: (f, b) for n, f, b in bert.matrix_layers(config, mix)}
    # by hand: one layer's first feed-forward product, 768 -> 3072, f32
    assert layers["layers.0.ffn1"] == (
        3 * 2 * 8192 * 768 * 3072,
        3 * 4 * (8192 * (768 + 3072) + 768 * 3072))
    assert bert.work_units(config, mix) == {"tokens": 8192}


@pytest.mark.parametrize("name", ["resnet50_v1", "bert_base"])
def test_same_seed_same_batches_and_weights(name):
    config = load("benchmark", "configs", name + ".json")
    traffic = load("benchmark", "traffic",
                   {"resnet50_v1": "train_b256",
                    "bert_base": "train_b16_t512"}[name] + ".json")
    config = {**config, **config["tiny"]["sizes"]}
    traffic = {**traffic, **config["tiny"]["traffic"]}
    fam, policy = family_of(config), config["dtype_policy"]
    big = 2 ** 31 + 5  # more than 32 signed bits hold
    a = fam.make_batches(config, policy, traffic, big)
    b = fam.make_batches(config, policy, traffic, big)
    c = fam.make_batches(config, policy, traffic, 5)
    assert len(a) == traffic["n_batches"]
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert onp.array_equal(onp.asarray(xa, "float32"),
                               onp.asarray(xb, "float32"))
        assert onp.array_equal(onp.asarray(ya), onp.asarray(yb))
        assert not onp.array_equal(onp.asarray(xa, "float32"),
                                   onp.asarray(xc, "float32"))
    # rows of a batch all differ, and so do the batches
    x0 = onp.asarray(a[0][0], "float32").reshape(traffic["batch"], -1)
    assert len({r.tobytes() for r in x0}) == traffic["batch"]
    wa = fam.make_weights(config, policy, big)
    wb = fam.make_weights(config, policy, big)
    assert all(onp.array_equal(onp.asarray(wa[n], "float32"),
                               onp.asarray(wb[n], "float32")) for n in wa)
    assert set(wa) == set(fam.param_shapes(config))


def test_unknown_device_kind_is_an_error():
    from benchmark import peaks
    table = load("benchmark", "peaks.json")
    assert peaks.lookup(table, "TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup(table, jax.devices("cpu")[0].device_kind)


def test_without_a_tpu_the_full_size_run_exits_nonzero():
    rc, last, err = run_harness(
        ["--workload", "resnet50_train_b256", "--seed", "1", "--seconds",
         "1", "--trace", "0"])
    assert rc != 0 and last is None
    assert "no TPU" in err


def test_outside_a_checkout_of_the_program_it_exits_nonzero(tmp_path):
    for p in manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("_state",
                                                      "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    rc, last, _ = run_harness(
        ["--workload", "resnet50_train_b256", "--seed", "1", "--seconds",
         "1", "--trace", "0", "--tiny"], root=str(tmp_path))
    assert rc != 0 and last is None


def test_a_cell_a_mix_and_a_metric_are_added_as_files_only(tmp_path):
    """``bert_base_train_b64_t128`` (a traffic file and a manifest entry)
    and a made-up per-layer metric go into a copy of the benchmark; no
    file that was there is edited, and the traced tiny run reports
    them."""
    root = tmp_path / "checkout"
    root.mkdir()
    for p in manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("_state",
                                                      "__pycache__"))
    os.symlink(os.path.join(ROOT, "mxnet_tpu"), root / "mxnet_tpu")
    before = {}
    for d, _, files in os.walk(root / "benchmark"):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    mix = load("benchmark", "traffic", "train_b16_t512.json")
    mix.update(batch=64, seq=128)
    with open(root / "benchmark" / "traffic" / "train_b64_t128.json",
              "w") as f:
        json.dump(mix, f)
    with open(root / "benchmark" / "layer_metrics" / "steps_traced.py",
              "w") as f:
        f.write("def read(run):\n    return run.result['steps']\n")
    m = manifest()
    m["workloads"].append({
        "name": "bert_base_train_b64_t128", "config": "bert_base",
        "traffic": "train_b64_t128", "chips": 1,
        "why": "64 sequences of 128: the cell attention bypasses"})
    m["per_layer"].append({
        "name": "steps_traced", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Step program",
        "moves": "step_ms", "workloads": ["bert_base_train_b64_t128"]})
    # no entry that was there is touched: the new cell reports the
    # end-to-end metrics that hold for every cell (step_ms, setup_s). To
    # report tokens_per_s too, its PR appends its name to that metric's
    # "workloads", which the contract wants on a metric of some cells.
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    rc, last, err = run_harness(
        ["--workload", "bert_base_train_b64_t128", "--seed", "9",
         "--seconds", "0.3", "--trace", "0", "--tiny"], root=str(root))
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True
    assert set(last["metrics"]) == {"step_ms", "setup_s"}
    rc, last, err = run_harness(
        ["--workload", "bert_base_train_b64_t128", "--seed", "9",
         "--seconds", "0.3", "--trace", "1", "--tiny"], root=str(root))
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True
    assert last["metrics"]["steps_traced"]["value"] == last["attempted"]
    assert "host_ms_per_step" in last["metrics"]
    # nothing of a device trace under a device metric's name on the CPU
    for name in ("step_mfu", "mxu_roofline", "device_idle_pct",
                 "non_mxu_ms_per_step", "peak_hbm_gb"):
        assert name not in last["metrics"]
    assert "busy_s" not in last["device"]
    for path, body in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == body, path
