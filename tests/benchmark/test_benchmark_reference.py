"""The ResNet reference against the Gluon model with the parts that the
tiny preset leaves out: the full stem (7x7, BatchNorm, max-pool) and a
block without a shortcut convolution. The same weights and batches
through ``Trainer.fuse_step`` and through ``reference_follow`` give the
same losses, first-gradient norms and parameter changes to float32
rounding. Both references at their tiny presets are held to their tiny
limits by ``test_benchmark_correct.py``'s sound runs; the bf16 policy's
readings are the chip's to give.
"""
import importlib

import jax
from bench_helpers import load

from benchmark import correctness
from benchmark import run as harness
from benchmark.traffic_kinds import train_device_batches as kind

SIZES = {"thumbnail": False, "layers": [2, 1, 1, 1]}


def test_reference_follows_the_gluon_model():
    config = load("benchmark", "configs", "resnet50_v1.json")
    traffic = load("benchmark", "traffic", "train_b256.json")
    config = {**config, **config["tiny"]["sizes"], **SIZES}
    traffic = {**traffic, **config["tiny"]["traffic"], "batch": 4}
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    ctx = harness.Run(sizes=config, traffic=traffic, family=family,
                      opt=config["optimizer"], policy="f32", seed=5,
                      device=jax.devices("cpu")[0])
    net, trainer, fused, batches, readings = kind.prepare(ctx)
    got = readings.readings()
    weights = family.make_weights(config, "f32", 5)
    ref = correctness.reference_follow(
        family, config, config["optimizer"], weights, batches,
        correctness.step_keys(5), "reference")
    limits = {"loss_gap": 1e-5, "grad_gap": 2e-3, "delta_gap": 2e-3}
    correct, compared, detail = correctness.compare(got, ref, limits)
    assert correct, (compared, detail)
    # every leaf of the net is a leaf of the reference
    assert set(got["grad_norms"]) == set(ref["grad_norms"])
    assert detail["leaves_in_delta"] > detail["leaves"] // 2


def test_reference_param_shapes_match_the_net():
    config = load("benchmark", "configs", "resnet50_v1.json")
    from benchmark.families import resnet_v1
    shapes = resnet_v1.param_shapes(config)
    # 53 convolutions + the classifier, 53 BatchNorms of four leaves
    assert sum(1 for n in shapes if n.endswith(".weight")) == 54
    assert sum(1 for n in shapes if n.endswith(".running_var")) == 53
