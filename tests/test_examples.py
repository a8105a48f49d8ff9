"""Smoke tier for examples/ — every script must run end to end with
tiny settings (ref: the reference CI's example runs)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(ROOT, "examples")


def test_train_mnist_example(load_example):
    mod = load_example("image_classification/train_mnist.py")
    score = mod.main(["--epochs", "2", "--num-examples", "320",
                      "--batch-size", "32"])
    assert score[0][0] == "accuracy" and 0.0 <= score[0][1] <= 1.0


def test_train_gluon_example(load_example):
    mod = load_example("image_classification/train_gluon.py")
    acc = mod.main(["--model", "mobilenetv2_0.25", "--steps", "4",
                    "--batch-size", "8", "--image-size", "32",
                    "--hybridize"])
    assert 0.0 <= acc <= 1.0


def test_word_lm_example_learns(load_example):
    mod = load_example("rnn/word_lm.py")
    ppl = mod.main(["--epochs", "2"])
    assert ppl < 15.0  # vocab 36; untrained ppl ~36


def test_ssd_example_loss_decreases(load_example):
    mod = load_example("ssd/train_ssd.py")
    first, last, mean_ap = mod.main(["--steps", "12", "--batch-size",
                                     "4", "--image-size", "32"])
    assert last < first
    assert 0.0 <= mean_ap <= 1.0  # VOC07 mAP computed on the decode


def test_quantization_example(load_example):
    mod = load_example("quantization/quantize_model.py")
    err, agree = mod.main(["--calib-mode", "naive",
                           "--num-calib-batches", "2"])
    assert err < 0.15 and agree >= 0.75


def test_transformer_lm_example_moe_mesh(load_example):
    """The flagship example composes dp x tp x sp with MoE experts on
    the virtual mesh (conftest provides 8 CPU devices)."""
    mod = load_example("transformer/train_lm.py")
    last = mod.main(["--dp", "2", "--tp", "2", "--sp", "2",
                     "--num-experts", "2", "--steps", "50"])
    assert last < 1.0


@pytest.mark.skipif(
    os.environ.get("MXTPU_DIST_CPU_TESTS") != "1",
    reason="jaxlib CPU backend lacks multiprocess collectives (same "
           "gap as the test_dist_kvstore skips); set "
           "MXTPU_DIST_CPU_TESTS=1 to run anyway")
def test_distributed_example_two_processes():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", sys.executable,
         os.path.join(EX, "distributed", "train_dist.py"),
         "--steps", "50"],
        env=env, capture_output=True, text=True, timeout=280)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert out.count("DIST_TRAIN_OK") == 2, out[-2000:]
