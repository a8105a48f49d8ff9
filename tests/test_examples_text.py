"""Smoke tier for the text and sequence examples (ref: the reference's
example/cnn_text_classification, example/nce-loss,
example/named_entity_recognition, example/multivariate_time_series,
example/bi-lstm-sort, example/rnn/bucketing). Each runs end to end with
tiny settings and asserts its learning signal."""
import pytest


@pytest.mark.slow
def test_bi_lstm_sort_example(load_example):
    acc = load_example("bi_lstm_sort/sort_lstm.py").main(
        ["--steps", "180", "--seq-len", "5", "--vocab", "6",
         "--hidden", "24", "--batch-size", "24"])
    assert acc > 0.5


def test_text_cnn_example(load_example):
    acc = load_example("cnn_text_classification/text_cnn.py").main(
        ["--steps", "100"])
    assert acc > 0.8


def test_nce_loss_example(load_example):
    acc = load_example("nce_loss/nce_lm.py").main(["--steps", "300"])
    assert acc > 0.5  # untrained top-1 is 1/200


def test_lstnet_forecast_example(load_example):
    first, last = load_example("multivariate_time_series/lstnet.py").main(
        ["--steps", "120"])
    assert last < first * 0.3


def test_ner_example_masked_tagging(load_example):
    acc = load_example("named_entity_recognition/ner.py").main(
        ["--steps", "120"])
    assert acc > 0.85


def test_bucketing_lm_example(load_example):
    """Variable-length bucketed LM (ref: example/rnn/bucketing) —
    the bucketed-jit answer to dynamic sequence lengths."""
    ppl = load_example("rnn/bucketing_lm.py").main(["--epochs", "10"])
    assert ppl < 6.0  # random would be ~15


def test_combined_mesh_lm_example(load_example):
    """Five-axis combined mesh example (dp x tp x sp x ep x pipe; the
    model-parallel story told mesh-first) trains under loss descent."""
    loss = load_example("model_parallel/combined_mesh_lm.py").main(
        ["--steps", "8"])
    assert loss < 5.8  # V=256 -> untrained ~ ln(256)=5.54+moe noise
