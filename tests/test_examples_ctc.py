"""Smoke tier for the CTC example family (ref: the reference's
example/ctc, example/speech_recognition, example/captcha): an acoustic
bi-LSTM, a captcha CNN and the plain CTC trainer, each run end to end
with tiny settings and held to its learning signal."""
import pytest


@pytest.mark.slow
def test_ctc_example_loss_decreases(load_example):
    first, last = load_example("ctc/ctc_train.py").main(
        ["--steps", "70", "--seq-len", "14", "--label-len", "3",
         "--vocab", "5", "--hidden", "32", "--batch-size", "8"])
    assert last < first * 0.85


def test_captcha_cnn_ctc_trains(load_example):
    first, last = load_example("captcha/cnn_ctc.py").main(["--steps", "80"])
    assert last < first * 0.7


def test_speech_recognition_ctc_trains(load_example):
    first, last = load_example("speech_recognition/lstm_ctc.py").main(
        ["--steps", "100"])
    assert last < first * 0.3
