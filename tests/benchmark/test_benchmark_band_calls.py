"""``window_attention_band_calls``: the reader against the program's
counters made by hand (nothing without them: the parent commit keeps
none in Laguna's cell), the manifest's entry, and the traced tiny run of
the Laguna cell, whose window layers take the XLA composition on the
CPU.
"""
import importlib

import pytest

from bench_helpers import manifest, run_harness

CELL = "laguna_xs2_train_b1_t8192"
METRIC = "window_attention_band_calls"


class FakeRun:
    def __init__(self):
        self.result = {"detail": {}}


def read(run):
    return importlib.import_module(
        f"benchmark.layer_metrics.{METRIC}").read(run)


@pytest.fixture
def counters():
    from mxnet_tpu.telemetry import metrics

    def clear():
        for name in list(metrics.all_metrics()):
            if name.startswith("attention_traced_total."):
                metrics.unregister(name)

    clear()
    yield lambda backend, n: metrics.counter(
        f"attention_traced_total.{backend}").inc(n)
    clear()


@pytest.mark.parametrize("traced,calls", [
    ({"band": 6, "kernel": 4}, 6),      # the cell on the chip
    ({"kernel": 10}, 0),                # a window the band kernel refuses
    ({"dense": 10}, 0),                 # the CPU
], ids=["band", "splash-only", "dense-only"])
def test_the_reader_reads_the_band_counter(counters, traced, calls):
    run = FakeRun()
    assert read(run) is None and run.result["detail"] == {}
    for backend, n in traced.items():
        counters(backend, n)
    assert read(run) == calls
    assert run.result["detail"]["attention_backend"] == traced


def test_the_manifests_entry():
    entry = [m for m in manifest()["per_layer"] if m["name"] == METRIC]
    assert entry == [{"name": METRIC, "unit": "count", "better": "higher",
                      "source": "program_counter", "layer": "Kernels",
                      "moves": "step_ms", "workloads": [CELL]}]


def test_the_traced_tiny_run_counts_its_attention_calls():
    """Five layers, each traced by the shape-resolving forward and by
    the step; none takes a kernel on the CPU."""
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 34), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"][METRIC] == {"value": 0, "unit": "count"}
    assert last["detail"]["attention_backend"] == {"dense": 10}
