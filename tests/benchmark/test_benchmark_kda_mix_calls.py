"""``kda_mix_kernel_calls``: the reader against the program's counters
made by hand (nothing without them: the parent commit keeps none in
Ling's cell), the manifest's entry, and the traced tiny run of the Ling
cell, whose mixers take the XLA composition on the CPU.
"""
import importlib

import pytest

from bench_helpers import manifest, run_harness

CELL = "ling_3_flash_train_b1_t4096"
METRIC = "kda_mix_kernel_calls"


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
            if name.startswith("kda_mix_traced_total."):
                metrics.unregister(name)

    clear()
    yield lambda backend, n: metrics.counter(
        f"kda_mix_traced_total.{backend}").inc(n)
    clear()


@pytest.mark.parametrize("traced,calls", [
    ({"kernel": 12}, 12),               # the cell on the chip
    ({"fused": 12}, 12),                # an XLA custom_vjp in its place
    ({"xla": 12}, 0),                   # the CPU, or heads of 64
], ids=["kernel", "fused", "xla-only"])
def test_the_reader_reads_the_kernel_counter(counters, traced, calls):
    run = FakeRun()
    assert read(run) is None and run.result["detail"] == {}
    for backend, n in traced.items():
        counters(backend, n)
    assert read(run) == calls
    assert run.result["detail"]["kda_mix_backend"] == traced


def test_the_manifests_entry():
    entry = [m for m in manifest()["per_layer"] if m["name"] == METRIC]
    assert entry == [{"name": METRIC, "unit": "count", "better": "higher",
                      "source": "program_counter", "layer": "Kernels",
                      "moves": "step_ms", "workloads": [CELL]}]


def test_the_traced_tiny_run_counts_its_mixers():
    """Three delta-rule mixers, each traced by the shape-resolving
    forward and by the step; none takes the kernel pair on the CPU."""
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 38), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"][METRIC] == {"value": 0, "unit": "count"}
    assert last["detail"]["kda_mix_backend"] == {"xla": 6}
    assert last["detail"]["kda_backend"] == {"chunked": 6}
