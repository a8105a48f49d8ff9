"""``moe_overflow_layers``: the reader against the program's gauges made
by hand (nothing without them: the parent commit keeps none), the
manifest's entry, and the traced tiny run of the Laguna cell, whose
layers hold half the experts so that the buffer holds the worst case.
"""
import importlib

import pytest

from bench_helpers import manifest, run_harness

CELL = "laguna_xs2_train_b1_t8192"
METRIC = "moe_overflow_layers"


class FakeRun:
    def __init__(self):
        self.result = {"detail": {}}


def read(run):
    return importlib.import_module(
        f"benchmark.layer_metrics.{METRIC}").read(run)


@pytest.fixture
def gauges():
    from mxnet_tpu.telemetry import metrics

    def clear():
        for name in list(metrics.all_metrics()):
            if name.startswith("moe_"):
                metrics.unregister(name)

    clear()
    yield lambda name, layer, value: metrics.gauge(
        f"{name}.layers.{layer}").set(value)
    clear()


@pytest.mark.parametrize("routed,count,fill", [
    ((8000, 8400, 9000), 0, 9000 / 16384),
    ((8000, 16384, 16385), 1, 16385 / 16384),
    ((20000, 65536, 100), 2, 4.0),
], ids=["all-fit", "one-row-over", "two-layers-over"])
def test_the_reader_counts_the_layers_that_overflowed(gauges, routed, count,
                                                      fill):
    run = FakeRun()
    assert read(run) is None and run.result["detail"] == {}
    for layer, rows in enumerate(routed, start=1):
        gauges("moe_rows_routed", layer, rows)
        gauges("moe_buffer_rows", layer, 16384)
        gauges("moe_rows_overflow", layer, max(0, rows - 16384))
    assert read(run) == count
    assert run.result["detail"]["moe_buffer_fill"] == pytest.approx(fill)


def test_a_program_without_the_gauge_reports_nothing(gauges):
    """The parent commit's layers keep ``moe_rows_routed`` alone."""
    gauges("moe_rows_routed", 1, 8000)
    run = FakeRun()
    assert read(run) is None
    assert "moe_buffer_fill" not in run.result["detail"]


def test_the_manifests_entry():
    entry = [m for m in manifest()["per_layer"] if m["name"] == METRIC]
    assert entry == [{"name": METRIC, "unit": "count", "better": "lower",
                      "source": "program_counter", "layer": "Kernels",
                      "moves": "step_ms", "workloads": [CELL]}]
    assert manifest()["per_layer"][-1]["name"] == METRIC


def test_the_traced_tiny_run_reports_no_overflow():
    rc, last, err = run_harness(
        ["--workload", CELL, "--seed", str(2 ** 31 + 77), "--seconds",
         "0.3", "--trace", "1", "--tiny"])
    assert rc == 0 and last is not None, err[-3000:]
    assert last["correct"] is True, last["compared"]
    assert last["metrics"][METRIC] == {"value": 0, "unit": "count"}
    # 4 of 8 experts held: twice the expected rows is every row
    assert 0 < last["detail"]["moe_buffer_fill"] <= 1.0
