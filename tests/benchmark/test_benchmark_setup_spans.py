"""The four readers of the program's set-up (``step_trace_s``,
``step_lower_s``, ``step_compile_s``, ``setup_eager_s``) on a hand-made
registry, their manifest entries, and the ``--tiny --trace 1`` rehearsal
that prints them from a real run on the CPU.
"""
import importlib
import os
import time

import pytest

from bench_helpers import ROOT, manifest, run_harness

METRICS = ("step_trace_s", "step_lower_s", "step_compile_s",
           "setup_eager_s")


class FakeRun:
    def __init__(self):
        self.result = {"detail": {}}


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def _registry(monkeypatch, observed=(), counted=()):
    """Make the program's registry hold exactly the histograms
    ``observed`` ({name: [seconds, ...]}) and the counters ``counted``
    ({name: value})."""
    from mxnet_tpu.telemetry import metrics
    made = {}
    for name, values in dict(observed).items():
        h = made[name] = metrics.Histogram(name)
        for v in values:
            h.observe(v)
    for name, value in dict(counted).items():
        c = made[name] = metrics.Counter(name)
        c.inc(value)
    monkeypatch.setattr(metrics, "all_metrics", lambda: dict(made))


PROGRAM = {
    "step_program_trace_seconds": [2.5],
    "step_program_lower_seconds": [1.25],
    "step_program_compile_seconds": [40.0],
    "setup_eager_seconds": [0.5, 3.0],
    "eager_program_build_seconds": [0.25, 0.5, 1.0],
}


@pytest.mark.parametrize("name,value", [
    ("step_trace_s", 2.5), ("step_lower_s", 1.25),
    ("step_compile_s", 40.0), ("setup_eager_s", 3.5)])
def test_each_reader_sums_its_histogram(monkeypatch, name, value):
    _registry(monkeypatch, PROGRAM, {"eager_programs_built_total": 7})
    assert _reader(name).read(FakeRun()) == pytest.approx(value)


@pytest.mark.parametrize("name", METRICS)
def test_without_the_programs_instruments_a_reader_gives_nothing(
        monkeypatch, name):
    # the parent's registry: the cache counters, none of these
    _registry(monkeypatch, {}, {"jax_compile_cache_misses_total": 3})
    run = FakeRun()
    assert _reader(name).read(run) is None
    assert run.result["detail"] == {}


def test_trace_reader_records_the_misses(monkeypatch):
    _registry(monkeypatch, {**PROGRAM,
                            "step_program_trace_seconds": [2.0, 0.5]})
    run = FakeRun()
    assert _reader("step_trace_s").read(run) == pytest.approx(2.5)
    assert run.result["detail"]["step_misses"] == 2


@pytest.mark.parametrize("reads,source", [
    ([], "compiler"), ([0.75], "cache"), ([0.75, 0.25], "cache")])
def test_compile_reader_says_where_the_program_came_from(monkeypatch,
                                                         reads, source):
    compiles = [5.0] * max(1, len(reads))
    _registry(monkeypatch, {
        **PROGRAM, "step_program_compile_seconds": compiles,
        **({"step_program_cache_retrieval_seconds": reads}
           if reads else {})})
    run = FakeRun()
    assert _reader("step_compile_s").read(run) == \
        pytest.approx(sum(compiles))
    assert run.result["detail"]["step_compile_source"] == source
    assert run.result["detail"]["cache_retrieval_s"] == \
        pytest.approx(sum(reads))


def test_a_step_built_twice_once_from_the_cache_is_mixed(monkeypatch):
    _registry(monkeypatch, {
        **PROGRAM, "step_program_compile_seconds": [30.0, 2.0],
        "step_program_cache_retrieval_seconds": [1.5]})
    run = FakeRun()
    _reader("step_compile_s").read(run)
    assert run.result["detail"]["step_compile_source"] == "mixed"


@pytest.mark.parametrize("tune", [None, [4.0, 2.0]])
def test_eager_reader_records_the_programs_it_built(monkeypatch, tune):
    observed = dict(PROGRAM)
    if tune is not None:
        observed["operator_tune_measure_seconds"] = tune
    _registry(monkeypatch, observed, {"eager_programs_built_total": 7})
    run = FakeRun()
    assert _reader("setup_eager_s").read(run) == pytest.approx(3.5)
    detail = run.result["detail"]
    assert detail["eager_programs_built"] == 7
    assert detail["eager_program_build_s"] == pytest.approx(1.75)
    if tune is None:
        assert "operator_tune_s" not in detail
    else:
        assert detail["operator_tune_s"] == pytest.approx(6.0)


@pytest.mark.parametrize("name", METRICS)
def test_the_manifest_has_the_metric(name):
    m = manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    entry = by_name[name]
    # every cell runs a fused step: no workloads list
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_counter", "layer": "Set-up",
                     "moves": "setup_s"}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_tiny_traced_rehearsal_prints_all_four():
    t0 = time.perf_counter()
    rc, last, err = run_harness(
        ["--workload", "resnet50_train_b256", "--seed", str(2 ** 31 + 37),
         "--seconds", "1", "--trace", "1", "--tiny"])
    wall = time.perf_counter() - t0
    assert rc == 0 and last is not None, err[-3000:]
    values = {name: last["metrics"][name]["value"] for name in METRICS}
    assert all(v > 0 for v in values.values()), values
    # all set-up, none of it counted twice: inside the process's wall
    assert sum(values.values()) < wall
    detail = last["detail"]
    assert detail["step_misses"] == 1
    assert detail["step_compile_source"] in ("compiler", "cache")
    assert detail["eager_programs_built"] > 0
    assert 0 < detail["eager_program_build_s"] <= values["setup_eager_s"]
