"""``programs_per_step``: the programs on the chip's ``XLA Modules``
line inside the steady window over the window's steps, on the recorded
trace the other reader tests use (``data/two_steps.xspace.txt``: the
step's program twice and one small program between them), and as the
manifest names it.
"""
import os

import jax
import pytest

import bench_helpers
from benchmark import trace_reduce
from benchmark.layer_metrics import programs_per_step

HERE = os.path.dirname(os.path.abspath(__file__))


class FakeRun:
    def __init__(self, summary):
        self.summary = summary


@pytest.fixture(scope="module")
def summary():
    with open(os.path.join(HERE, "data", "two_steps.xspace.txt")) as f:
        text = "\n".join(l for l in f.read().splitlines()
                         if not l.startswith("#"))
    raw = trace_reduce.read_xplane(
        profile=jax.profiler.ProfileData.from_text_proto(text))
    return trace_reduce.summarize(raw, "jit_pure_step")


def test_programs_run_in_the_window_over_its_steps(summary):
    assert (summary["module_runs"], summary["steps"]) == (3, 2)
    assert programs_per_step.read(FakeRun(summary)) == 1.5


@pytest.mark.parametrize("leaves", [150, 193])
def test_a_program_a_scalar_shows_as_programs_a_step(summary, leaves):
    # what the parent commit's traces read: the step, the key's two,
    # and a scalar program for the rate and the decay of every leaf
    s = dict(summary, module_runs=(3 + 2 * leaves) * summary["steps"])
    assert programs_per_step.read(FakeRun(s)) == 3 + 2 * leaves


@pytest.mark.parametrize("summary_", [None, {"steps": 0,
                                             "module_runs": 7}])
def test_without_a_steady_window_it_reads_nothing(summary_):
    assert programs_per_step.read(FakeRun(summary_)) is None


def test_the_manifest_names_it_under_the_trainers_host_layer():
    entry = [e for e in bench_helpers.manifest()["per_layer"]
             if e["name"] == "programs_per_step"]
    assert entry == [{
        "name": "programs_per_step", "unit": "count", "better": "lower",
        "source": "device_trace", "layer": "Trainer host work",
        "moves": "step_ms"}]
    assert os.path.exists(os.path.join(
        bench_helpers.ROOT, "benchmark", "layer_metrics",
        "programs_per_step.py"))
