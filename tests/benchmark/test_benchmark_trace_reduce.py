"""The reduction from a trace to numbers, on a small hand-built trace
kept beside this file (``data/two_steps.xspace.txt``, in the layout of a
TPU trace) and a small HLO text (``data/step.hlo.txt``): the steady
window on the trace's own clock, the busy union, the idle share, each
operation's own time, the idle gaps with what the host was in, which
instructions the HLO says hold matrix work and how many FLOPs they
hold. No chip and no described topology here.
"""
import os

import jax
import pytest

import bench_helpers  # noqa: F401  (puts the checkout on sys.path)
from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def raw():
    with open(os.path.join(HERE, "data", "two_steps.xspace.txt")) as f:
        text = "\n".join(l for l in f.read().splitlines()
                         if not l.startswith("#"))
    profile = jax.profiler.ProfileData.from_text_proto(text)
    return trace_reduce.read_xplane(profile=profile)


@pytest.fixture(scope="module")
def hlo():
    with open(os.path.join(HERE, "data", "step.hlo.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def classes(hlo):
    return trace_reduce.classify_hlo(hlo)


def test_planes_and_lines_are_found(raw):
    assert sorted(raw["devices"]) == [0]
    dev = raw["devices"][0]
    assert len(dev["ops"]) == 10 and len(dev["modules"]) == 4
    # names lose the HLO's percent sign; host spans keep theirs
    assert {n for n, _, _ in dev["ops"]} == {
        "fusion.1", "copy.2", "while.3", "fusion.4", "add.5", "fusion.6",
        "convert.8"}
    assert "bench.block" in {n for n, _, _ in raw["host"]}


def test_steady_window_is_whole_periods_on_the_device_clock(raw, hlo):
    program = trace_reduce.module_name(hlo)
    assert program == "jit_pure_step"
    assert trace_reduce.module_name("jit_pure_step(1)") == program
    modules = raw["devices"][0]["modules"]
    # first run of the step's program to its last: two periods, the
    # small program between them counted in, the third run left out
    assert trace_reduce.steady_window(modules, program) == (
        1000.0, 27000.0, 2)
    assert trace_reduce.steady_window(modules[:1], program) is None
    assert trace_reduce.steady_window(modules, "jit_other") is None


def test_busy_union_counts_nested_events_once(raw):
    # [1000,10000) + [14000,19000) + [20000,21000) + [22000,22500)
    # + [27000,31000)
    assert trace_reduce.union_ns(raw["devices"][0]["ops"]) == 19500.0


def test_self_times_take_children_out(raw):
    own = trace_reduce.self_times(raw["devices"][0]["ops"])
    assert own == {"fusion.1": 12000.0, "copy.2": 2000.0,
                   "while.3": 1000.0, "fusion.4": 2000.0,
                   "add.5": 1000.0, "fusion.6": 1000.0,
                   "convert.8": 500.0}
    assert sum(own.values()) == 19500.0  # own times add up to the union


def test_classify_hlo_follows_calls(classes):
    # a fusion over a convolution, a fusion over a dot inside a while
    # body, the while that holds it, and a Mosaic custom call
    assert classes == {"convolution.9": "mxu", "dot.3": "mxu",
                       "fusion.1": "mxu", "fusion.4": "mxu",
                       "while.3": "mxu", "fusion.6": "mxu"}
    assert "copy.2" not in classes and "add.5" not in classes


def test_hlo_flops_from_the_lines_own_shapes(hlo):
    conv = 2.0 * (8 * 16 * 32 * 32) * (3 * 3 * 3)  # 3x3 over 3 channels
    dot = 2.0 * (8 * 4) * 16
    # (the convolution's line prints its operands' shapes, the dot's only
    # their names). A fusion holds what its computation holds; the while
    # holds nothing of its own (its body's fusion is an event itself); a
    # Mosaic kernel's FLOPs the HLO does not tell
    assert trace_reduce.hlo_flops(hlo) == {
        "convolution.9": conv, "fusion.1": conv, "dot.3": dot,
        "fusion.4": dot, "fusion.6": None}


def test_summary_is_read_inside_the_steady_window(raw, hlo, classes):
    s = trace_reduce.summarize(
        raw, "jit_pure_step", classes,
        annotations=("bench.dispatch", "bench.block"),
        flops=trace_reduce.hlo_flops(hlo))
    assert s["chips"] == 1 and s["steps"] == 2
    # the window and the busy time are the trace's: no host clock
    assert s["window_s"] == pytest.approx(26e-6)
    assert s["busy_s"] == pytest.approx(15.5e-6)
    assert s["module_runs"] == 3  # two steps and the small program
    # while.3's own microsecond is loop overhead of a loop that holds a
    # dot: it goes with the matrix class, its add does not; the third
    # run's fusion.1 lies outside the window
    assert s["class_seconds"]["mxu"] == pytest.approx(12e-6)
    assert s["class_seconds"]["other"] == pytest.approx(3.5e-6)
    assert list(s["op_seconds"])[0] == "fusion.1"
    assert s["op_seconds"]["fusion.1"] == pytest.approx(8e-6)
    # the matrix events' FLOPs by the HLO, a step: fusion.1 twice and
    # fusion.4 once over two steps; fusion.6 is a Mosaic kernel
    conv, dot = 2.0 * (8 * 16 * 32 * 32) * 27, 2.0 * 32 * 16
    assert s["mxu_hlo_flops"] == (2 * conv + dot) / 2
    assert s["mxu_unknown"] == ["fusion.6"]
    # the longest gap runs to the window's end inside bench.dispatch,
    # the next lies inside bench.block, the third under no span of the
    # harness ("main" and jax's own spans are not asked for)
    assert s["idle_gaps"][0] == ("bench.dispatch", pytest.approx(4.5e-6))
    assert s["idle_gaps"][1] == ("bench.block", pytest.approx(4e-6))
    assert s["idle_gaps"][2] == ("none", pytest.approx(1e-6))
    b = trace_reduce.breakdown(s, classes)
    assert b["device_ops"][0] == ["fusion.1[mxu]", pytest.approx(4e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


class _Family:
    def __init__(self, need):
        self.need = need

    def needed_flops(self, sizes, traffic):
        return self.need

    def matrix_layers(self, sizes, traffic):
        return [("all", self.need, 1.0)]


def _reader_run(summary, need):
    from benchmark import run as harness
    device = type("Device", (), {"device_kind": "TPU v5 lite"})()
    return harness.Run(
        summary=summary, family=_Family(need), sizes={}, traffic={},
        device=device,
        peaks=bench_helpers.load("benchmark", "peaks.json"))


def test_device_readers_take_the_traces_clock(raw, hlo, classes):
    from benchmark.layer_metrics import device_idle_pct, step_mfu
    s = trace_reduce.summarize(raw, "jit_pure_step", classes)
    run = _reader_run(s, need=197e12 * 13e-6 * 0.5)
    # half of the peak's FLOPs in a period of 13 microseconds
    assert step_mfu.read(run) == pytest.approx(50.0)
    assert device_idle_pct.read(run) == pytest.approx(
        100 * (1 - 15.5 / 26))


def test_mxu_roofline_fails_where_the_classes_miss_matrix_work(
        raw, hlo, classes):
    from benchmark.layer_metrics import mxu_roofline
    flops = trace_reduce.hlo_flops(hlo)
    s = trace_reduce.summarize(raw, "jit_pure_step", classes, flops=flops)
    # a Mosaic kernel among the events: its FLOPs are unknown, no check
    assert mxu_roofline.read(_reader_run(s, need=1e12)) > 0
    known = {k: v for k, v in classes.items() if k != "fusion.6"}
    s = trace_reduce.summarize(raw, "jit_pure_step", known, flops=flops)
    need = s["mxu_hlo_flops"]
    # the least time of `need` FLOPs over the 5.5 us a step of the events
    least = need / 197e12
    assert mxu_roofline.read(_reader_run(s, need)) == pytest.approx(
        100 * least / 5.5e-6)
    with pytest.raises(RuntimeError, match="classed with the others"):
        mxu_roofline.read(_reader_run(s, need=1.1 * need))


def test_a_trace_without_two_runs_of_the_program_gives_nothing(raw):
    empty = {"devices": {0: {"ops": [], "modules": []}},
             "host": raw["host"]}
    assert trace_reduce.summarize(empty, "jit_pure_step") is None
    assert trace_reduce.summarize(raw, "jit_other") is None
