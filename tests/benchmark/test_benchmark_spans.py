"""The reductions of ``benchmark/span_reduce.py``: the program's span
trees (hand-made here, in the form ``mxnet_tpu.trace.drain()`` gives)
to host milliseconds a step, and the step's HLO scope names (lines
recorded from the BERT cell's step compiled for a v5e,
``data/step_scopes.hlo.txt``) with made-up event times to device
milliseconds by phase; then the ``--tiny --trace 1`` rehearsal, which
prints the four ``program_span`` metrics and, having no device trace,
none of the four ``device_trace`` ones.
"""
import os

import pytest

import bench_helpers
from benchmark import span_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
HOST = ("host_prep_ms_per_step", "host_dispatch_ms_per_step",
        "host_writeback_ms_per_step", "host_blocked_ms_per_step")
DEVICE = ("fwd_ms_per_step", "bwd_ms_per_step", "opt_ms_per_step",
          "attention_ms_per_step")


def span(name, sid, parent, dur_us, **attrs):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "dur_us": dur_us, "attrs": attrs}


def step(n, prep_us, cpu_ns=None):
    """One step's spans: a root of 1000 us, prep with one child,
    dispatch 100 us, writeback 50 us."""
    cpu = {} if cpu_ns is None else {"cpu_ns": cpu_ns}
    r = f"r{n}"
    return [span("train.step", r, None, 1000.0, step=n, **cpu),
            span("step.prep", f"p{n}", r, prep_us, **cpu),
            span("step.prep.hyper", f"h{n}", f"p{n}", prep_us / 2, **cpu),
            span("step.dispatch", f"d{n}", r, 100.0, **cpu),
            span("step.writeback", f"w{n}", r, 50.0, **cpu)]


SPANS = [span("serve.request", "s0", None, 5.0)] \
    + step(0, 9999.0, cpu_ns=0) + step(1, 600.0, cpu_ns=400_000) \
    + step(2, 800.0, cpu_ns=700_000) \
    + [span("step.prep", "stray", "nobody", 77.0)]


class FakeRun:
    def __init__(self, hlo_text, op_seconds, steps=2, busy_s=None):
        self.result = {"hlo_text": hlo_text}
        self.summary = {"op_seconds": op_seconds, "steps": steps,
                        "busy_s": sum(op_seconds.values())
                        if busy_s is None else busy_s}


@pytest.fixture(scope="module")
def scoped_hlo():
    with open(os.path.join(HERE, "data", "step_scopes.hlo.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def unscoped_hlo():
    with open(os.path.join(HERE, "data", "step.hlo.txt")) as f:
        return f.read()


# seconds over a window of two steps, by instruction
OP_SECONDS = {"fusion.103": 0.010, "convolution_add_fusion.4": 0.020,
              "fusion.7": 0.002, "select_reduce_fusion": 0.030,
              "fusion.118": 0.040, "divide_subtract_fusion": 0.006,
              "copy-done.93": 0.001, "copy-done.300": 0.001,
              "convert_element_type.5": 0.001}


def test_the_last_steps_trees_are_taken_by_parent_id():
    trees = span_reduce.group_steps(SPANS, 2)
    assert [root["attrs"]["step"] for root, _ in trees] == [1, 2]
    for root, below in trees:
        assert {s["name"] for s in below} == {
            "step.prep", "step.prep.hyper", "step.dispatch",
            "step.writeback"}
        assert all(s["span_id"].endswith(str(root["attrs"]["step"]))
                   for s in below)


@pytest.mark.parametrize("name,ms", [("step.prep", 0.7),
                                     ("step.prep.hyper", 0.35),
                                     ("step.dispatch", 0.1),
                                     ("step.writeback", 0.05),
                                     ("step.compile", None)])
def test_span_wall_a_step(name, ms):
    trees = span_reduce.group_steps(SPANS, 2)
    assert span_reduce.span_ms_per_step(trees, name) == (
        None if ms is None else pytest.approx(ms))


def test_blocked_is_the_roots_wall_less_their_cpu_time():
    trees = span_reduce.group_steps(SPANS, 2)
    # (1000 - 400) us and (1000 - 700) us
    assert span_reduce.blocked_ms_per_step(trees) == pytest.approx(0.45)


@pytest.mark.parametrize("spans,steps", [
    (SPANS, 4),                       # fewer roots than steps
    ([], 2),                          # tracing off: nothing drained
    (SPANS, 0)])
def test_without_the_windows_roots_the_host_readers_give_nothing(spans,
                                                                 steps):
    trees = span_reduce.group_steps(spans, steps)
    assert trees is None
    assert span_reduce.span_ms_per_step(trees, "step.prep") is None
    assert span_reduce.blocked_ms_per_step(trees) is None


def test_a_program_that_records_no_cpu_time_gives_no_blocked_time():
    older = step(0, 500.0) + step(1, 500.0)
    trees = span_reduce.group_steps(older, 2)
    assert span_reduce.span_ms_per_step(trees, "step.prep") == \
        pytest.approx(0.5)
    assert span_reduce.blocked_ms_per_step(trees) is None


@pytest.mark.parametrize("path,phase", [
    ("jit(pure_step)/jvp(forward)/layers/0/attn/qkv/FullyConnected/"
     "dot_general", "fwd"),
    ("jit(pure_step)/forward/layers/0/ln1/LayerNorm/add", "fwd"),
    ("jit(pure_step)/transpose(jvp(forward))/layers/0/attn/mul", "bwd"),
    ("jit(pure_step)/optimizer/sub", "opt"),
    ("jit(pure_step)/optimizer/exchange/psum", "opt"),
    ("jit(convert_element_type)/convert_element_type", None),
    ("inputs[0]", None)])
def test_phase_by_the_outermost_naming_segment(path, phase):
    assert span_reduce.phase_of(path) == phase


def test_op_paths_reads_the_recorded_lines(scoped_hlo):
    paths = span_reduce.op_paths(scoped_hlo)
    assert paths["fusion.118"].endswith(
        "layers/1/attn/proj/FullyConnected/dot_general")
    assert paths["fusion.7"] == "jit(pure_step)/jvp(forward)/mean/div"


def test_a_compilers_copy_takes_its_nearest_named_consumers_path(
        scoped_hlo):
    paths = span_reduce.op_paths(scoped_hlo)
    # copy-start.93 -> copy-done.93 -> the q/k/v product's fusion
    assert paths["copy-done.93"] == paths["copy-start.93"] == \
        paths["convolution_add_fusion.4"]
    # what nothing named consumes keeps no path
    assert "copy-done.300" not in paths


@pytest.mark.parametrize("phase,ms", [("fwd", 16.5), ("bwd", 35.0),
                                      ("opt", 3.0)])
def test_device_time_by_phase(scoped_hlo, phase, ms):
    run = FakeRun(scoped_hlo, OP_SECONDS)
    assert span_reduce.phase_ms_per_step(run, phase) == pytest.approx(ms)
    # what names no phase (another program's operation, a copy that
    # nothing named consumes) is a class of its own, here 2 of 110 ms
    assert span_reduce.phase_seconds(run)[None] == pytest.approx(0.002)


def test_time_under_a_blocks_scope_forward_and_backward(scoped_hlo):
    run = FakeRun(scoped_hlo, OP_SECONDS)
    assert span_reduce.scope_ms_per_step(run, "attn") == \
        pytest.approx(30.5)
    assert span_reduce.scope_ms_per_step(run, "no_such_block") is None


def test_phase_readers_raise_where_the_unscoped_class_is_visible(
        scoped_hlo):
    run = FakeRun(scoped_hlo, {**OP_SECONDS, "copy-done.300": 0.010})
    with pytest.raises(RuntimeError, match="names no phase"):
        span_reduce.phase_ms_per_step(run, "fwd")
    # the attention reader names a scope, not the three phases
    assert span_reduce.scope_ms_per_step(run, "attn") == \
        pytest.approx(30.5)


@pytest.mark.parametrize("reader", ["phase", "scope"])
def test_an_hlo_without_scope_names_gives_nothing_not_zero(unscoped_hlo,
                                                           reader):
    run = FakeRun(unscoped_hlo, {"fusion.4": 0.5, "add.5": 0.1})
    assert span_reduce.phase_seconds(run) is None
    if reader == "phase":
        assert span_reduce.phase_ms_per_step(run, "fwd") is None
    else:
        assert span_reduce.scope_ms_per_step(run, "attn") is None


def test_no_trace_gives_nothing(scoped_hlo):
    run = FakeRun(scoped_hlo, OP_SECONDS)
    run.summary = None
    assert span_reduce.phase_ms_per_step(run, "fwd") is None
    assert span_reduce.scope_ms_per_step(run, "attn") is None


@pytest.mark.parametrize("cell", ["bert_base_train_b16_t512"])
def test_tiny_traced_rehearsal_prints_the_program_span_metrics(cell):
    rc, out, err = bench_helpers.run_harness(
        ["--workload", cell, "--seed", "2500000001", "--seconds", "1",
         "--trace", "1", "--tiny"])
    assert rc == 0, err[-2000:]
    metrics = out["metrics"]
    assert set(HOST) <= set(metrics)
    assert not set(DEVICE) & set(metrics)
    host = metrics["host_ms_per_step"]["value"]
    parts = sum(metrics[m]["value"] for m in HOST[:3])
    assert 0 < parts <= host
    assert 0 <= metrics["host_blocked_ms_per_step"]["value"] <= host
