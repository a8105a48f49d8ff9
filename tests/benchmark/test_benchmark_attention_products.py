"""The two readers of the ``attn`` / ``products`` scope on the tiny BERT
rehearsal: the step program that ``families/bert.py`` builds at the
configuration's ``tiny`` sizes, compiled here on the CPU, its HLO's own
scope paths, and a made-up device time for every instruction (a CPU run
has no device trace, and gives no time)."""
import importlib

import pytest

from bench_helpers import load
from benchmark import span_reduce
from benchmark.families import bert

MS = 1e-3  # seconds given to every instruction of the step's HLO


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


class Device:
    platform, device_kind = "tpu", "TPU v5 lite"


class FakeRun:
    def __init__(self, hlo, sizes, mix):
        self.result = {"hlo_text": hlo, "detail": {}}
        names = span_reduce.op_paths(hlo)
        self.summary = {"op_seconds": dict.fromkeys(names, MS), "steps": 1,
                        "busy_s": MS * len(names)}
        self.sizes, self.traffic, self.family = sizes, mix, bert
        self.device, self.peaks = Device, load("benchmark", "peaks.json")


@pytest.fixture(scope="module")
def tiny_step():
    """``(hlo text, sizes, traffic, attention calls traced)`` of the
    tiny BERT cell's fused step."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import _wrap
    from mxnet_tpu.telemetry import metrics

    config = load("benchmark", "configs", "bert_base.json")
    mix = load("benchmark", "traffic", "train_b16_t512.json")
    over = config["tiny"]
    sizes, mix = {**config, **over["sizes"]}, {**mix, **over["traffic"]}
    before = {label: metrics.counter(
        f"attention_traced_total.{label}").value()
        for label in ("kernel", "dense")}
    weights = bert.make_weights(sizes, config["dtype_policy"], 7)
    (x, y), *_ = bert.make_batches(sizes, config["dtype_policy"], mix, 7)
    net, loss_fn = bert.build_program(sizes, config["dtype_policy"], weights,
                                      mx.cpu(0), x)
    opt = dict(config["optimizer"])
    trainer = gluon.Trainer(net.collect_params(), opt.pop("name"), opt)
    fused = trainer.fuse_step(net, loss_fn)
    fused.step(_wrap(x), _wrap(y))
    hlo = fused.compiled(_wrap(x), _wrap(y)).as_text()
    traced = {label: metrics.counter(
        f"attention_traced_total.{label}").value() - was
        for label, was in before.items()}
    return hlo, sizes, mix, traced


def test_the_products_scope_is_found_inside_the_attention_block(tiny_step):
    hlo, sizes, mix, traced = tiny_step
    run = FakeRun(hlo, sizes, mix)
    products = reader("attention_products_ms_per_step")(run)
    whole = reader("attention_ms_per_step")(run)
    assert products is not None and 0 < products <= whole
    # the projections and the relayouts of the heads lie outside it
    assert products < whole
    # both passes are there: the tape replays the scoped function
    paths = [p for p in span_reduce.run_paths(run).values()
             if "/attn/products/" in p]
    assert {span_reduce.phase_of(p) for p in paths} == {"fwd", "bwd"}
    # on the CPU every layer traced the dense composition, once in the
    # shape-resolving forward and once in the step
    layers = sizes["num_hidden_layers"]
    assert traced == {"kernel": 0, "dense": 2 * layers}
    backend = run.result["detail"]["attention_backend"]
    assert set(backend) == {"kernel", "dense"}
    assert backend["dense"] >= 2 * layers and backend["kernel"] == 0


def test_the_roofline_takes_the_familys_attention_rows(tiny_step):
    hlo, sizes, mix, _ = tiny_step
    run = FakeRun(hlo, sizes, mix)
    rows = [(f, b) for name, f, b in bert.matrix_layers(sizes, mix)
            if name.endswith(".attn.products")]
    assert len(rows) == sizes["num_hidden_layers"]
    least = max(sum(f for f, _ in rows) / 197e12,
                sum(b for _, b in rows) / 819e9)
    ms = reader("attention_products_ms_per_step")(run)
    assert reader("attention_products_roofline")(run) == \
        pytest.approx(100 * least / (1e-3 * ms))


def test_at_the_full_size_the_rows_are_464_gflop_and_memory_bound():
    config = load("benchmark", "configs", "bert_base.json")
    mix = load("benchmark", "traffic", "train_b16_t512.json")
    rows = [(f, b) for name, f, b in bert.matrix_layers(config, mix)
            if name.endswith(".attn.products")]
    flops, nbytes = sum(f for f, _ in rows), sum(b for _, b in rows)
    assert flops == 12 * 3 * 2 * 16 * 2 * 512 * 512 * 768
    assert nbytes == 12 * 3 * 4 * 4 * 16 * 512 * 768
    assert nbytes / 819e9 > flops / 197e12


def test_without_a_products_scope_both_readers_give_nothing(tiny_step):
    """The parent commit's case: its HLO names ``attn`` and no
    ``products`` below it."""
    hlo, sizes, mix, _ = tiny_step
    run = FakeRun(hlo.replace("/attn/products/", "/attn/"), sizes, mix)
    assert reader("attention_ms_per_step")(run) is not None
    assert reader("attention_products_ms_per_step")(run) is None
    assert reader("attention_products_roofline")(run) is None
    assert "attention_backend" not in run.result["detail"]


def test_without_a_trace_both_readers_give_nothing(tiny_step):
    hlo, sizes, mix, _ = tiny_step
    run = FakeRun(hlo, sizes, mix)
    run.summary = None
    assert reader("attention_products_ms_per_step")(run) is None
    assert reader("attention_products_roofline")(run) is None
