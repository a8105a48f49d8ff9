"""Graph-optimizer tests (mxnet_tpu/opt/ — ISSUE 7).

The property the whole subsystem rides on: for every optimization
level, every fixture graph, and both execution modes, the optimized
graph matches the unoptimized one within the pipeline's DECLARED
tolerance class (bitwise for level 1, tolerance-tagged for level 2 —
the PR-5 parity discipline), with zero steady-state recompiles after
warmup. Plus per-pass targeted rewrites, the I/O-contract/verify
revert rails, Pallas fallback cleanliness on CPU, PassManager ordering
determinism, and the tools wiring.
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, nd, sym, telemetry
from mxnet_tpu.opt import (OptReport, build_manager, opt_level,
                           optimize_symbol, parity_check,
                           random_value_map)
from mxnet_tpu.opt.rewrite import MutableGraph
from mxnet_tpu.passes import Pass, PassManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
rs = onp.random.RandomState(7)


def _arr(*shape, lo=-1.0, hi=1.0):
    return nd.array(rs.uniform(lo, hi, shape).astype("float32"))


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    for f in ("MXNET_GRAPH_OPT", "MXNET_GRAPH_OPT_VERIFY",
              "MXNET_GRAPH_OPT_PALLAS"):
        config.unset_flag(f)


# ---------------------------------------------------------------------------
# fixture graphs
# ---------------------------------------------------------------------------

def conv_fixture():
    n = sym.var("data")
    for i, nf in enumerate((8, 16)):
        n = sym.Convolution(n, kernel=(3, 3), num_filter=nf,
                            pad=(1, 1), name=f"c{i}")
        n = sym.BatchNorm(n, name=f"bn{i}")
        n = sym.Activation(n, act_type="relu", name=f"r{i}")
    n = sym.Pooling(n, kernel=(2, 2), stride=(2, 2), pool_type="max",
                    name="p0")
    n = sym.Flatten(n)
    n = sym.FullyConnected(n, num_hidden=8, name="fc")
    return n, {"data": (2, 3, 8, 8)}


def lm_fixture(B=2, T=16, C=16, H=2):
    D = C // H
    x = sym.var("data")
    proj = {}
    for nm in ("q", "k", "v"):
        p = sym.FullyConnected(x, num_hidden=C, flatten=False,
                               no_bias=True, name=nm)
        p = sym.reshape(p, shape=(B, T, H, D))
        proj[nm] = sym.transpose(p, axes=(0, 2, 1, 3))
    scores = sym.batch_dot(proj["q"], proj["k"],
                           transpose_b=True) * (1.0 / D ** 0.5)
    att = sym.batch_dot(sym.softmax(scores, axis=-1), proj["v"])
    att = sym.reshape(sym.transpose(att, axes=(0, 2, 1, 3)),
                      shape=(B, T, C))
    f = sym.FullyConnected(att, num_hidden=C, flatten=False, name="ff")
    return sym.broadcast_add(x, f), {"data": (B, T, C)}


def mlp_fixture():
    """Symbol-mode graph with fold/cse/elide material."""
    x = sym.var("data")
    c = (sym.ones((1, 8)) * 2.0 + 1.0) / 3.0
    fc = sym.FullyConnected(x, num_hidden=8, name="fc1")
    a1 = sym.Activation(fc, act_type="relu", name="a1")
    a2 = sym.Activation(fc, act_type="relu", name="a2")
    n = sym.broadcast_add((a1 + 0.0) * 1.0, a2)
    n = sym.broadcast_add(n, c)
    return sym.FullyConnected(n, num_hidden=4, name="fc2"), \
        {"data": (4, 6)}


FIXTURES = {"conv": conv_fixture, "lm": lm_fixture, "mlp": mlp_fixture}
# level -> tolerance class the pipeline may use on these fixtures
LEVEL_CLASS = {1: "bitwise", 2: "fusion"}


# ---------------------------------------------------------------------------
# the property suite: parity at every level x fixture x mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", sorted(FIXTURES))
@pytest.mark.parametrize("level", [1, 2])
def test_parity_property(fixture, level):
    net, shapes = FIXTURES[fixture]()
    optimized, report = optimize_symbol(net, level=level,
                                        where=f"test:{fixture}")
    assert report is not None and report.reverted is None
    # binding surface is preserved verbatim
    assert optimized.list_arguments() == net.list_arguments()
    assert optimized.list_auxiliary_states() == \
        net.list_auxiliary_states()
    vm = random_value_map(net, shapes, seed=3)
    tol = report.tolerance_class
    # level 1 must not escalate past bitwise; level 2 may
    assert tol == "bitwise" if level == 1 else tol in (
        "bitwise", "layout", "fusion")
    for training in (False, True):
        ok, problems = parity_check(net, optimized, vm,
                                    training=training, tol_class=tol)
        assert ok, (f"{fixture} level {level} train={training}: "
                    f"{problems}")


@pytest.mark.parametrize("level", [0, 1, 2])
def test_executor_steady_state_recompiles(level):
    config.set_flag("MXNET_GRAPH_OPT", level)
    net, shapes = conv_fixture()
    ex = net.simple_bind(grad_req="null", **shapes)
    for nm, a in ex.arg_dict.items():
        a._rebind(_arr(*a.shape)._data)
    for _ in range(2):
        ex.forward(is_train=False)[0].asnumpy()
    rc0 = telemetry.recompile_count()
    for _ in range(4):
        ex.forward(is_train=False)[0].asnumpy()
    assert telemetry.recompile_count() - rc0 == 0
    if level:
        assert ex.opt_report is not None
    if level == 2:  # the conv fixture only has level-2 material
        assert ex.opt_report.total_rewrites > 0


def test_executor_backward_parity():
    """Fused/optimized executor gradients match level 0 within the
    declared class (train-mode forward_backward, fixed buffers)."""
    net, shapes = conv_fixture()
    rng = onp.random.RandomState(5)
    grads = {}
    for level in (0, 2):
        config.set_flag("MXNET_GRAPH_OPT", level)
        rs_l = onp.random.RandomState(11)
        ex = net.simple_bind(grad_req="write", **shapes)
        for nm in ex._arg_names:
            ex.arg_dict[nm]._rebind(nd.array(rs_l.uniform(
                -0.5, 0.5, ex.arg_dict[nm].shape)
                .astype("float32"))._data)
        ex.forward(is_train=True)
        ex.backward([nd.array(rng.uniform(
            -1, 1, ex.outputs[0].shape).astype("float32"))])
        grads[level] = {n: g.asnumpy().copy()
                        for n, g in ex.grad_dict.items()}
        rng = onp.random.RandomState(5)  # same cotangent both levels
    for name in grads[0]:
        onp.testing.assert_allclose(
            grads[0][name], grads[2][name], rtol=2e-4, atol=1e-5,
            err_msg=f"grad mismatch for {name}")


# ---------------------------------------------------------------------------
# per-pass targeted rewrites
# ---------------------------------------------------------------------------

def _run_single(passname, net, level=2):
    pm = build_manager(level)
    g = MutableGraph(net)
    n, findings = pm.get(passname).apply(g)
    return n, g


def test_fold_pass():
    x = sym.var("data")
    c = sym.ones((2, 3)) * 4.0 + 1.0
    net = sym.broadcast_add(x, c)
    n, g = _run_single("opt.fold", net)
    assert n == 2
    opt = g.to_symbol()
    vm = {"data": rs.uniform(-1, 1, (2, 3)).astype("float32")}
    ok, problems = parity_check(net, opt, vm, tol_class="bitwise")
    assert ok, problems
    assert any(nd2.op == "_graph_const" for nd2 in opt._topo_nodes())


def test_fold_respects_size_cap():
    from mxnet_tpu.opt import passes_basic
    x = sym.var("data")
    big = sym.ones((300, 300)) * 2.0  # 90k > 65536 cap
    net = sym.broadcast_add(x, big)
    n, g = _run_single("opt.fold", net)
    assert n == 0


def test_cse_pass():
    x = sym.var("x")
    a = sym.FullyConnected(x, num_hidden=4, name="fc")
    r1 = sym.Activation(a, act_type="relu")
    r2 = sym.Activation(a, act_type="relu")
    net = sym.broadcast_add(r1, r2)
    n, g = _run_single("opt.cse", net)
    assert n == 1
    ok, problems = parity_check(
        net, g.to_symbol(),
        random_value_map(net, {"x": (2, 6)}), tol_class="bitwise")
    assert ok, problems


def test_cse_never_merges_rng_ops():
    x = sym.var("x")
    d1 = sym.Dropout(x, p=0.5, name="d1")
    d2 = sym.Dropout(x, p=0.5, name="d2")
    net = sym.broadcast_add(d1, d2)
    n, _g = _run_single("opt.cse", net)
    assert n == 0


def test_elide_pass():
    x = sym.var("x")
    net = ((x + 0.0) * 1.0) / 1.0
    net = sym.cast(net, dtype="float32")  # unprovable input dtype: kept
    n, g = _run_single("opt.elide", net)
    assert n == 3
    ok, problems = parity_check(
        net, g.to_symbol(), {"x": rs.uniform(-1, 1, (2, 3))
                             .astype("float32")}, tol_class="bitwise")
    assert ok, problems


def test_elide_cast_with_provable_dtype():
    x = sym.var("x")
    net = sym.cast(sym.cast(x, dtype="float16"), dtype="float16")
    n, _g = _run_single("opt.elide", net)
    assert n == 1  # outer cast's input dtype is provable; inner kept


def test_dce_sweeps_orphans():
    net, shapes = mlp_fixture()
    optimized, report = optimize_symbol(net, level=1)
    by_pass = {p["pass"]: p["rewrites"] for p in report.passes}
    assert by_pass["opt.dce"] > 0
    assert report.nodes_after < report.nodes_before


def test_fusion_patterns_and_census():
    net, shapes = conv_fixture()
    _opt, report = optimize_symbol(net, level=2)
    assert report.fused_census.get("conv_bn_relu", 0) >= 1
    lm, lshapes = lm_fixture()
    _opt2, rep2 = optimize_symbol(lm, level=2)
    assert rep2.fused_census.get("attention", 0) == 1


def test_fused_group_keeps_bn_aux_updates():
    """BatchNorm moving stats must flow out of a fused group exactly
    as they do unfused (train mode updates, eval mode identity)."""
    net, shapes = conv_fixture()
    optimized, report = optimize_symbol(net, level=2)
    vm = random_value_map(net, shapes, seed=9)
    from mxnet_tpu.opt.verify import _run
    _outs, aux = _run(optimized, vm, training=True)
    assert set(aux) == set(net.list_auxiliary_states())
    for k, v in aux.items():
        assert not onp.allclose(v, vm[k]), \
            f"aux {k} was not updated in train mode"


def test_attention_fusion_is_exact_on_cpu():
    """The Pallas-unavailable fallback is the unfused composition —
    bitwise, not merely close."""
    lm, shapes = lm_fixture()
    optimized, report = optimize_symbol(lm, level=2)
    assert report.fused_census.get("attention") == 1
    vm = random_value_map(lm, shapes, seed=13)
    from mxnet_tpu.opt.verify import _run
    a, _ = _run(lm, vm, training=False)
    b, _ = _run(optimized, vm, training=False)
    for x, y in zip(a, b):
        assert onp.array_equal(onp.asarray(x), onp.asarray(y))


def test_layout_pass_counts_and_parity():
    net, shapes = conv_fixture()
    n, g = _run_single("opt.layout", net)
    assert n >= 4  # 2 convs + bns + relus + pool join the region
    opt = g.to_symbol()
    ops = [nd2.op for nd2 in opt._topo_nodes() if not nd2.is_variable]
    assert "_nhwc_conv" in ops and "_nhwc_pool" in ops
    ok, problems = parity_check(
        net, opt, random_value_map(net, shapes, seed=2),
        training=True, tol_class="layout")
    assert ok, problems


def test_layout_skips_tiny_regions():
    x = sym.var("data")
    lone = sym.Convolution(x, kernel=(3, 3), num_filter=4, name="c")
    net = sym.Flatten(lone)  # conv alone: region of 1 -> skipped
    n, _g = _run_single("opt.layout", net)
    assert n == 0


# ---------------------------------------------------------------------------
# safety rails
# ---------------------------------------------------------------------------

def test_pipeline_reverts_on_broken_pass(monkeypatch):
    from mxnet_tpu.opt import passes_basic

    def boom(self, graph):
        raise RuntimeError("injected")

    monkeypatch.setattr(passes_basic.CommonSubexpr, "apply", boom)
    net, _ = mlp_fixture()
    out, report = optimize_symbol(net, level=1)
    assert out is net  # unchanged object — the revert contract
    assert "injected" in (report.reverted or "")


def test_cse_keeps_type_distinct_params():
    """0 == 0.0 == False in python; the CSE key must not alias
    int/float-typed params (weak-type promotion differs)."""
    from mxnet_tpu.opt.rewrite import canon_params
    assert canon_params({"s": 2}) != canon_params({"s": 2.0})
    assert canon_params({"s": 0}) != canon_params({"s": False})
    assert canon_params({"s": (1,)}) != canon_params({"s": (1.0,)})


def test_mp_sgd_pallas_traced_scalars_under_jit():
    """lr/wd/rescale arrive TRACED from the eager _jk jit; the Pallas
    path must neither crash on them nor retrace when they change."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.opt.kernels import mp_sgd_mom_update_pallas
    from mxnet_tpu.ops.optimizer_ops import mp_sgd_mom_update
    w32 = jnp.asarray(rs.uniform(-1, 1, (9, 5)).astype("float32"))
    g = jnp.asarray(rs.uniform(-1, 1, (9, 5)).astype("float32"))
    m = jnp.asarray(rs.uniform(-1, 1, (9, 5)).astype("float32"))
    w16 = w32.astype(jnp.float16)

    @jax.jit
    def step(w16, g, m, w32, lr, wd, rg):
        return mp_sgd_mom_update_pallas(
            w16, g, m, w32, lr=lr, momentum=0.9, wd=wd,
            rescale_grad=rg, clip_gradient=1.0, interpret=True)

    out = step(w16, g, m, w32, jnp.float32(0.1), jnp.float32(0.01),
               jnp.float32(0.5))
    ref = mp_sgd_mom_update(w16, g, m, w32, lr=0.1, momentum=0.9,
                            wd=0.01, rescale_grad=0.5,
                            clip_gradient=1.0)
    for a, b in zip(out, ref):
        onp.testing.assert_allclose(
            onp.asarray(a, dtype="float32"),
            onp.asarray(b, dtype="float32"), rtol=1e-6, atol=1e-6)
    step(w16, g, m, w32, jnp.float32(0.2), jnp.float32(0.0),
         jnp.float32(1.0))  # scheduler tick: same compiled program
    assert step._cache_size() == 1


def test_verify_gate_catches_train_only_bug(monkeypatch):
    """A rewrite bug visible only in train mode (BN momentum changed —
    eval outputs identical, aux updates differ) must trip the
    bind-time gate and revert."""
    from mxnet_tpu.opt import passes_basic

    real_apply = passes_basic.IdentityElide.apply

    def evil_apply(self, graph):
        for node in graph.topo():
            if node.op == "BatchNorm":
                node.params["momentum"] = 0.5
        n, f = real_apply(self, graph)
        return n + 1, f  # claim a rewrite so the pipeline keeps it

    monkeypatch.setattr(passes_basic.IdentityElide, "apply",
                        evil_apply)
    config.set_flag("MXNET_GRAPH_OPT", 1)
    config.set_flag("MXNET_GRAPH_OPT_VERIFY", True)
    net, shapes = conv_fixture()
    ex = net.simple_bind(grad_req="null", **shapes)
    assert ex.opt_report.verified is False
    assert ex.opt_report.reverted is not None
    assert ex._run_symbol is ex._symbol  # reverted to the original


def test_bind_time_verify_gate():
    """MXNET_GRAPH_OPT_VERIFY runs parity on the live buffers; a clean
    pipeline passes and the report records it."""
    config.set_flag("MXNET_GRAPH_OPT", 2)
    config.set_flag("MXNET_GRAPH_OPT_VERIFY", True)
    net, shapes = conv_fixture()
    ex = net.simple_bind(grad_req="null", **shapes)
    assert ex.opt_report is not None
    assert ex.opt_report.verified is True
    assert ex.opt_report.reverted is None


def test_opt_level_resolution():
    assert opt_level(0) == 0
    assert opt_level(7) == 2       # clamped
    assert opt_level(-3) == 0
    config.set_flag("MXNET_GRAPH_OPT", 2)
    assert opt_level() == 2


# ---------------------------------------------------------------------------
# PassManager ordering (satellite: deterministic registration order)
# ---------------------------------------------------------------------------

def test_passmanager_explicit_ordering():
    class P1(Pass):
        name = "zzz"
        order = 10

        def run(self, target):
            return []

    class P2(Pass):
        name = "aaa"
        order = 20

        def run(self, target):
            return []

    class P3(Pass):
        name = "mmm"
        order = 10  # ties break by registration sequence

    pm = PassManager()
    pm.register(P2())
    pm.register(P1())
    pm.register(P3())
    # explicit keys beat both registration and alphabetical order;
    # the zzz/mmm tie at order 10 resolves by registration sequence
    assert pm.ordered_names() == ["zzz", "mmm", "aaa"]
    assert pm.names() == ["aaa", "mmm", "zzz"]  # display stays sorted
    # re-registering a name keeps its slot (pipeline rebuild stable)
    pm.register(P1())
    assert pm.ordered_names() == ["zzz", "mmm", "aaa"]
    # the override argument wins over the class attribute
    pm.register(P2(), order=5)
    assert pm.ordered_names()[0] == "aaa"


def test_rewrite_pipeline_order_is_documented_sequence():
    pm = build_manager(2)
    assert pm.ordered_names() == [
        "opt.fold", "opt.cse", "opt.elide", "opt.layout", "opt.fuse",
        "opt.dce"]
    assert build_manager(1).ordered_names() == [
        "opt.fold", "opt.cse", "opt.elide", "opt.dce"]


# ---------------------------------------------------------------------------
# Pallas kernels: fallback + interpret-mode numerics
# ---------------------------------------------------------------------------

def test_mp_sgd_pallas_fallback_matches_op():
    """On CPU the Pallas entry point must silently return the XLA
    composition's result (automatic fallback)."""
    from mxnet_tpu.opt.kernels import (mp_sgd_mom_update_pallas,
                                       pallas_kernels_active)
    assert not pallas_kernels_active()  # CPU tier-1
    import jax.numpy as jnp
    w32 = jnp.asarray(rs.uniform(-1, 1, (5, 7)).astype("float32"))
    g = jnp.asarray(rs.uniform(-1, 1, (5, 7)).astype("float32"))
    m = jnp.asarray(rs.uniform(-1, 1, (5, 7)).astype("float32"))
    w16 = w32.astype(jnp.float16)
    out = mp_sgd_mom_update_pallas(w16, g, m, w32, lr=0.1,
                                   momentum=0.9, wd=0.01,
                                   rescale_grad=0.5, clip_gradient=1.0)
    from mxnet_tpu.ops.optimizer_ops import mp_sgd_mom_update
    ref = mp_sgd_mom_update(w16, g, m, w32, lr=0.1, momentum=0.9,
                            wd=0.01, rescale_grad=0.5,
                            clip_gradient=1.0)
    for a, b in zip(out, ref):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))


def test_mp_sgd_pallas_interpret_mode():
    """The Mosaic program itself, run on the host interpreter, matches
    the XLA composition (kernel numerics, padding/unpadding)."""
    from mxnet_tpu.opt.kernels import mp_sgd_mom_update_pallas
    from mxnet_tpu.ops.optimizer_ops import mp_sgd_mom_update
    import jax.numpy as jnp
    for shape in ((3,), (17, 9), (2, 3, 5)):
        w32 = jnp.asarray(rs.uniform(-1, 1, shape).astype("float32"))
        g = jnp.asarray(rs.uniform(-1, 1, shape).astype("float32"))
        m = jnp.asarray(rs.uniform(-1, 1, shape).astype("float32"))
        w16 = w32.astype(jnp.bfloat16)
        out = mp_sgd_mom_update_pallas(
            w16, g, m, w32, lr=0.05, momentum=0.9, wd=0.001,
            rescale_grad=1.0, clip_gradient=-1.0, interpret=True)
        ref = mp_sgd_mom_update(w16, g, m, w32, lr=0.05, momentum=0.9,
                                wd=0.001, rescale_grad=1.0,
                                clip_gradient=-1.0)
        for a, b in zip(out, ref):
            onp.testing.assert_allclose(
                onp.asarray(a, dtype="float32"),
                onp.asarray(b, dtype="float32"), rtol=1e-6, atol=1e-6)
            assert a.shape == b.shape and a.dtype == b.dtype


def test_sgd_multi_precision_uses_fused_kernel():
    """The eager fp16 SGD path routes through mp_sgd_mom_update (one
    dispatch incl. cast) and still converges like the fp32 loop."""
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           multi_precision=True)
    w = nd.array(rs.uniform(-1, 1, (4, 4)).astype("float32")) \
        .astype("float16")
    g = nd.array(rs.uniform(-1, 1, (4, 4)).astype("float32")) \
        .astype("float16")
    state = opt.create_state_multi_precision(0, w)
    w32_before = state[0].asnumpy().copy()
    opt.update_multi_precision(0, w, g, state)
    assert w.dtype == onp.float16
    assert not onp.allclose(state[0].asnumpy(), w32_before)
    onp.testing.assert_allclose(
        w.asnumpy().astype("float32"),
        state[0].asnumpy().astype("float16").astype("float32"))


# ---------------------------------------------------------------------------
# StepFunction / serve integration
# ---------------------------------------------------------------------------

def _sym_step_fixture():
    x = sym.var("data")
    w = sym.var("w")
    net = sym.FullyConnected(x, w, num_hidden=4, no_bias=True,
                             name="fcx")
    net = (net + 0.0) * 1.0  # elide fodder
    return sym.LinearRegressionOutput(net, sym.var("label"),
                                      name="lro")


def test_stepfunction_symbol_mode_parity():
    """Optimized symbol-mode fused step follows the unoptimized loss
    trajectory bitwise (level 1 rewrites are bitwise-class)."""
    from mxnet_tpu.step import StepFunction
    losses = {}
    for level in (0, 1):
        config.set_flag("MXNET_GRAPH_OPT", level)
        rs_l = onp.random.RandomState(3)
        args = {"w": nd.array(rs_l.uniform(-0.3, 0.3, (4, 6))
                              .astype("float32"))}
        fused = StepFunction(
            _sym_step_fixture(), arg_dict=args,
            input_names=("data", "label"), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
        if level:
            assert fused.opt_report is not None
            assert fused.opt_report.total_rewrites > 0
        x = nd.array(rs_l.uniform(-1, 1, (2, 6)).astype("float32"))
        y = nd.array(rs_l.uniform(-1, 1, (2, 4)).astype("float32"))
        traj = [float(fused.step(x, y).asnumpy().mean())
                for _ in range(4)]
        losses[level] = (traj, args["w"].asnumpy().copy())
    assert losses[0][0] == losses[1][0], "loss trajectory diverged"
    onp.testing.assert_array_equal(losses[0][1], losses[1][1])


def test_serving_engine_reports_graph_opt():
    from mxnet_tpu.serve import ServingEngine
    from mxnet_tpu.serve.buckets import BucketLadder
    config.set_flag("MXNET_GRAPH_OPT", 2)
    net, shapes = conv_fixture()
    ex = net.simple_bind(grad_req="null", **shapes)
    for nm, a in ex.arg_dict.items():
        if nm != "data":
            a._rebind(_arr(*a.shape, lo=-0.3, hi=0.3)._data)
    eng = ServingEngine(ex, input_specs=[shapes["data"][1:]],
                        ladder=BucketLadder([1, 2]), batching=False)
    eng.warmup()
    st = eng.stats()
    assert st["graph_opt"]["level"] == 2
    assert st["graph_opt"]["rewrites"] > 0
    rc = telemetry.metrics.counter(
        "mxserve_recompile_after_warmup_total").value()
    eng.predict(rs.uniform(-1, 1, shapes["data"][1:])
                .astype("float32"))
    assert telemetry.metrics.counter(
        "mxserve_recompile_after_warmup_total").value() == rc
    eng.close()


# ---------------------------------------------------------------------------
# tools / serialization
# ---------------------------------------------------------------------------

def test_optimized_graph_json_roundtrip():
    net, shapes = conv_fixture()
    optimized, _rep = optimize_symbol(net, level=2)
    reloaded = mx.sym.load_json(optimized.tojson())
    vm = random_value_map(net, shapes, seed=21)
    ok, problems = parity_check(optimized, reloaded, vm,
                                training=True, tol_class="bitwise")
    assert ok, problems


def test_mxlint_opt_selfcheck_cli():
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
         "--opt", "--json"],
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    rep = json.loads(res.stdout)
    assert rep["summary"]["error"] == 0
    fired = [f for f in rep["findings"] if f["check"] == "fuse"]
    assert fired, "fusion never fired in the self-check"


def test_mxprof_opt_report(tmp_path):
    # counters are process-cumulative: the verify-gate test above
    # deliberately records a failure, which mxprof rightly reports as
    # an error exit — zero the slate so this test sees only its bind
    telemetry.metrics.reset_metrics()
    config.set_flag("MXNET_GRAPH_OPT", 2)
    net, shapes = conv_fixture()
    net.simple_bind(grad_req="null", **shapes)
    dump = tmp_path / "metrics.jsonl"
    telemetry.export_jsonl(str(dump))
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
         "opt", str(dump), "--json"],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    rep = json.loads(res.stdout)
    om = rep["opt_metrics"]
    assert om["graphs"] >= 1
    assert om["passes"]["fuse"]["rewrites"] >= 1
    assert om["fused"].get("conv_bn_relu", 0) >= 1


def test_report_to_dict_schema():
    net, _ = mlp_fixture()
    _opt, rep = optimize_symbol(net, level=1)
    d = rep.to_dict()
    for key in ("level", "passes", "total_rewrites",
                "tolerance_class", "fused_census", "nodes_before",
                "nodes_after", "reverted", "findings"):
        assert key in d
    json.dumps(d)  # must be JSON-serializable end to end
