"""mxshard: GSPMD sharded training (ISSUE 6).

Contracts under test (all on the conftest-forced 8-device CPU mesh):
- the sharded fused step matches the replicated StepFunction within
  float tolerance (cross-replica reduction order is the only
  difference), and BITWISE on a 1-device mesh (no collectives);
- ZeRO: per-replica optimizer-state bytes ~ 1/8 of the replicated
  baseline, measured through the plan's addressable-shard accounting
  AND the per-device telemetry gauges;
- one sharded program per signature, zero steady-state recompiles;
- data + tensor parallel compose from one axes dict
  (P("batch","model")) with no user-model changes;
- shardlint verifies the compiled HLO's sharding annotations and
  catches accidental full replication;
- checkpoints record the mesh/spec in the manifest and reshard on
  restore: an 8-device run resumes on a 4-device mesh (TrainGuard
  included) with the loss trajectory continuing within tolerance.
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.shard import P, ShardPlan, ShardedStepFunction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_net(hidden=64, out=8, in_units=32, prefix=None):
    # checkpoint restore installs parameters BY NAME: a restarting
    # process re-creates the same prefixes (the counter starts over),
    # but same-process "restarts" in tests must pin prefix= to match
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu", flatten=False,
                         in_units=in_units))
        net.add(nn.Dense(out, flatten=False, in_units=hidden))
    net.initialize(mx.initializer.Xavier())
    return net


def _data(batch=16, feat=32, out=8, seed=0):
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.uniform(-1, 1, (batch, feat)).astype("float32"))
    y = nd.array(rng.uniform(-1, 1, (batch, out)).astype("float32"))
    return x, y


def _clone_into(src_net, dst_net):
    ps, pd = (src_net._collect_params_with_prefix(),
              dst_net._collect_params_with_prefix())
    for k in ps:
        pd[k].set_data(ps[k].data())


def _trainer(net, opt="sgd", kwargs=None):
    return gluon.Trainer(net.collect_params(), opt,
                         dict(kwargs or {"learning_rate": 0.05,
                                         "momentum": 0.9}))


# ---------------------------------------------------------------------------
# parity: sharded step vs replicated StepFunction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt_name,opt_kwargs", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}),
])
def test_sharded_step_matches_replicated(opt_name, opt_kwargs):
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    net_a, net_b = _make_net(), _make_net()
    _clone_into(net_a, net_b)
    tr_a = _trainer(net_a, opt_name, opt_kwargs)
    tr_b = _trainer(net_b, opt_name, opt_kwargs)
    fused_a = tr_a.fuse_step(net_a, loss_fn)  # replicated baseline
    fused_b = tr_b.fuse_step(net_b, loss_fn, shard_plan=ShardPlan())
    assert isinstance(fused_b, ShardedStepFunction)
    assert fused_b.plan.n_devices == 8
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    for step in range(4):
        la = fused_a.step(x, y).asnumpy()
        lb = fused_b.step(x, y).asnumpy()
        onp.testing.assert_allclose(la, lb, rtol=2e-6, atol=2e-6,
                                    err_msg=f"loss @ step {step}")
    for k in pa:
        onp.testing.assert_allclose(
            pa[k].data().asnumpy(), pb[k].data().asnumpy(),
            rtol=2e-5, atol=2e-6, err_msg=f"param {k}")


def test_one_device_mesh_is_bitwise_equal():
    """On a 1-device mesh there are no collectives, so 'within
    tolerance' tightens to bitwise — the sharded compile path itself
    introduces no numeric drift."""
    import jax
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    net_a, net_b = _make_net(), _make_net()
    _clone_into(net_a, net_b)
    tr_a, tr_b = _trainer(net_a), _trainer(net_b)
    fused_a = tr_a.fuse_step(net_a, loss_fn)
    plan = ShardPlan(devices=jax.devices()[:1])
    fused_b = tr_b.fuse_step(net_b, loss_fn, shard_plan=plan)
    for _ in range(3):
        la = fused_a.step(x, y).asnumpy()
        lb = fused_b.step(x, y).asnumpy()
        assert onp.array_equal(la, lb)
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    for k in pa:
        assert onp.array_equal(pa[k].data().asnumpy(),
                               pb[k].data().asnumpy()), k


# ---------------------------------------------------------------------------
# ZeRO memory contract
# ---------------------------------------------------------------------------

def test_zero_per_replica_opt_state_is_one_eighth():
    """The acceptance number: per-replica optimizer-state bytes ~ 1/8
    of the replicated baseline on the 8-device mesh (all state dims
    here divide by 8), while replicated parameters stay full-size on
    every device."""
    x, y = _data()
    net = _make_net()
    tr = _trainer(net, "adam", {"learning_rate": 0.01})
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)
    rep = fused.memory_report()
    assert rep["devices"] == 8
    total = rep["opt_state"]["total_bytes"]
    per = rep["opt_state"]["per_replica_bytes"]
    assert total > 0
    assert per == total // 8, (per, total)
    assert rep["opt_state"]["replicated_fraction"] == 1.0
    # parameters replicate: each device holds the full set
    assert rep["params"]["per_replica_bytes"] == \
        rep["params"]["total_bytes"]
    # ... and the gauges the mxprof shard report reads agree
    g = telemetry.metrics.gauge
    assert g("shard_mesh_devices").value() == 8
    assert g("shard_opt_state_bytes_per_replica").value() == per
    assert g("shard_opt_state_bytes_total").value() == total


def test_zero_off_replicates_state():
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan(zero=False))
    fused.step(x, y)
    rep = fused.memory_report()
    assert rep["opt_state"]["per_replica_bytes"] == \
        rep["opt_state"]["total_bytes"]


def test_per_device_memory_census():
    """telemetry.memory gains per-device attribution: a ZeRO-sharded
    buffer counts 1/N per device, visible per device id."""
    from mxnet_tpu.telemetry import memory as tmem
    x, y = _data()
    net = _make_net()
    tr = _trainer(net, "adam", {"learning_rate": 0.01})
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)
    per_dev = tmem.per_device_live_bytes()
    assert len(per_dev) == 8
    assert all(v > 0 for v in per_dev.values())
    sample = tmem.sample(emit_event=False)
    assert sample["per_device"] is not None
    assert telemetry.metrics.gauge("memory_live_bytes_dev0").value() > 0


# ---------------------------------------------------------------------------
# recompile discipline
# ---------------------------------------------------------------------------

def test_zero_steady_state_recompiles():
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)  # warmup: the one compile
    rc0 = telemetry.recompile_count()
    misses0 = fused.cache_info()["misses"]
    for _ in range(3):
        fused.step(x, y)
    assert telemetry.recompile_count() == rc0
    assert fused.cache_info()["misses"] == misses0
    assert len(fused._cache) == 1
    # a new global batch (still divisible) is exactly one new program
    x2, y2 = _data(batch=32)
    fused.step(x2, y2)
    fused.step(x2, y2)
    assert fused.cache_info()["misses"] == misses0 + 1
    assert len(fused._cache) == 2


def test_sharded_step_takes_its_rates_as_one_replicated_array(
        host_array_calls):
    """The per-step rates and weight decays enter the sharded program
    as ONE replicated f32 ``(2, leaves)`` array made on the host, and a
    net with twice the leaves makes no more device arrays on the host
    path of a warm step."""
    x, y = _data()
    made = []
    for layers in (2, 4):
        net = nn.HybridSequential(prefix=f"deep{layers}_")
        with net.name_scope():
            for _ in range(layers):
                net.add(nn.Dense(8, flatten=False))
        net.initialize(mx.initializer.Xavier())
        net(x)
        tr = _trainer(net, "adam", {"learning_rate": 0.01, "wd": 0.1})
        fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                             shard_plan=ShardPlan())
        fused.step(x, y)
        fused.step(x, y)
        made.append(host_array_calls(lambda: fused.step(x, y)))
        hyper = fused._hyper()
        assert type(hyper) is onp.ndarray
        assert hyper.shape == (2, 2 * layers)
        assert hyper.dtype == onp.float32
    # the two batch inputs are placed on the mesh, nothing a leaf
    assert made[1] <= made[0] <= 4
    args = fused.compiled(x, y).input_shardings[0]
    assert len(args) == 5
    assert args[2].is_fully_replicated
    assert len(args[2].device_set) == fused.plan.n_devices


# ---------------------------------------------------------------------------
# DP x TP composition
# ---------------------------------------------------------------------------

def test_dp_tp_composition_matches_replicated():
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    net_a, net_b = _make_net(), _make_net()
    _clone_into(net_a, net_b)
    tr_a, tr_b = _trainer(net_a), _trainer(net_b)
    fused_a = tr_a.fuse_step(net_a, loss_fn)
    plan = ShardPlan(axes={"batch": -1, "model": 2},
                     param_specs={"0.weight": P("model")})
    assert plan.axes == {"batch": 4, "model": 2}
    fused_b = tr_b.fuse_step(net_b, loss_fn, shard_plan=plan)
    for _ in range(3):
        la = fused_a.step(x, y).asnumpy()
        lb = fused_b.step(x, y).asnumpy()
        onp.testing.assert_allclose(la, lb, rtol=2e-6, atol=2e-6)
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    for k in pa:
        onp.testing.assert_allclose(
            pa[k].data().asnumpy(), pb[k].data().asnumpy(),
            rtol=2e-5, atol=2e-6, err_msg=f"param {k}")


def test_zero_composes_with_tensor_parallel_spec():
    """A model-sharded weight's optimizer state inherits the tensor
    sharding AND ZeRO-shards its free dim 0: P('batch', 'model')
    without anyone writing it."""
    plan = ShardPlan(axes={"batch": -1, "model": 2},
                     param_specs={"0.weight": P(None, "model")})
    w = onp.zeros((64, 32), "float32")
    spec = plan.state_spec("0.weight", w).spec
    assert tuple(spec) == ("batch", "model")
    # dim 0 already taken by the param spec: no double-sharding
    plan2 = ShardPlan(axes={"batch": -1, "model": 2},
                      param_specs={"0.weight2": P("model")})
    spec2 = plan2.state_spec("0.weight2", w).spec
    assert tuple(spec2) == ("model",)


def test_plan_validates_divisibility():
    plan = ShardPlan(axes={"batch": -1, "model": 2},
                     param_specs={"0.weight": P("model")})
    with pytest.raises(mx.MXNetError, match="does not divide"):
        plan.param_spec("0.weight", onp.zeros((7, 4), "float32"))


def test_global_batch_must_divide():
    x, y = _data(batch=12)  # 12 % 8 != 0
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    with pytest.raises(mx.MXNetError, match="does not divide"):
        fused.step(x, y)


# ---------------------------------------------------------------------------
# MXSHARD_AUTO / from_env
# ---------------------------------------------------------------------------

def test_mxshard_auto_flag_selects_sharded_step():
    from mxnet_tpu.step import StepFunction
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    config.set_flag("MXSHARD_AUTO", True)
    try:
        fused = tr.fuse_step(net, gluon.loss.L2Loss())
        assert isinstance(fused, ShardedStepFunction)
        assert fused.plan.n_devices == 8
        assert tr._shard_plan is fused.plan
    finally:
        config.unset_flag("MXSHARD_AUTO")
    tr2 = _trainer(_make_net())
    fused2 = tr2.fuse_step(net, gluon.loss.L2Loss())
    assert not isinstance(fused2, ShardedStepFunction)
    assert isinstance(fused2, StepFunction)


def test_shard_plan_from_env():
    config.set_flag("MXSHARD_AXES", "batch:4,model:2")
    try:
        plan = ShardPlan.from_env()
        assert plan.axes == {"batch": 4, "model": 2}
        assert plan.batch_axis == "batch"
    finally:
        config.unset_flag("MXSHARD_AXES")
    config.set_flag("MXSHARD_AXES", "batch:oops")
    try:
        with pytest.raises(mx.MXNetError, match="MXSHARD_AXES"):
            ShardPlan.from_env()
    finally:
        config.unset_flag("MXSHARD_AXES")


# ---------------------------------------------------------------------------
# shardlint
# ---------------------------------------------------------------------------

def test_shardlint_clean_on_good_step():
    from mxnet_tpu.passes.shardlint import lint_shard_report
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)
    report = fused.shard_report(x, y)
    findings = lint_shard_report(report)
    assert all(f.severity == "info" for f in findings), findings
    checks = {f.check for f in findings}
    assert "collectives" in checks
    # the gradient exchange is visible in the compiled HLO
    from mxnet_tpu.parallel.hlo_check import collective_report
    infos = collective_report(report["hlo"], report["mesh"])
    assert any(ci.op == "all-reduce" and ci.axes == {"batch"}
               for ci in infos)
    # ... and the data inputs really compiled batch-sharded (the
    # data-parallel annotation itself, not just its collectives)
    assert len(report["data_shardings"]) == 2  # x and y
    for got in report["data_shardings"]:
        assert not got.is_fully_replicated, got


def test_shardlint_catches_accidental_replication():
    """Replace the compiled state shardings with replicated ones — the
    pass must flag both the mismatch and the ZeRO contract breach."""
    import jax
    from mxnet_tpu.passes.shardlint import lint_shard_report
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)
    report = dict(fused.shard_report(x, y))
    rep = fused.plan.replicated()
    report["output_shardings"] = (
        report["output_shardings"][0],
        jax.tree.map(lambda _: rep, report["sspec"]),
        None)
    findings = lint_shard_report(report)
    checks = {f.check for f in findings if f.severity == "error"}
    assert "sharding-mismatch" in checks
    assert "zero-not-applied" in checks


def test_shardlint_catches_replicated_data_input():
    """A dropped inputs in_shardings entry (every replica computing
    the full global batch) is invisible to parity tests and to
    batch-axis collective counts — the pass must catch it from the
    compiled input shardings."""
    from mxnet_tpu.passes.shardlint import lint_shard_report
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)
    report = dict(fused.shard_report(x, y))
    rep = fused.plan.replicated()
    report["data_shardings"] = tuple(
        rep for _ in report["data_shardings"])
    findings = lint_shard_report(report)
    assert any(f.check == "data-input-replicated"
               and f.severity == "error" for f in findings), findings


def test_shardlint_registered_in_default_manager():
    from mxnet_tpu.passes import default_manager
    pm = default_manager()
    assert "shardlint" in pm.names()
    assert pm.get("shardlint").run(None) == []


# ---------------------------------------------------------------------------
# resharding checkpoints (8 -> 4 devices)
# ---------------------------------------------------------------------------

def _losses(fused, batches):
    return [float(fused.step(x, y).asnumpy().mean())
            for x, y in batches]


def test_manifest_records_plan_and_from_manifest_rebuilds(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager
    x, y = _data()
    net = _make_net()
    tr = _trainer(net)
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan())
    fused.step(x, y)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, trainer=tr)
    with open(os.path.join(str(tmp_path), "step_1",
                           "manifest.json")) as f:
        manifest = json.load(f)
    shard = manifest["shard"]
    assert shard["n_devices"] == 8
    assert shard["zero"] is True
    assert dict(shard["axes"]) == {"batch": 8}
    # rebuild on fewer devices: the batch axis re-infers
    import jax
    plan4 = ShardPlan.from_manifest(shard, devices=jax.devices()[:4])
    assert plan4.n_devices == 4
    assert plan4.axes == {"batch": 4}
    assert plan4.zero is True


def test_reshard_restore_8_to_4_continues_trajectory(tmp_path):
    """Train on an 8-device mesh, checkpoint, restore onto a 4-device
    mesh, continue: the loss trajectory matches an uninterrupted run
    within tolerance, and the reshard is counted."""
    from mxnet_tpu.checkpoint import CheckpointManager
    import jax
    loss_fn = gluon.loss.L2Loss()
    batches = [_data(seed=s) for s in range(6)]

    # every run starts from the same weight snapshot; one pinned
    # prefix = identical param names, as a real restart would have
    net0 = _make_net(prefix="reshard_")
    snap = {k: p.data().asnumpy()
            for k, p in net0._collect_params_with_prefix().items()}

    def fresh_net():
        n = _make_net(prefix="reshard_")
        pp = n._collect_params_with_prefix()
        for k, v in snap.items():
            pp[k].set_data(nd.array(v))
        return n

    # uninterrupted reference run on 8 devices
    net_r = fresh_net()
    tr_r = _trainer(net_r)
    fused_r = tr_r.fuse_step(net_r, loss_fn, shard_plan=ShardPlan())
    ref_losses = _losses(fused_r, batches)

    # interrupted run: 3 steps on 8 devices, checkpoint
    net_i = fresh_net()
    tr_i = _trainer(net_i)
    fused_i = tr_i.fuse_step(net_i, loss_fn, shard_plan=ShardPlan())
    part_losses = _losses(fused_i, batches[:3])
    onp.testing.assert_allclose(part_losses, ref_losses[:3],
                                rtol=1e-6)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, trainer=tr_i)

    # "restart" on HALF the devices
    rc0 = telemetry.metrics.counter(
        "shard_reshard_restores_total").value()
    net_c = fresh_net()
    tr_c = _trainer(net_c)
    plan4 = ShardPlan(devices=jax.devices()[:4])
    fused_c = tr_c.fuse_step(net_c, loss_fn, shard_plan=plan4)
    step = mgr.restore_latest(trainer=tr_c)
    assert step == 3
    assert telemetry.metrics.counter(
        "shard_reshard_restores_total").value() == rc0 + 1
    cont_losses = _losses(fused_c, batches[3:])
    onp.testing.assert_allclose(cont_losses, ref_losses[3:],
                                rtol=5e-5, atol=1e-6)
    rep = fused_c.memory_report()
    assert rep["devices"] == 4
    assert rep["opt_state"]["per_replica_bytes"] == \
        rep["opt_state"]["total_bytes"] // 4


def test_trainguard_preempt_resumes_on_smaller_mesh(tmp_path):
    """mxresil integration: a preempted sharded job's emergency
    checkpoint restores through TrainGuard onto a smaller mesh with
    the post-update weights intact."""
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.resil import Preempted, TrainGuard
    import jax
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    net = _make_net(prefix="guarded_")
    tr = _trainer(net)
    fused = tr.fuse_step(net, loss_fn, shard_plan=ShardPlan())
    params = net._collect_params_with_prefix()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    seen = {}
    with pytest.raises(Preempted):
        with TrainGuard(mgr, trainer=tr, checkpoint_every=100,
                        install_signals=False) as guard:
            for step in range(guard.resume(), 10):
                fused.step(x, y)
                seen[step] = {k: p.data().asnumpy()
                              for k, p in params.items()}
                if step == 2:
                    guard.request_preempt()
                guard.completed(step, loss=1.0)
    # resume on a 4-device mesh in a "new process"
    net2 = _make_net(prefix="guarded_")
    tr2 = _trainer(net2)
    fused2 = tr2.fuse_step(net2, loss_fn,
                           shard_plan=ShardPlan(
                               devices=jax.devices()[:4]))
    mgr2 = CheckpointManager(str(tmp_path))
    with TrainGuard(mgr2, trainer=tr2, checkpoint_every=100,
                    install_signals=False) as guard2:
        assert guard2.resume() == 3
    p2 = net2._collect_params_with_prefix()
    for k in p2:
        assert onp.array_equal(p2[k].data().asnumpy(), seen[2][k]), k
    fused2.step(x, y)  # and training continues on the smaller mesh


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------

def test_mxprof_shard_report(tmp_path):
    sink = str(tmp_path / "metrics.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_METRICS_EXPORT=sink)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    code = (
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import gluon, nd\n"
        "from mxnet_tpu.gluon import nn\n"
        "from mxnet_tpu.shard import ShardPlan\n"
        "net = nn.HybridSequential()\n"
        "with net.name_scope():\n"
        "    net.add(nn.Dense(64, flatten=False, in_units=32))\n"
        "net.initialize()\n"
        "x = nd.array(onp.ones((16, 32), 'float32'))\n"
        "y = nd.array(onp.ones((16, 64), 'float32'))\n"
        "tr = gluon.Trainer(net.collect_params(), 'adam',"
        " {'learning_rate': 0.01})\n"
        "fused = tr.fuse_step(net, gluon.loss.L2Loss(),"
        " shard_plan=ShardPlan())\n"
        "for _ in range(3):\n"
        "    fused.step(x, y)\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
         "shard", sink], env=env, capture_output=True, text=True,
        timeout=300)
    assert r2.returncode == 0, r2.stderr[-800:]
    assert "mesh devices: 8" in r2.stdout
    assert "optimizer state" in r2.stdout
    assert "fully sharded" in r2.stdout
    r3 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
         "shard", sink, "--json"], env=env, capture_output=True,
        text=True, timeout=300)
    assert r3.returncode == 0, r3.stderr[-800:]
    doc = json.loads(r3.stdout)
    assert doc["tool"] == "mxprof"
    sm = doc["shard_metrics"]
    assert sm["devices"] == 8
    assert sm["opt_state"]["replicated_fraction"] == 1.0
    assert len(sm["per_device_live"]) == 8


def test_mxlint_shard_selfcheck():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
         "--shard"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    assert "shardlint" in r.stdout
    assert "0 error(s), 0 warning(s)" in r.stdout
