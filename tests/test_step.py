"""mxstep: the fused whole-train-step compiler (ISSUE 5).

Contracts under test:
- the fused step (one donated XLA computation: forward + backward +
  exchange + optimizer) is BITWISE-equal to the eager per-param loop
  for SGD/Adam/AdamW over several steps, momentum/weight-decay state
  included — where XLA compiles an op the same way inside one program
  and alone, which on jax 0.9's XLA:CPU needs its dot fusions off
  (tests/conftest.py sets --xla_cpu_experimental_ynn_fusion_type=);
- steady-state shapes never recompile (tier-1 smoke: >=2 post-warmup
  steps with zero recompiles);
- donation safety: old weight buffers are not aliased into the new
  step, and the gluon Parameters stay usable (eager forward, second
  trainer) after fused steps;
- mxresil compatibility: preemption at a step boundary checkpoints the
  post-update weights;
- the aggregated eager update honors MXNET_OPTIMIZER_AGGREGATION_SIZE
  and matches the scalar loop bitwise;
- Trainer._allreduce_grads coalesces dense grads into size-capped flat
  buckets (O(buckets) kvstore round trips) without changing results.
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, config, gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.step import GradientBuckets, StepFunction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_net(hidden=16, out=4):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu", flatten=False))
        net.add(nn.Dense(out, flatten=False))
    net.initialize(mx.initializer.Xavier())
    return net


def _data(batch=8, feat=10, out=4, seed=0):
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.uniform(-1, 1, (batch, feat)).astype("float32"))
    y = nd.array(rng.uniform(-1, 1, (batch, out)).astype("float32"))
    return x, y


def _clone_into(src_net, dst_net):
    ps, pd = (src_net._collect_params_with_prefix(),
              dst_net._collect_params_with_prefix())
    for k in ps:
        pd[k].set_data(ps[k].data())


def _state_leaves(updater):
    import jax
    out = []
    for i in sorted(updater.states):
        leaves = jax.tree.leaves(jax.tree.map(
            lambda v: onp.asarray(v._data), updater.states[i],
            is_leaf=lambda v: hasattr(v, "_data")))
        out.append(leaves)
    return out


# ---------------------------------------------------------------------------
# bitwise parity: fused step vs eager per-param loop
# ---------------------------------------------------------------------------

def _parity_run(tr_a, tr_b, net_a, net_b, x, y, steps=4):
    """Eager loop on ``net_a`` against the fused step on ``net_b``:
    losses, parameters and optimizer state bitwise equal after every
    step, dtypes unchanged. Returns the fused step."""
    loss_fn = gluon.loss.L2Loss()
    fused = tr_b.fuse_step(net_b, loss_fn)
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    dtypes = {k: p.data().dtype for k, p in pb.items()}
    for step in range(steps):
        with autograd.record():
            loss_a = loss_fn(net_a(x), y)
        loss_a.backward()
        tr_a.step(x.shape[0])
        loss_b = fused.step(x, y)
        assert onp.array_equal(loss_a.asnumpy(), loss_b.asnumpy()), \
            f"loss diverged at step {step}"
        for k in pa:
            assert pb[k].data().dtype == dtypes[k], k
            assert onp.array_equal(pa[k].data().asnumpy(),
                                   pb[k].data().asnumpy()), \
                f"param {k} diverged at step {step}"
    leaves_a = _state_leaves(tr_a._updaters[0])
    leaves_b = _state_leaves(tr_b._updaters[0])
    assert len(leaves_a) == len(leaves_b) > 0
    for sa, sb in zip(leaves_a, leaves_b):
        for a, b in zip(sa, sb):
            assert a.dtype == b.dtype
            assert onp.array_equal(a, b), "optimizer state diverged"
    return fused


def _net_pair(dtype):
    """Two nets with the same weights, cast to ``dtype``, and a batch
    of that dtype."""
    x, y = _data()
    net_a, net_b = _make_net(), _make_net()
    net_a(x), net_b(x)
    _clone_into(net_a, net_b)
    net_a.cast(dtype), net_b.cast(dtype)
    return net_a, net_b, x.astype(dtype), y.astype(dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("opt_name,opt_kwargs", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("sgd", {"learning_rate": 0.05}),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}),
    ("adamw", {"learning_rate": 0.01, "wd": 0.01}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9}),
])
def test_fused_step_bitwise_equals_eager(opt_name, opt_kwargs, dtype):
    """The acceptance contract: >=3 steps, params AND optimizer state
    bitwise-equal between the fused step and the eager loop. In
    bfloat16 (the ResNet cell's policy: parameters, gradients and state)
    it holds because each leaf's rate and weight decay, which reach the
    trace in one f32 array, are cast to the leaf's dtype: ``lr * g``
    then rounds as the eager kernels' weak python float does, and the
    state keeps its dtype (an f32 scalar would promote it)."""
    net_a, net_b, x, y = _net_pair(dtype)
    tr_a = gluon.Trainer(net_a.collect_params(), opt_name,
                         dict(opt_kwargs))
    tr_b = gluon.Trainer(net_b.collect_params(), opt_name,
                         dict(opt_kwargs))
    _parity_run(tr_a, tr_b, net_a, net_b, x, y)
    for leaves in _state_leaves(tr_b._updaters[0]):
        assert all(str(v.dtype) == dtype for v in leaves)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_step_scheduler_and_multipliers_bitwise_no_retrace(dtype):
    """A rate that changes every step and per-parameter lr_mult /
    wd_mult: every leaf's own pair reaches its update (bitwise equal to
    the eager loop), and the values never retrace."""
    net_a, net_b, x, y = _net_pair(dtype)
    trainers = []
    for net in (net_a, net_b):
        for k, (_, p) in enumerate(
                sorted(net._collect_params_with_prefix().items())):
            p.lr_mult = 1.0 + 0.37 * k
            p.wd_mult = 0.5 * k
        sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.83)
        trainers.append(gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": 0.07, "momentum": 0.9, "wd": 0.013,
             "lr_scheduler": sched}))
    misses0 = telemetry.metrics.counter(
        "fused_step_cache_misses_total").value()
    fused = _parity_run(*trainers, net_a, net_b, x, y)
    assert fused.cache_info()["misses"] == misses0 + 1
    rates = fused._hyper()[0]
    assert len(set(rates.tolist())) == len(rates)  # a rate a leaf


def test_fused_step_standalone_optimizer():
    """StepFunction without a trainer owns its Updater; training
    reduces the loss."""
    x, y = _data()
    net = _make_net()
    net(x)
    fused = StepFunction(net, gluon.loss.L2Loss(), optimizer="adam",
                         optimizer_params={"learning_rate": 0.01})
    first = float(fused.step(x, y).asnumpy().mean())
    for _ in range(10):
        last = float(fused.step(x, y).asnumpy().mean())
    assert last < first
    assert fused._updater.states  # state lives in the owned Updater


# ---------------------------------------------------------------------------
# recompile discipline (tier-1 smoke for the bench contract)
# ---------------------------------------------------------------------------

def test_zero_recompiles_on_steady_state_shapes():
    """>=2 post-warmup steps with ZERO recompiles; a new batch shape
    costs exactly one more compile."""
    x, y = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    fused = tr.fuse_step(net, gluon.loss.L2Loss())
    fused.step(x, y)  # warmup: the one compile
    rc0 = telemetry.recompile_count()
    misses0 = fused.cache_info()["misses"]
    for _ in range(3):
        fused.step(x, y)
    assert telemetry.recompile_count() == rc0, \
        "steady-state fused steps recompiled"
    info = fused.cache_info()
    assert info["misses"] == misses0
    assert info["programs"] == 1
    # a different batch size is one (and only one) new program
    x2, y2 = _data(batch=4)
    fused.step(x2, y2)
    fused.step(x2, y2)
    assert fused.cache_info()["misses"] == misses0 + 1
    assert fused._cache and len(fused._cache) == 2
    # misses are classified by the recompile auditor as fused_step
    kinds = {r["kind"] for r in telemetry.recompile_report()}
    assert "fused_step" in kinds


def test_fused_step_scalar_changes_do_not_recompile():
    """lr travels as a traced scalar: a scheduler-style change between
    steps must not add a compile."""
    x, y = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    fused = tr.fuse_step(net, gluon.loss.L2Loss())
    fused.step(x, y)
    misses0 = fused.cache_info()["misses"]
    tr.set_learning_rate(0.01)
    fused.step(x, y)
    tr.set_learning_rate(0.002)
    fused.step(x, y)
    assert fused.cache_info()["misses"] == misses0


# ---------------------------------------------------------------------------
# the per-step rates: one host array, no device array a leaf
# ---------------------------------------------------------------------------

def _deep_net(layers):
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(layers):
            net.add(nn.Dense(8, flatten=False))
    net.initialize(mx.initializer.Xavier())
    return net


def test_hyper_is_one_host_array():
    x, y = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01, "wd": 0.25})
    fused = tr.fuse_step(net, gluon.loss.L2Loss())
    fused.step(x, y)
    counts = dict(fused._optimizer._index_update_count)
    hyper = fused._hyper()
    assert type(hyper) is onp.ndarray
    assert hyper.shape == (2, 4) and hyper.dtype == onp.float32
    # it still advances every leaf's update count, as the eager loop
    for i in fused._indices:
        assert fused._optimizer._index_update_count[i] == counts[i] + 1
    # row 0 the rates (Adam's bias correction folded in, so not the
    # base rate), row 1 the weight decays
    assert onp.all(hyper[0] != onp.float32(0.01)) and onp.all(hyper[0] > 0)
    assert onp.array_equal(hyper[1], onp.full(4, 0.25, onp.float32))


def test_warm_step_makes_no_device_array_a_leaf(host_array_calls):
    """Twice the trainable leaves, the same host path: nothing in
    step() makes a device array (a program, on the chip) a leaf."""
    x, y = _data(out=8)
    made = []
    for layers in (2, 4):
        net = _deep_net(layers)
        net(x)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        fused = tr.fuse_step(net, gluon.loss.L2Loss())
        fused.step(x, y)
        fused.step(x, y)
        made.append(host_array_calls(lambda: fused.step(x, y)))
        assert len(fused._indices) == 2 * layers
    assert made[1] <= made[0] <= 2


def test_compiled_lowers_the_signature_that_ran(tmp_path):
    """``compiled()`` hands out the program that ran: with a
    persistent cache on, lowering it compiles no second program, and
    its arguments are the step's five with ONE array for the rates
    (subprocess: jax's cache configuration is process-global)."""
    cache_dir = str(tmp_path / "xla_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (
        "import jax, numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import gluon, nd\n"
        "from mxnet_tpu.gluon import nn\n"
        "from mxnet_tpu.step.cache import enable_compile_cache\n"
        "from mxnet_tpu.telemetry import metrics\n"
        "assert enable_compile_cache(%r, min_compile_time_secs=0.0)\n"
        "net = nn.HybridSequential()\n"
        "net.add(nn.Dense(16, activation='relu'), nn.Dense(4))\n"
        "net.initialize()\n"
        "x, y = nd.ones((8, 10)), nd.ones((8, 4))\n"
        "net(x)\n"
        "tr = gluon.Trainer(net.collect_params(), 'sgd',\n"
        "                   {'learning_rate': 0.05, 'momentum': 0.9})\n"
        "fused = tr.fuse_step(net, gluon.loss.L2Loss())\n"
        # twice: a step's outputs are committed to their device, which
        # fresh parameters are not, and jax keys a program on that
        "fused.step(x, y).asnumpy()\n"
        "fused.step(x, y).asnumpy()\n"
        "miss = metrics.counter('jax_compile_cache_misses_total')\n"
        "m0 = miss.value()\n"
        "assert m0 >= 1\n"
        "c = fused.compiled(x, y)\n"
        "assert miss.value() == m0, (m0, miss.value())\n"
        "args = c.input_shardings[0]\n"
        "assert len(args) == 5\n"
        "assert len(jax.tree.leaves(args[2])) == 1\n"
        "hyper = c.in_avals[0][2]\n"
        "assert hyper.shape == (2, 4) and str(hyper.dtype) == 'float32'\n"
        % cache_dir)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

def test_donation_safety_old_buffers_not_reused():
    """Post-step, parameters are REBOUND to fresh buffers (never
    mutated in place), and the block stays fully usable eagerly."""
    x, y = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    fused = tr.fuse_step(net, gluon.loss.L2Loss())
    params = net._collect_params_with_prefix()
    nd_objs = {k: p.data() for k, p in params.items()}
    old_raw = {k: p.data()._data for k, p in params.items()}
    old_copy = {k: p.data().asnumpy() for k, p in params.items()}
    fused.step(x, y)
    for k, p in params.items():
        # same NDArray object (trainer/checkpoint references survive)
        assert p.data() is nd_objs[k]
        # ... rebound to a NEW buffer (no in-place mutation of the old)
        assert p.data()._data is not old_raw[k]
        assert not onp.array_equal(p.data().asnumpy(), old_copy[k])
    # on CPU donation is off: the old buffers must be untouched
    for k in params:
        assert onp.array_equal(onp.asarray(old_raw[k]), old_copy[k])
    # the block still runs eagerly (no deleted/donated buffer leaks)
    out = net(x)
    assert onp.isfinite(out.asnumpy()).all()
    # and a second fused step still works
    fused.step(x, y)


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_fused_step_refuses_non_fused_optimizer():
    x, _ = _data()
    net = _make_net()
    net(x)
    with pytest.raises(mx.MXNetError, match="fused_apply"):
        StepFunction(net, gluon.loss.L2Loss(), optimizer="adagrad")


def test_fused_step_refuses_update_on_kvstore():
    x, _ = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1},
                       kvstore=mx.kv.create("local"),
                       update_on_kvstore=True)
    with pytest.raises(mx.MXNetError, match="update_on_kvstore"):
        tr.fuse_step(net, gluon.loss.L2Loss())


# ---------------------------------------------------------------------------
# mxresil compatibility
# ---------------------------------------------------------------------------

def test_preempt_at_step_boundary_checkpoints_post_update_weights(
        tmp_path):
    """A preemption observed at the fused-step boundary commits an
    emergency checkpoint holding the POST-update weights (the fused
    write-back happened before the boundary)."""
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.resil import Preempted, TrainGuard
    x, y = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    fused = tr.fuse_step(net, gluon.loss.L2Loss())
    params = net._collect_params_with_prefix()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    seen = {}
    with pytest.raises(Preempted) as exc:
        with TrainGuard(mgr, trainer=tr, checkpoint_every=100,
                        install_signals=False) as guard:
            for step in range(guard.resume(), 10):
                fused.step(x, y)
                seen[step] = {k: p.data().asnumpy()
                              for k, p in params.items()}
                if step == 2:
                    guard.request_preempt()
                guard.completed(step, loss=1.0)
    assert exc.value.step == 2
    # "restart": wipe the weights, then restore the emergency
    # checkpoint into the trainer — it must hold the POST-update state
    # of the last completed step
    for p in params.values():
        p.set_data(nd.zeros(p.shape))
    mgr2 = CheckpointManager(str(tmp_path))
    step = mgr2.latest_step()
    _, _, extra = mgr2.restore(step, trainer=tr)
    assert extra["emergency"] is True and extra["next_step"] == 3
    for k, p in params.items():
        assert onp.array_equal(p.data().asnumpy(), seen[2][k]), \
            f"restored {k} != post-update weights of step 2"


# ---------------------------------------------------------------------------
# aggregated eager update (MXNET_OPTIMIZER_AGGREGATION_SIZE)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("agg", [1, 2, 45])
def test_aggregated_update_matches_scalar_bitwise(agg):
    config.set_flag("MXNET_OPTIMIZER_AGGREGATION_SIZE", agg)
    try:
        x, y = _data()
        loss_fn = gluon.loss.L2Loss()
        net_a, net_b = _make_net(), _make_net()
        net_a(x), net_b(x)
        _clone_into(net_a, net_b)
        tr_a = gluon.Trainer(net_a.collect_params(), "adam",
                             {"learning_rate": 0.01, "wd": 0.001})
        tr_b = gluon.Trainer(net_b.collect_params(), "adam",
                             {"learning_rate": 0.01, "wd": 0.001})
        tr_b._updaters[0].aggregate_updates = False  # scalar loop
        for _ in range(3):
            for net, tr in ((net_a, tr_a), (net_b, tr_b)):
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                tr.step(x.shape[0])
        pa = net_a._collect_params_with_prefix()
        pb = net_b._collect_params_with_prefix()
        for k in pa:
            assert onp.array_equal(pa[k].data().asnumpy(),
                                   pb[k].data().asnumpy())
    finally:
        config.unset_flag("MXNET_OPTIMIZER_AGGREGATION_SIZE")


# ---------------------------------------------------------------------------
# bucketed gradient exchange
# ---------------------------------------------------------------------------

def test_bucketed_allreduce_matches_no_kvstore():
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    net_a, net_b = _make_net(), _make_net()
    net_a(x), net_b(x)
    _clone_into(net_a, net_b)
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9})
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9},
                         kvstore=mx.kv.create("local"),
                         update_on_kvstore=False)
    for _ in range(3):
        for net, tr in ((net_a, tr_a), (net_b, tr_b)):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(x.shape[0])
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    for k in pa:
        assert onp.array_equal(pa[k].data().asnumpy(),
                               pb[k].data().asnumpy())
    buckets, leftover, _sig = tr_b._grad_buckets
    assert len(buckets) >= 1 and not leftover
    assert telemetry.metrics.gauge("grad_bucket_count").value() >= 1


def test_bucket_assignment_rebuilt_after_cast():
    """Parameter.cast mid-run (amp fine-tuning) must rebuild the
    bucket layout — a stale assignment would concat mixed dtypes."""
    x, y = _data()
    net = _make_net()
    net(x)
    loss_fn = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01},
                       kvstore=mx.kv.create("local"),
                       update_on_kvstore=False)
    with autograd.record():
        loss_fn(net(x), y).backward()
    tr.step(x.shape[0])
    sig_before = tr._grad_buckets[2]
    for p in net.collect_params().values():
        p.cast("bfloat16")
    x16 = nd.array(x._data.astype("bfloat16"))
    with autograd.record():
        loss_fn(net(x16), y).backward()
    tr.step(x.shape[0])
    assert tr._grad_buckets[2] != sig_before
    for b in tr._grad_buckets[0].buckets:
        assert str(b.dtype) == "bfloat16"
    for p in net.collect_params().values():
        assert str(p.data().dtype) == "bfloat16"  # no dtype drift


def _tied_net():
    """Two Dense layers over ONE weight and bias (``params=``)."""
    net = nn.HybridSequential()
    with net.name_scope():
        d1 = nn.Dense(10, activation="tanh", flatten=False, in_units=10)
        net.add(d1)
        net.add(nn.Dense(10, flatten=False, in_units=10,
                         params=d1.params))
    net.initialize(mx.initializer.Xavier())
    return net


@pytest.mark.parametrize("opt_name,opt_kwargs", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01}),
])
def test_fused_step_ties_shared_parameters(opt_name, opt_kwargs):
    """A Parameter shared between blocks (``params=``) is ONE leaf of
    the fused step: its gradient the sum over its uses, one update, its
    update count advanced once a step; bitwise the eager loop's."""
    x, _ = _data(feat=10)
    y = nd.array(onp.random.RandomState(1).uniform(
        -1, 1, (x.shape[0], 10)).astype("float32"))
    net_a, net_b = _tied_net(), _tied_net()
    net_a(x), net_b(x)
    _clone_into(net_a, net_b)
    names = net_b._collect_params_with_prefix()
    assert names["0.weight"] is names["1.weight"]
    tr_a = gluon.Trainer(net_a.collect_params(), opt_name, dict(opt_kwargs))
    tr_b = gluon.Trainer(net_b.collect_params(), opt_name, dict(opt_kwargs))
    loss_fn = gluon.loss.L2Loss()
    fused = tr_b.fuse_step(net_b, loss_fn)
    pa = net_a._collect_params_with_prefix()
    for step in range(3):
        with autograd.record():
            loss_a = loss_fn(net_a(x), y)
        loss_a.backward()
        tr_a.step(x.shape[0])
        loss_b = fused.step(x, y)
        # equal to rounding, not to the bit: one program adds the two
        # uses' gradients inside its reductions, the tape after them
        assert onp.allclose(loss_a.asnumpy(), loss_b.asnumpy(),
                            rtol=1e-6, atol=0), step
        for k in pa:
            assert onp.allclose(pa[k].data().asnumpy(),
                                names[k].data().asnumpy(),
                                rtol=1e-5, atol=1e-7), (k, step)
    for sa, sb in zip(_state_leaves(tr_a._updaters[0]),
                      _state_leaves(tr_b._updaters[0])):
        for a, b in zip(sa, sb):
            assert onp.allclose(a, b, rtol=1e-5, atol=1e-7)
    assert fused._trainable == ("0.bias", "0.weight")
    index = tr_b._param2idx[names["0.weight"].name]
    assert tr_b._optimizer._index_update_count[index] == 3
    assert tr_b._optimizer._index_update_count == \
        tr_a._optimizer._index_update_count
    # the gradient held both uses: a net whose second layer has a
    # weight of its own, started from the same values, moves otherwise
    untied = nn.HybridSequential()
    with untied.name_scope():
        untied.add(nn.Dense(10, activation="tanh", flatten=False,
                            in_units=10))
        untied.add(nn.Dense(10, flatten=False, in_units=10))
    untied.initialize()
    untied(x)
    start = _tied_net()
    start(x)
    _clone_into(net_a, start)  # any tied values: only equality matters
    for k, p in untied._collect_params_with_prefix().items():
        p.set_data(start._collect_params_with_prefix()[k].data())
    tied_step = StepFunction(start, gluon.loss.L2Loss(), optimizer="sgd")
    loose_step = StepFunction(untied, gluon.loss.L2Loss(), optimizer="sgd")
    assert onp.array_equal(tied_step.step(x, y).asnumpy(),
                           loose_step.step(x, y).asnumpy())
    w_tied = start._collect_params_with_prefix()["0.weight"].data()
    w_loose = untied._collect_params_with_prefix()
    assert not onp.array_equal(w_tied.asnumpy(),
                               w_loose["0.weight"].data().asnumpy())
    before = net_a._collect_params_with_prefix()["0.weight"].data()
    moved = (w_loose["0.weight"].data() - before) \
        + (w_loose["1.weight"].data() - before)
    assert onp.allclose((w_tied - before).asnumpy(), moved.asnumpy(),
                        rtol=1e-5, atol=1e-7)


def test_sharded_and_elastic_steps_still_refuse_shared_parameters():
    """The plan places parameters, and the elastic step buckets and
    votes on gradients, by name: they are not made to tie, and say so."""
    from mxnet_tpu.elastic.stepfn import ElasticStepFunction
    from mxnet_tpu.shard import ShardPlan
    assert ElasticStepFunction._ties_shared is False
    x, _ = _data(feat=10)
    net = _tied_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    fused = tr.fuse_step(net, gluon.loss.L2Loss(),
                         shard_plan=ShardPlan({"batch": 2}))
    with pytest.raises(mx.MXNetError, match="shared"):
        fused.step(x, nd.zeros((x.shape[0], 10)))


def test_fused_step_tracks_grad_req_and_dtype_changes():
    """Freeze/unfreeze (grad_req flip) re-derives the trainable set;
    Parameter.cast shows up as a cache miss (visible recompile), not a
    phantom hit."""
    x, y = _data()
    net = _make_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    fused = tr.fuse_step(net, gluon.loss.L2Loss())
    fused.step(x, y)
    params = net._collect_params_with_prefix()
    frozen = params["0.weight"]
    before = frozen.data().asnumpy()
    frozen.grad_req = "null"  # freeze mid-run
    fused.step(x, y)
    assert onp.array_equal(frozen.data().asnumpy(), before), \
        "frozen parameter still updated"
    assert "0.weight" not in fused._trainable
    frozen.grad_req = "write"  # unfreeze
    fused.step(x, y)
    assert not onp.array_equal(frozen.data().asnumpy(), before), \
        "unfrozen parameter not updated"
    # a cast is a NEW program: counted as a miss, seen by the auditor
    misses0 = fused.cache_info()["misses"]
    for p in params.values():
        p.cast("bfloat16")
    fused.step(nd.array(x._data.astype("bfloat16")), y)
    assert fused.cache_info()["misses"] == misses0 + 1


def test_fused_step_hyperparam_mutation_retraces():
    """Structural hyperparameters (momentum, betas) are baked into the
    trace; mutating one mid-run must retrace AND be honored — fused
    stays bitwise-equal to the eager loop across the change."""
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    net_a, net_b = _make_net(), _make_net()
    net_a(x), net_b(x)
    _clone_into(net_a, net_b)
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.5})
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.5})
    fused = tr_b.fuse_step(net_b, loss_fn)

    def one(step):
        with autograd.record():
            loss_fn(net_a(x), y).backward()
        tr_a.step(x.shape[0])
        fused.step(x, y)

    one(0)
    misses0 = fused.cache_info()["misses"]
    # momentum warmup: both optimizers flip mid-run
    tr_a._optimizer.momentum = 0.9
    tr_b._optimizer.momentum = 0.9
    one(1)
    one(2)
    assert fused.cache_info()["misses"] == misses0 + 1  # one retrace
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    for k in pa:
        assert onp.array_equal(pa[k].data().asnumpy(),
                               pb[k].data().asnumpy())


def test_gradient_buckets_assignment():
    """Size caps, dtype segregation, oversized-param isolation."""
    items = [
        (0, (256,), "float32", 1024),
        (1, (256,), "float32", 1024),
        (2, (4096,), "float32", 16384),      # oversized: own bucket
        (3, (128,), "bfloat16", 256),        # dtype: never shares
        (4, (256,), "float32", 1024),
    ]
    gb = GradientBuckets(items, cap_bytes=2048)
    by_dtype = {}
    for b in gb.buckets:
        assert b.nbytes <= 2048 or len(b.entries) == 1
        assert len({str(b.dtype)}) == 1
        by_dtype.setdefault(str(b.dtype), []).append(
            [i for i, _, _ in b.entries])
    flat_f32 = [i for g in by_dtype["float32"] for i in g]
    assert sorted(flat_f32) == [0, 1, 2, 4]
    assert by_dtype["bfloat16"] == [[3]]
    assert [2] in by_dtype["float32"]  # oversized isolated
    # flatten/unflatten round-trips shapes and values
    import jax.numpy as jnp
    grads = {i: jnp.arange(int(onp.prod(shape)), dtype=jnp.float32
                           if dt == "float32" else jnp.bfloat16
                           ).reshape(shape) * (i + 1)
             for i, shape, dt, _ in items}
    for b in gb.buckets:
        flat = gb.flatten(b, grads)
        back = gb.unflatten(b, flat)
        for i, seg in back.items():
            assert onp.array_equal(onp.asarray(seg, dtype="float32"),
                                   onp.asarray(grads[i],
                                               dtype="float32"))


# ---------------------------------------------------------------------------
# symbol mode (executor eval_graph machinery)
# ---------------------------------------------------------------------------

def test_symbol_mode_trains():
    from mxnet_tpu import sym
    rng = onp.random.RandomState(0)
    xv = rng.uniform(-1, 1, (8, 10)).astype("float32")
    yv = rng.uniform(-1, 1, (8, 1)).astype("float32")
    data = sym.Variable("data")
    label = sym.Variable("label")
    fc = sym.FullyConnected(data, num_hidden=1, name="fc")
    loss = sym.sum(sym.square(fc - label), axis=1) / 2.0
    args = {"fc_weight": nd.array(rng.randn(1, 10).astype("float32")
                                  * 0.1),
            "fc_bias": nd.zeros((1,))}
    fused = StepFunction(loss, arg_dict=args,
                         input_names=("data", "label"),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    losses = [float(fused.step(nd.array(xv), nd.array(yv))
                    .asnumpy().mean()) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.5
    assert fused.cache_info()["programs"] == 1


# ---------------------------------------------------------------------------
# eager-sync gating (MXNET_EAGER_SYNC)
# ---------------------------------------------------------------------------

def test_eager_sync_flag_gates_engine(tmp_path):
    from mxnet_tpu import engine
    assert not engine.eager_sync()  # default async
    config.set_flag("MXNET_EAGER_SYNC", True)
    try:
        assert engine.eager_sync()
    finally:
        config.unset_flag("MXNET_EAGER_SYNC")
    assert not engine.eager_sync()
    # profiler imperative domain forces sync while recording
    from mxnet_tpu import profiler
    # the profile goes beside the configured file: keep it out of the
    # checkout's root
    profiler.set_config(filename=str(tmp_path / "profile.json"),
                        profile_imperative=True, aggregate_stats=False)
    profiler.set_state("run")
    try:
        assert engine.eager_sync()
    finally:
        profiler.set_state("stop")
        profiler.reset()
        profiler.set_config(filename="profile.json")
    assert not engine.eager_sync()


# ---------------------------------------------------------------------------
# steplint
# ---------------------------------------------------------------------------

def test_steplint_flags_unfused_optimizer():
    from mxnet_tpu.optimizer import Optimizer
    from mxnet_tpu.passes.steplint import OptimizerFusionAudit

    class NoFused(Optimizer):
        def update(self, index, weight, grad, state):
            pass

    class Fused(Optimizer):
        def update(self, index, weight, grad, state):
            pass

        def fused_apply(self, indices, weights, grads, states, lrs,
                        wds):
            return list(weights), list(states)

    findings = OptimizerFusionAudit().run(
        {"nofused": NoFused, "fusedok": Fused})
    checks = {f.obj: f for f in findings}
    assert "NoFused" in checks
    assert checks["NoFused"].severity == "warn"
    assert checks["NoFused"].check == "no-fused-apply"
    assert "Fused" not in checks


def test_steplint_builtin_registry_clean():
    """Every built-in optimizer is fused or carries a documented
    exemption — no warns."""
    from mxnet_tpu.passes.steplint import OptimizerFusionAudit
    findings = OptimizerFusionAudit().run()
    assert all(f.severity == "info" for f in findings), findings
    infos = {f.obj for f in findings}
    # the fused five never appear, even at info
    assert not infos & {"SGD", "NAG", "Adam", "AdamW", "RMSProp"}


# ---------------------------------------------------------------------------
# mxprof step report
# ---------------------------------------------------------------------------

def test_mxprof_step_report(tmp_path):
    sink = str(tmp_path / "metrics.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_METRICS_EXPORT=sink)
    code = (
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import gluon, nd\n"
        "from mxnet_tpu.gluon import nn\n"
        "net = nn.HybridSequential()\n"
        "with net.name_scope():\n"
        "    net.add(nn.Dense(8, flatten=False))\n"
        "net.initialize()\n"
        "x = nd.array(onp.ones((4, 6), 'float32'))\n"
        "y = nd.array(onp.ones((4, 8), 'float32'))\n"
        "net(x)\n"
        "tr = gluon.Trainer(net.collect_params(), 'sgd',"
        " {'learning_rate': 0.1})\n"
        "fused = tr.fuse_step(net, gluon.loss.L2Loss())\n"
        "for _ in range(3):\n"
        "    fused.step(x, y)\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
         "step", sink], env=env, capture_output=True, text=True,
        timeout=300)
    assert r2.returncode == 0, r2.stderr[-800:]
    assert "fused step (mxstep)" in r2.stdout
    assert "2 hit(s), 1 miss(es)" in r2.stdout
    r3 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxprof.py"),
         "step", sink, "--json"], env=env, capture_output=True,
        text=True, timeout=300)
    assert r3.returncode == 0
    import json
    doc = json.loads(r3.stdout)
    assert doc["tool"] == "mxprof"
    assert doc["step_metrics"]["fused_step_cache_hits_total"] == 2


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_flag_writes_to_disk(tmp_path):
    """MXNET_COMPILE_CACHE_DIR populates an on-disk cache at import
    (subprocess: jax compilation-cache config is process-global)."""
    cache_dir = str(tmp_path / "xla_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=cache_dir)
    code = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.step.cache import enable_compile_cache\n"
        "assert enable_compile_cache('%s', min_compile_time_secs=0.0)\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda a: (a * 3 + 1).sum())(jnp.ones((256, 256)))"
        ".block_until_ready()\n" % cache_dir)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    assert os.path.isdir(cache_dir) and os.listdir(cache_dir), \
        "no cache entries written"
