"""The Laguna decoder (``models/laguna.py``: its blocks, ``parallel/moe.py``
``RoutedExpertsFFN``, ``ops/banded_attention.py``) at small sizes on the
CPU, against the plain reference that the benchmark keeps
(``benchmark/families/laguna.py``: the one copy, as for ``bert_base``).
"""
import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, models  # noqa: E402
from mxnet_tpu.ndarray.ndarray import _wrap  # noqa: E402

from benchmark import correctness  # noqa: E402
from benchmark.families import laguna  # noqa: E402


def tiny_sizes(policy="f32", layers=5):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_xs2.json")) as f:
        config = json.load(f)
    return {**config, **config["tiny"]["sizes"], "dtype_policy": policy,
            "num_hidden_layers": layers}


TRAFFIC = {"batch": 2, "seq": 32, "n_batches": 3}


def build(sizes, seed=5):
    """The net holding the seed's weights (no set-up forward: the
    benchmark's ``build_program`` runs one, the harness's tests cover
    it), its loss, the weights and the batches."""
    policy = sizes["dtype_policy"]
    weights = laguna.make_weights(sizes, policy, seed)
    batches = laguna.make_batches(sizes, policy, TRAFFIC, seed)
    net = models.LagunaLM.from_config(sizes)
    net.initialize(ctx=mx.cpu(0))
    for name, p in net._collect_params_with_prefix().items():
        dt = str(jnp.dtype(laguna.param_dtype(name, policy)))
        if str(p.data().dtype) != dt:
            p.cast(dt)
        p.set_data(_wrap(jnp.array(weights[name], copy=True)))
    return net, gluon.loss.SoftmaxCrossEntropyLoss(), weights, batches


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

# float32: the two compute the same mathematics in a different order
# (sorted grouped rows against a masked loop, blocked against dense
# attention), so they agree to float32 round-off. bfloat16 (the cell's
# policy): the program rounds every activation and product to 8 bits of
# mantissa where the reference keeps float32, so the median leaf's
# gradient agrees to a few percent of its norm; the worst leaves are the
# routers', whose small gradients are differences of the experts'
# outputs and take the rounding of all of them.
@pytest.mark.parametrize("policy,tol", [("f32", 1e-5),
                                        ("bf16_norm_router_f32", 0.15)],
                         ids=["f32", "bf16"])
def test_model_matches_reference_loss_logits_and_gradients(policy, tol):
    # layers 0 and 1 hold every kind of block: full attention with the
    # dense FFN, sliding attention with experts; all five layers run
    # against the reference under the harness (tests/benchmark)
    sizes = tiny_sizes(policy, layers=2)
    net, loss_fn, weights, batches = build(sizes)
    x, y = batches[0]
    names = sorted(weights)
    from mxnet_tpu.gluon.block import functional_call

    def program(pvals):
        # the pure trace of the blocks that the fused step differentiates
        (logits,), _ = functional_call(net, pvals, [_wrap(x)],
                                       training=True)
        (loss,), _ = functional_call(loss_fn, {}, [_wrap(logits),
                                                   _wrap(y)], training=True)
        return jnp.sum(loss), logits

    (loss, logits), grads = jax.jit(jax.value_and_grad(
        program, has_aux=True))(dict(weights))
    f32 = {n: weights[n].astype(jnp.float32) for n in names}

    def ref_loss(p):
        per_seq, _ = laguna.reference_loss(sizes, p, x, y,
                                           correctness.Rounding, None)
        return jnp.sum(per_seq)

    with jax.default_matmul_precision("highest"):
        want_logits = laguna.reference_logits(sizes, f32, x,
                                              correctness.Rounding)
        want_loss, want_grads = jax.value_and_grad(ref_loss)(f32)
    got_logits = onp.asarray(logits)
    scale = float(jnp.max(jnp.abs(want_logits)))
    assert got_logits.dtype == onp.float32
    assert onp.abs(got_logits - onp.asarray(want_logits)).max() \
        <= tol * scale
    assert abs(float(loss) - float(want_loss)) \
        <= tol * float(want_loss) / 10
    errs = {}
    for n in names:
        got = onp.asarray(grads[n], "float32")
        want = onp.asarray(want_grads[n])
        errs[n] = onp.linalg.norm(got - want) / max(onp.linalg.norm(want),
                                                    1e-12)
    worst = max(errs, key=errs.get)
    assert errs[worst] <= tol, (worst, errs[worst])
    assert onp.median(list(errs.values())) <= tol / 4


def test_parameters_are_the_configurations():
    sizes = tiny_sizes()
    net = models.LagunaLM.from_config(sizes)
    net.initialize()
    got = {n: tuple(p.shape)
           for n, p in net._collect_params_with_prefix().items()}
    want = {n: tuple(s) for n, (s, _) in laguna.param_shapes(sizes).items()}
    assert got == want
    heads = [net.layers[i].attn._geometry["heads"] for i in range(5)]
    windows = [net.layers[i].attn._geometry["window"] for i in range(5)]
    assert heads == [4, 6, 6, 6, 4]
    assert windows == [None, 8, 8, 8, None]
    assert [hasattr(layer, "moe") for layer in net.layers] == \
        [False, True, True, True, True]


def test_rotary_tables_follow_the_configuration():
    """Partial rotary (the first half of a head), yarn's blend between
    the correction dimensions, cos and sin times attention_factor; the
    program's tables against the reference's own computation, and its
    rotation against the reference's."""
    sizes = tiny_sizes()
    full = sizes["rope_parameters"]["full_attention"]
    cos, sin, turn = models.laguna.rotary_tables(32, 128, full)
    assert cos.shape == (32, 128) and turn.shape == (128, 128)
    assert cos[0, 0] == pytest.approx(full["attention_factor"])
    # past the rotary dimensions (128 x 0.5) nothing turns
    assert (cos[:, 64:] == 1).all() and (sin[:, 64:] == 0).all()
    assert (turn[64:] == 0).all() and (turn[:, 64:] == 0).all()
    inv, scale = laguna._inverse_frequencies(64, full)
    assert scale == pytest.approx(0.1 * math.log(64) + 1.0)
    assert onp.allclose(cos[:, :32], onp.cos(onp.arange(32)[:, None]
                                            * inv[None]) * scale,
                        atol=1e-6)
    # the fastest dimension keeps its frequency, the slowest is
    # interpolated by the factor
    plain = 1.0 / full["rope_theta"] ** (onp.arange(0, 64, 2) / 64)
    assert inv[0] == pytest.approx(plain[0])
    assert inv[-1] == pytest.approx(plain[-1] / full["factor"])
    sliding = sizes["rope_parameters"]["sliding_attention"]
    assert models.laguna.rotary_tables(8, 128, sliding)[0][0, 0] == 1.0
    x = jax.random.normal(jax.random.key(0), (2, 32, 3, 128))
    for rope in (full, sliding):
        got = models.laguna._rotate(x, *models.laguna.rotary_tables(
            32, 128, rope))
        assert onp.allclose(got, laguna._rope(x, rope, 128), atol=1e-5)


# ---------------------------------------------------------------------------
# through Trainer.fuse_step
# ---------------------------------------------------------------------------

ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
        "epsilon": 1e-8, "wd": 0.0}


def test_fused_step_equals_eager_steps():
    """Three steps of ``Trainer.fuse_step`` against three of the eager
    loop (record, backward, ``Trainer.step``) on the same weights and
    batches, on the first two layers (every new block is in them):
    losses and every parameter agree to float32 round-off (the fused
    program fuses across the operators the eager loop runs one at a
    time)."""
    sizes = tiny_sizes(layers=2)
    net_a, loss_fn, _, batches = build(sizes)
    net_b, _, _, _ = build(sizes)
    tr_a = gluon.Trainer(net_a.collect_params(), "adam", dict(ADAM))
    tr_b = gluon.Trainer(net_b.collect_params(), "adam", dict(ADAM))
    fused = tr_b.fuse_step(net_b, loss_fn)
    misses = []
    for x, y in batches:
        x, y = _wrap(x), _wrap(y)
        with autograd.record():
            loss_a = loss_fn(net_a(x), y)
        loss_a.backward()
        tr_a.step(x.shape[0])
        loss_b = fused.step(x, y)
        misses.append(fused.cache_info()["misses"])  # process-wide count
        assert onp.allclose(loss_a.asnumpy(), loss_b.asnumpy(), atol=1e-5)
    pa = net_a._collect_params_with_prefix()
    pb = net_b._collect_params_with_prefix()
    for n in pa:
        assert onp.allclose(pa[n].data().asnumpy(), pb[n].data().asnumpy(),
                            atol=2e-5), n
    assert misses[0] == misses[-1]  # one program: no step after the first compiles


def test_scope_names_are_in_the_compiled_step():
    from benchmark import scope_paths, span_reduce
    sizes = tiny_sizes()
    net, loss_fn, _, batches = build(sizes)
    trainer = gluon.Trainer(net.collect_params(), "adam", dict(ADAM))
    fused = trainer.fuse_step(net, loss_fn)
    x, y = _wrap(batches[0][0]), _wrap(batches[0][1])
    fused.step(x, y)
    paths = set(span_reduce.op_paths(fused.compiled(x, y).as_text())
                .values())
    for names in (("attn", "window"), ("attn", "full"), ("moe", "route"),
                  ("moe", "dispatch"), ("moe", "experts"),
                  ("moe", "combine"), ("moe", "shared"), ("mlp",),
                  ("layers", "4", "attn", "full"),
                  ("layers", "3", "attn", "window")):
        for phase in ("fwd", "bwd"):
            assert any(scope_paths.holds(p, names)
                       and span_reduce.phase_of(p) == phase
                       for p in paths), (names, phase)
    # a sliding layer's products lie under window and never under full
    assert not any(scope_paths.holds(p, ("layers", "0", "attn", "window"))
                   for p in paths)
    assert not any(scope_paths.holds(p, ("layers", "1", "attn", "full"))
                   for p in paths)


def _traced_counts():
    from mxnet_tpu.telemetry import metrics
    return {label: metrics.counter(
        f"attention_traced_total.{label}").value()
        for label in ("band", "kernel", "dense")}


@pytest.mark.parametrize("on_tpu,head_dim,window,label", [
    (False, 128, 128, "dense"),    # no chip: the composition, whatever
    (True, 128, 128, "band"),      # a window the band kernel takes
    (True, 128, 200, "kernel"),    # one it does not: jax's splash kernel
    (True, 128, None, "kernel"),   # a full layer
    (True, 16, 128, "dense"),      # a head no kernel takes
], ids=["cpu", "band", "window-200", "full", "head-16"])
def test_attention_counts_its_traced_backend(monkeypatch, on_tpu, head_dim,
                                             window, label):
    """A call of ``LagunaAttention`` bumps
    ``attention_traced_total.<label>`` once, with the label of what
    ``ops.banded_attention``'s rule gives at the call's shape, and runs
    that (a chip is pretended by the rule's answers with the kernels
    interpreted); each gives the composition's numbers."""
    from mxnet_tpu.models import laguna as family
    from mxnet_tpu.ops import banded_attention as ba
    from mxnet_tpu.telemetry import metrics

    def on_a_chip(t, d, window=None, group=1):
        if ba.band_available(t, d, window, group):
            return "band_interpret"
        return "splash_interpret" if ba.splash_available(t, d) else "xla"

    rope = {"rope_type": "default", "rope_theta": 10000.0}
    attn = family.LagunaAttention(32, 2, 1, head_dim, rope, window=window)
    attn.initialize(ctx=mx.cpu(0))
    x = mx.nd.array(onp.random.RandomState(3).randn(1, 512, 32)
                    .astype("float32"))
    want = attn(x).asnumpy()            # resolves the deferred shapes
    found = _traced_counts()
    try:
        if on_tpu:
            monkeypatch.setattr(family, "default_backend", on_a_chip)
        before = _traced_counts()
        got = attn(x).asnumpy()
        after = _traced_counts()
    finally:
        # the process's record is the benchmark readers' too
        for name, value in found.items():
            counter = metrics.counter(f"attention_traced_total.{name}")
            counter.reset()
            counter.inc(value)
    assert {k: after[k] - before[k] for k in after} == \
        {k: int(k == label) for k in after}
    assert onp.allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("t,window,heads,kv_heads,dtype,rotary_share", [
    (1024, 512, 4, 2, jnp.float32, 0.5),
    (512, 128, 2, 1, jnp.float32, 1.0),      # T of one query block
    (1024, 256, 8, 1, jnp.bfloat16, 0.5),    # the cell's group and dtype
], ids=["w-512", "one-block", "bf16-group-8"])
def test_the_band_kernel_turns_and_gates_as_the_composition_does(
        t, window, heads, kv_heads, dtype, rotary_share):
    """A sliding layer's block (rotary positions on q and k, attention,
    the per-head gate) through the band kernel, which does all three in
    one pass, interpreted: the result and the gradients of q, k, v and
    the gate logits of the block as the XLA composition computes it."""
    from mxnet_tpu.models import laguna as family
    d = 128
    cos, sin, turn = family.rotary_tables(
        t, d, {"rope_type": "default", "rope_theta": 10000.0,
               "partial_rotary_factor": rotary_share})
    ks = jax.random.split(jax.random.key(t + heads), 5)
    shapes = [(1, t, heads * d), (1, t, kv_heads * d), (1, t, kv_heads * d),
              (1, t, heads), (1, t, heads * d)]
    *ops, ct = [jax.random.normal(key, s, jnp.float32).astype(dtype)
                for key, s in zip(ks, shapes)]

    def run(backend):
        out, vjp = jax.vjp(functools.partial(
            family._gated_attention, cos=cos, sin=sin, turn=turn,
            heads=heads, kv_heads=kv_heads, head_dim=d, window=window,
            backend=backend), *ops)
        return (out,) + vjp(ct)

    tol = 1e-5 if dtype == jnp.float32 else 0.03
    for a, b in zip(run("band_interpret"), run("xla")):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(a - b))) <= tol * float(
            jnp.max(jnp.abs(b)))
