"""Pallas kernel tests (interpret mode on CPU; real lowering on TPU)."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import flash_attention
from mxnet_tpu.parallel.ring_attention import local_attention
from mxnet_tpu.test_utils import assert_almost_equal


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    B, H, T, D = 2, 2, 256, 64
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)


def test_flash_attention_grad():
    B, H, T, D = 1, 2, 128, 64
    rng = onp.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, False, None, 128, 128,
                                       True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(local_attention(q_, k_, v_) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grad_tiled_kernel(causal):
    """The Pallas backward (dq/dk/dv kernels with per-block recompute)
    must match the dense vjp — multi-block so the K/Q sweeps and the
    causal block-skip actually execute."""
    B, H, T, D = 1, 2, 256, 64
    rng = onp.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))

    def f_flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal, None, 128, 128, True)

    def f_ref(q_, k_, v_):
        return local_attention(q_, k_, v_, causal=causal)

    _, vjp_f = jax.vjp(f_flash, q, k, v)
    _, vjp_r = jax.vjp(f_ref, q, k, v)
    for a, b, nm in zip(vjp_f(g), vjp_r(g), "qkv"):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_attention_grad_cross_length():
    """Tq != Tk (cross attention) through the tiled backward."""
    B, H, Tq, Tk, D = 1, 1, 128, 256, 64
    rng = onp.random.RandomState(4)
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32"))
    _, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, False, None, 128, 128,
                                        True), q, k, v)
    _, vjp_r = jax.vjp(lambda a, b, c: local_attention(a, b, c), q, k, v)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_padded_odd_seq(causal):
    """Non-tiling seq length now runs the KERNEL via tail padding + the
    kv_len mask (VERDICT r3 item 2) — exact match vs dense."""
    B, H, T, D = 1, 2, 100, 64
    rng = onp.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.4)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.4)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    ref = local_attention(q, k, v, causal=causal)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_padded_head_dim_96(causal):
    """BERT-shaped head_dim 96 pads the contraction to 128 (exact) and
    the padded grad columns slice off — fwd AND bwd vs dense."""
    B, H, T, D = 1, 2, 384, 96
    rng = onp.random.RandomState(6)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    out, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, causal, None, 128, 128,
                                        True), q, k, v)
    ref, vjp_r = jax.vjp(
        lambda a, b, c: local_attention(a, b, c, causal=causal), q, k, v)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_attention_padded_odd_seq_grad():
    """Gradients through the pad/mask path: odd Tq AND odd Tk AND odd
    head_dim at once (cross-length, non-causal)."""
    B, H, Tq, Tk, D = 1, 1, 100, 200, 80
    rng = onp.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32"))
    g = jnp.asarray(rng.randn(B, H, Tq, D).astype("float32"))
    _, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, False, None, 128, 128,
                                        True), q, k, v)
    _, vjp_r = jax.vjp(lambda a, b, c: local_attention(a, b, c), q, k, v)
    for a, b in zip(vjp_f(g), vjp_r(g)):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_attention_fallback_tiny():
    # sequences too short to amortize a 128 block still fall back
    q = jnp.ones((1, 1, 16, 32), jnp.float32)
    out = flash_attention(q, q, q, False, None, 128, 128, True)
    ref = local_attention(q, q, q)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("bq,bk", [(256, 128), (128, 256), (64, 128)])
def test_flash_attention_causal_mixed_blocks(bq, bk):
    """Regression: causal K-block count must cover the Q-block's LAST row
    (wrong when block_q > block_k)."""
    B, H, T, D = 1, 1, 256, 64
    rng = onp.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32") * 0.5)
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, bq, bk, True)
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=2e-4,
                        atol=2e-4)


def test_flash_attention_available_predicate():
    from mxnet_tpu.ops.pallas_kernels import (_plan_blocks,
                                              flash_attention_available)
    # the kernel takes padded shapes (odd lengths, head_dim 96)...
    assert flash_attention_available(400, 400, 64)
    assert flash_attention_available(512, 400, 64)
    assert flash_attention_available(384, 384, 96)
    # ...but is not offered where the dense composition is faster
    # (short sequences), though it tiles them
    for t_q, t_k in ((100, 100), (128, 128), (128, 100), (256, 512)):
        assert not flash_attention_available(t_q, t_k, 64)
        assert _plan_blocks(jnp.ones((1, 1, t_q, 64)),
                            jnp.ones((1, 1, t_k, 64)), 512, 1024)
    # 128-multiple big heads tile exactly; other big heads fall back
    assert flash_attention_available(512, 512, 512)
    assert not flash_attention_available(512, 512, 300)
    # tiny sequences the kernel does not even tile
    assert not flash_attention_available(16, 16, 64)
    assert _plan_blocks(jnp.ones((1, 1, 16, 64)), jnp.ones((1, 1, 16, 64)),
                        512, 1024) is None


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [128, 256, 384, 512, 1024, 2048])
def test_attention_rule_over_the_measured_table(t, d, dtype):
    """The shapes of the chip's kernel-against-dense table (PERF.md
    section 6, PR 29; B x H = 192, forward plus backward): dense won at
    128 and 256, the kernel from 384 up, in both head sizes and both
    dtypes."""
    from mxnet_tpu.ops.pallas_kernels import flash_attention_available
    assert flash_attention_available(t, t, d, dtype) == (t >= 384)
    assert flash_attention_available(t, t, d, jnp.dtype(dtype)) == (t >= 384)


@pytest.mark.parametrize("t,d,dtype", [
    (32, 32, "float32"),       # shorter than the kernel tiles
    (256, 64, "float32"),      # dense is faster
    (512, 320, "float32"),     # a head size the matrix unit cannot tile
    (512, 64, "float16"),      # a dtype the kernel does not multiply in
    (512, 64, "float64"),
    (512, 64, "int8")])
def test_attention_rule_leaves_the_rest_to_dense(t, d, dtype):
    from mxnet_tpu.ops.pallas_kernels import flash_attention_available
    assert not flash_attention_available(t, t, d, dtype)


def test_attention_rule_is_false_while_gspmd_partitions_the_trace():
    from mxnet_tpu.ops.pallas_kernels import (flash_attention_available,
                                              gspmd_partitioned)
    assert flash_attention_available(512, 512, 64, "float32")
    with gspmd_partitioned():
        assert not flash_attention_available(512, 512, 64, "float32")
        with gspmd_partitioned():
            assert not flash_attention_available(512, 512, 64, "float32")
        assert not flash_attention_available(512, 512, 64, "float32")
    assert flash_attention_available(512, 512, 64, "float32")


def _traced_counts():
    from mxnet_tpu.telemetry import metrics
    return {label: metrics.counter(
        f"attention_traced_total.{label}").value()
        for label in ("kernel", "dense")}


@pytest.fixture
def traced_counts_kept():
    """The process's counters left as the test found them: what a test
    traces under a pretended chip is not the process's record, and the
    benchmark's reader of the same counters
    (``tests/benchmark/test_benchmark_attention_products.py``) may run
    in this worker after it."""
    from mxnet_tpu.telemetry import metrics
    found = _traced_counts()
    yield
    for label, value in found.items():
        counter = metrics.counter(f"attention_traced_total.{label}")
        counter.reset()
        counter.inc(value)


def _interpreted(q, k, v, causal=False, scale=None):
    return flash_attention(q, k, v, causal, scale, interpret=True)


@pytest.mark.parametrize("on_tpu,t,label", [
    (False, 512, "dense"),     # no chip: dense whatever the rule says
    (True, 512, "kernel"),
    (True, 128, "dense")])     # the rule refuses the shape
def test_multi_head_attention_counts_its_traced_backend(
        monkeypatch, traced_counts_kept, on_tpu, t, label):
    """A call of ``MultiHeadAttention`` bumps
    ``attention_traced_total.<label>`` once, with the label the rule
    gives, and takes that backend."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer
    monkeypatch.setattr(transformer, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(transformer, "flash_attention", _interpreted)
    attn = transformer.MultiHeadAttention(64, 2)
    attn.initialize()
    x = mx.nd.array(onp.random.RandomState(11).randn(2, t, 64)
                    .astype("float32"))
    attn(x).wait_to_read()          # resolves the deferred shapes
    before = _traced_counts()
    got = attn(x).asnumpy()
    after = _traced_counts()
    assert {k: after[k] - before[k] for k in after} == \
        {"kernel": int(label == "kernel"), "dense": int(label == "dense")}
    monkeypatch.setattr(transformer, "_on_tpu", lambda: False)
    assert_almost_equal(got, attn(x).asnumpy(), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("active,label", [(False, "dense"),
                                          (True, "kernel")])
def test_fused_attention_counts_its_traced_backend(
        monkeypatch, traced_counts_kept, active, label):
    from mxnet_tpu.ops import fused, pallas_kernels
    monkeypatch.setattr(fused, "pallas_attention_active",
                        lambda *a: active)
    monkeypatch.setattr(pallas_kernels, "flash_attention", _interpreted)
    q, k, v, _ = _qkvg((1, 2, 128, 64), 12)
    before = _traced_counts()
    out = jax.jit(lambda *a: fused.fused_attention(*a, scale=0.125))(q, k, v)
    after = _traced_counts()
    assert {k_: after[k_] - before[k_] for k_ in after} == \
        {"kernel": int(active), "dense": int(not active)}
    assert_almost_equal(onp.asarray(out),
                        onp.asarray(local_attention(q, k, v)),
                        rtol=2e-4, atol=2e-4)


def _qkvg(shape, seed, dtype="float32"):
    rng = onp.random.RandomState(seed)
    q, k, v, g = (jnp.asarray(rng.randn(*shape).astype("float32") * s)
                  .astype(dtype) for s in (0.3, 0.3, 1.0, 1.0))
    return q, k, v, g


def _assert_kernel_matches_dense(q, k, v, g, causal, tol):
    """Output and the three gradients of the kernel (interpret mode,
    default blocks) against the dense composition on the same values in
    float32."""
    out, vjp_f = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, causal, None,
                                        interpret=True), q, k, v)
    ref, vjp_r = jax.vjp(
        lambda a, b, c: local_attention(a, b, c, causal=causal),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    assert out.dtype == q.dtype
    for a, b in zip((out,) + vjp_f(g), (ref,) + vjp_r(g.astype(jnp.float32))):
        assert a.dtype == q.dtype
        assert_almost_equal(onp.asarray(a.astype(jnp.float32)),
                            onp.asarray(b), rtol=tol, atol=tol)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-3),
                                       ("bfloat16", 3e-2)])
def test_attention_kernel_bert_head_shape(dtype, tol, causal):
    """BERT's head shape: a head's 512 keys fit one block, so a block of
    queries meets them all at once (no key loop, no running maximum)
    and the backward pass is one kernel. bfloat16 operands run the same
    kernel without the casts; their results round to bfloat16."""
    from mxnet_tpu.ops.pallas_kernels import _plan_blocks
    q, k, v, g = _qkvg((2, 12, 512, 64), 8, dtype)
    plan = _plan_blocks(q, k, 256, 1024)
    assert (plan["bq"], plan["bk"], plan["Tkp"]) == (256, 512, 512)
    _assert_kernel_matches_dense(q, k, v, g, causal, tol)


@pytest.mark.parametrize("causal", [False, True])
def test_attention_kernel_key_block_loop(causal):
    """Past the single-block limit (T = 2048) the keys stream in blocks
    of 1024 under the online softmax, and the backward pass is two
    sweeps."""
    from mxnet_tpu.ops.pallas_kernels import _plan_blocks
    q, k, v, g = _qkvg((1, 2, 2048, 64), 9)
    assert _plan_blocks(q, k, 256, 1024)["bk"] == 1024
    _assert_kernel_matches_dense(q, k, v, g, causal, 2e-3)


def _bfloat16_casts_outside_kernels(jaxpr):
    """convert_element_type equations to bfloat16 in a jaxpr and its
    sub-jaxprs, not looking inside a pallas_call."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "convert_element_type" \
                and eqn.params["new_dtype"] == jnp.bfloat16:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _bfloat16_casts_outside_kernels(sub)
    return found


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_attention_kernel_rounds_nothing_outside_itself(which):
    """float32 q, k, v and do enter the kernels as float32 and o, dq, dk,
    dv leave them as float32: the casts to bfloat16 are tile by tile
    inside (what the dense composition's default-precision dots round),
    never a whole array in HBM."""
    q, k, v, g = _qkvg((2, 12, 512, 64), 10)

    def fwd(q_, k_, v_):
        return flash_attention(q_, k_, v_)

    def bwd(q_, k_, v_, g_):
        return jax.vjp(fwd, q_, k_, v_)[1](g_)

    closed = jax.make_jaxpr(fwd)(q, k, v) if which == "forward" \
        else jax.make_jaxpr(bwd)(q, k, v, g)
    text = str(closed)
    assert "pallas_call" in text
    assert not _bfloat16_casts_outside_kernels(closed.jaxpr)
    assert all(v_.aval.dtype == jnp.float32 for v_ in closed.jaxpr.outvars)
    # and the kernels themselves do cast: the products are bfloat16
    assert "bf16" in text


def test_attention_backend_is_not_measured_nor_read_from_the_environment():
    """The call sites ask the rule and nothing else: no ``operator_tune``
    measurement (a forward-only timing of batch 1 would flip between
    two close candidates from run to run), no flag of their own."""
    import inspect
    from mxnet_tpu import config
    from mxnet_tpu.models import transformer
    from mxnet_tpu.ops import fused, pallas_kernels
    for module in (transformer, pallas_kernels):
        source = inspect.getsource(module)
        assert "operator_tune" not in source and "_otune" not in source
        assert "environ" not in source and "get_env" not in source
    assert "operator_tune" not in inspect.getsource(fused.fused_attention)
    assert not [name for name in config.flags()
                if "ATTENTION" in name]


def _rounded_einsum(spec, spec_da, spec_db):
    """An einsum as XLA's default precision runs it on a TPU, forward
    and backward: operands rounded to bfloat16, float32 accumulation."""
    def mm(spec_, a, b):
        return jnp.einsum(spec_, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    @jax.custom_vjp
    def f(a, b):
        return mm(spec, a, b)

    f.defvjp(lambda a, b: (f(a, b), (a, b)),
             lambda res, g: (mm(spec_da, g, res[1]), mm(spec_db, g, res[0])))
    return f


def test_attention_kernel_rounds_what_the_dense_composition_rounds(
        monkeypatch):
    """With the products in bfloat16, as on the chip, the kernel's
    gradients are as far from exact attention as the dense
    composition's under XLA's default precision, and a query's ds sums
    to zero over the keys as closely: the key bias's gradient, sum_j
    dk_j, exactly 0 in exact arithmetic, is the same rounding noise on
    both sides. (Taking the softmax's backward sum from o and do, as
    flash kernels do, leaves 1.7 times the noise there; under Adam that
    moved the cell's ``delta_gap_mean`` past its limit: PERF.md,
    PR 29.)"""
    from mxnet_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_mxu_dtype",
                        lambda dtype, interpret: jnp.bfloat16)
    scores = _rounded_einsum("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd",
                             "bhqk,bhqd->bhkd")
    weighted = _rounded_einsum("bhqk,bhkd->bhqd", "bhqd,bhkd->bhqk",
                               "bhqd,bhqk->bhkd")

    def dense(q_, k_, v_):
        return weighted(jax.nn.softmax(scores(q_, k_) * 0.125, axis=-1), v_)

    def exact(q_, k_, v_):
        with jax.default_matmul_precision("highest"):
            return local_attention(q_, k_, v_)

    rng = onp.random.RandomState(13)
    q, k, v, g = (jnp.asarray(rng.randn(1, 4, 512, 64).astype("float32"))
                  for _ in range(4))
    got = {}
    for name, fn in (("exact", exact), ("dense", dense),
                     ("kernel", lambda *a: flash_attention(
                         *a, interpret=True))):
        out, vjp = jax.vjp(fn, q, k, v)
        got[name] = (out,) + vjp(g)

    def gaps(name):
        return [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for a, b in zip(got[name], got["exact"])]

    def key_bias_grad(name):
        return float(jnp.linalg.norm(got[name][2].sum(axis=2)))

    for kernel_gap, dense_gap in zip(gaps("kernel"), gaps("dense")):
        assert 2e-3 < dense_gap < 6e-3           # bfloat16 products
        assert abs(kernel_gap - dense_gap) < 0.05 * dense_gap
    assert key_bias_grad("exact") < 1e-4
    assert abs(key_bias_grad("kernel") - key_bias_grad("dense")) \
        < 0.1 * key_bias_grad("dense")
