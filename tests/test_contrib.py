"""Contrib op tests: detection (SSD), control flow, numpy namespace
(ref: tests/python/unittest/test_contrib_operator.py,
test_contrib_control_flow.py, test_numpy_*)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


def test_box_iou():
    a = nd.array([[0.0, 0.0, 2.0, 2.0]])
    b = nd.array([[1.0, 1.0, 3.0, 3.0], [0.0, 0.0, 2.0, 2.0]])
    iou = nd.contrib.box_iou(a, b)
    assert iou.shape == (1, 2)
    assert iou.asnumpy()[0, 0] == pytest.approx(1.0 / 7.0, rel=1e-5)
    assert iou.asnumpy()[0, 1] == pytest.approx(1.0)


def test_box_nms():
    # rows: [cls, score, x0, y0, x1, y1]
    dets = nd.array([
        [0, 0.9, 0.0, 0.0, 1.0, 1.0],
        [0, 0.8, 0.05, 0.05, 1.0, 1.0],   # overlaps first -> suppressed
        [0, 0.7, 2.0, 2.0, 3.0, 3.0],     # far away -> kept
    ])
    out = nd.contrib.box_nms(dets, overlap_thresh=0.5, coord_start=2,
                             score_index=1, id_index=0)
    got = out.asnumpy()
    assert got[0, 1] == pytest.approx(0.9)
    assert (got[1] == -1).all()
    assert got[2, 1] == pytest.approx(0.7)


def test_multibox_prior():
    data = nd.zeros((1, 3, 4, 4))
    anchors = nd.contrib.MultiBoxPrior(data, sizes=(0.5, 0.25),
                                       ratios=(1, 2))
    # num_anchors = 2 + 2 - 1 = 3
    assert anchors.shape == (1, 4 * 4 * 3, 4)
    a = anchors.asnumpy()[0]
    # first anchor of first cell: size 0.5 centered at (0.125, 0.125)
    assert a[0, 0] == pytest.approx(0.125 - 0.25)
    assert a[0, 2] == pytest.approx(0.125 + 0.25)


def test_multibox_target_and_detection():
    data = nd.zeros((1, 3, 2, 2))
    anchors = nd.contrib.MultiBoxPrior(data, sizes=(0.4,), ratios=(1,))
    A = anchors.shape[1]
    # one gt box matching the first cell's anchor
    label = nd.array([[[0, 0.05, 0.05, 0.45, 0.45],
                       [-1, -1, -1, -1, -1]]])
    cls_pred = nd.zeros((1, 2, A))
    bt, bm, ct = nd.contrib.MultiBoxTarget(anchors, label, cls_pred)
    assert bt.shape == (1, 4 * A)
    assert bm.shape == (1, 4 * A)
    assert ct.shape == (1, A)
    ctn = ct.asnumpy()[0]
    assert (ctn == 1).sum() >= 1       # at least one anchor matched class 0
    # detection decode roundtrip: zero offsets = raw anchors
    cls_prob = nd.array(onp.stack([onp.full((A,), 0.1),
                                   onp.full((A,), 0.9)])[None])
    loc_pred = nd.zeros((1, 4 * A))
    det = nd.contrib.MultiBoxDetection(cls_prob, loc_pred, anchors,
                                       nms_threshold=0.99)
    assert det.shape == (1, A, 6)
    d0 = det.asnumpy()[0, 0]
    assert d0[0] == 0.0                # class id
    assert d0[1] == pytest.approx(0.9)


def test_bipartite_matching():
    score = nd.array([[0.9, 0.1], [0.8, 0.7]])
    rows, cols = nd.contrib.bipartite_matching(score, threshold=0.5)
    assert rows.asnumpy().tolist() == [0.0, 1.0]
    assert cols.asnumpy().tolist() == [0.0, 1.0]


def test_foreach():
    def body(x, state):
        new_s = state + x
        return new_s * 1.0, new_s

    data = nd.array([[1.0], [2.0], [3.0]])
    init = nd.array([0.0])
    outs, final = nd.contrib.foreach(body, data, init)
    assert outs.asnumpy().reshape(-1).tolist() == [1.0, 3.0, 6.0]
    assert final.asnumpy().tolist() == [6.0]


def test_foreach_grad():
    w = nd.array([2.0])
    w.attach_grad()

    def body(x, state):
        o = x * w
        return o, state + o

    data = nd.array([[1.0], [2.0]])
    with mx.autograd.record():
        outs, final = nd.contrib.foreach(body, data, nd.array([0.0]))
        loss = final.sum()
    loss.backward()
    assert w.grad.asscalar() == pytest.approx(3.0)


def test_while_loop():
    def cond_fn(i, s):
        return i < 5

    def func(i, s):
        return s * 1.0, [i + 1, s + i]

    outs, final = nd.contrib.while_loop(
        cond_fn, func, [nd.array([0.0]), nd.array([0.0])],
        max_iterations=10)
    assert final[0].asscalar() == 5.0
    assert final[1].asscalar() == 10.0  # 0+1+2+3+4


def test_cond():
    x = nd.array([2.0])
    out = nd.contrib.cond(lambda a: a.sum() > 1,
                          lambda a: a * 10,
                          lambda a: a * -1, [x])
    assert out.asscalar() == 20.0
    out = nd.contrib.cond(lambda a: a.sum() > 5,
                          lambda a: a * 10,
                          lambda a: a * -1, [x])
    assert out.asscalar() == -2.0


def test_np_namespace():
    a = mx.np.array([[1.0, 2.0], [3.0, 4.0]])
    assert isinstance(a, mx.np.ndarray)
    b = mx.np.ones((2, 2))
    c = mx.np.add(a, b)
    assert c.asnumpy().tolist() == [[2, 3], [4, 5]]
    # bool comparisons (np semantics differ from nd)
    m = a > 2
    assert str(m.dtype) == "bool"
    assert mx.np.sum(a).item() == 10.0
    d = mx.np.dot(a, b)
    assert d.asnumpy()[0, 0] == 3.0
    t = mx.np.tensordot(a, b, axes=1)
    assert t.shape == (2, 2)
    e = mx.np.einsum("ij,jk->ik", a, b)
    assert_almost_equal(e.asnumpy(), d.asnumpy())
    # conversion
    nd_arr = a.as_nd_ndarray()
    assert isinstance(nd_arr, nd.NDArray)
    assert not isinstance(nd_arr, mx.np.ndarray)
    s = mx.np.random.uniform(0, 1, size=(3,))
    assert s.shape == (3,)


def test_npx():
    x = mx.np.array([[-1.0, 1.0]])
    out = mx.npx.relu(x)
    assert isinstance(out, mx.np.ndarray)
    assert out.asnumpy().tolist() == [[0.0, 1.0]]
    sm = mx.npx.softmax(x)
    assert sm.asnumpy().sum() == pytest.approx(1.0)


def test_image_ops():
    img = nd.array(onp.random.randint(0, 255, (8, 8, 3)).astype("uint8"))
    t = nd._image_to_tensor(img)
    assert t.shape == (3, 8, 8)
    assert t.asnumpy().max() <= 1.0
    norm = nd._image_normalize(t, mean=(0.5, 0.5, 0.5), std=(0.2, 0.2, 0.2))
    assert norm.shape == (3, 8, 8)
    r = nd._image_resize(img, size=(4, 4))
    assert r.shape == (4, 4, 3)
    c = nd._image_crop(img, x=1, y=2, width=3, height=4)
    assert c.shape == (4, 3, 3)
    f = nd._image_flip_left_right(img)
    assert_almost_equal(f.asnumpy()[:, 0], img.asnumpy()[:, -1])


def test_quantization_roundtrip():
    x = nd.array(onp.random.uniform(-3, 3, (4, 5)).astype("float32"))
    q, mn, mx_ = nd._contrib_quantize_v2(x)
    assert str(q.dtype) == "int8"
    deq = nd._contrib_dequantize(q, mn, mx_)
    assert_almost_equal(deq.asnumpy(), x.asnumpy(), atol=0.05)


def test_quantized_fc():
    x8 = nd.array(onp.random.randint(-127, 127, (2, 4)), dtype="int8")
    w8 = nd.array(onp.random.randint(-127, 127, (3, 4)), dtype="int8")
    b = nd.zeros(3, dtype="int8")
    mn = nd.array([-1.0])
    mx_ = nd.array([1.0])
    out, omin, omax = nd._contrib_quantized_fully_connected(
        x8, w8, b, mn, mx_, mn, mx_, mn, mx_, num_hidden=3)
    expect = x8.asnumpy().astype("int32") @ w8.asnumpy().astype("int32").T
    assert_almost_equal(out.asnumpy(), expect)


def test_quantize_model_end_to_end():
    """quantize_model must emit a REWRITTEN graph that executes the int8
    conv/FC kernels and stays close to the fp32 model (ref:
    quantize_graph_pass.cc + quantization.py quantize_model)."""
    import mxnet_tpu as mx
    from mxnet_tpu import io, sym
    from mxnet_tpu.contrib.quantization import quantize_model

    rs = onp.random.RandomState(0)
    x = sym.var("data")
    c = sym.Convolution(x, name="conv0", kernel=(3, 3), num_filter=8,
                        pad=(1, 1))
    r = sym.Activation(c, act_type="relu")
    f = sym.flatten(r)
    o = sym.FullyConnected(f, name="fc0", num_hidden=6)
    net = o

    args = {"conv0_weight": nd.array(rs.randn(8, 3, 3, 3)
                                     .astype("float32") * 0.3),
            "conv0_bias": nd.array(rs.randn(8).astype("float32") * 0.1),
            "fc0_weight": nd.array(rs.randn(6, 8 * 6 * 6)
                                   .astype("float32") * 0.1),
            "fc0_bias": nd.array(rs.randn(6).astype("float32") * 0.1)}
    data = rs.uniform(-1, 1, (8, 3, 6, 6)).astype("float32")
    calib = io.NDArrayIter(data={"data": nd.array(data)}, batch_size=4)

    qsym, qargs, qaux = quantize_model(
        net, args, {}, calib_mode="naive", calib_data=calib,
        ctx=mx.cpu())
    # the rewrite actually lowered onto the int8 ops
    ops = {n.op for n in qsym._topo_nodes() if n.op}
    assert "_contrib_quantized_conv" in ops
    assert "_contrib_quantized_fully_connected" in ops
    assert str(qargs["conv0_weight"].dtype) == "int8"
    assert str(qargs["fc0_weight"].dtype) == "int8"

    xs = nd.array(data[:4])
    ref = net.bind(mx.cpu(), {"data": xs, **args}).forward()[0].asnumpy()
    got = qsym.bind(mx.cpu(), {"data": xs, **qargs}).forward()[0].asnumpy()
    # int8 quantization error bound: close in absolute + rank order
    spread = max(ref.max() - ref.min(), 1e-6)
    assert onp.abs(got - ref).max() / spread < 0.15
    agree = (got.argmax(axis=1) == ref.argmax(axis=1)).mean()
    assert agree >= 0.75


def test_quantize_model_bias_shifts_output_range():
    """Bias that recenters the output must not break calibration: the
    bias is folded into the int32 accumulator (scaled s_data*s_weight)
    so the calibrated post-bias requantize range applies to what is
    actually requantized. Regression: all-negative conv outputs ~-20
    recentered near 0 by bias +5 used to clip at >100% error."""
    import mxnet_tpu as mx
    from mxnet_tpu import io, sym
    from mxnet_tpu.contrib.quantization import quantize_model

    rs = onp.random.RandomState(1)
    x = sym.var("data")
    net = sym.Convolution(x, name="conv0", kernel=(1, 1), num_filter=4)

    w = -onp.abs(rs.randn(4, 3, 1, 1).astype("float32"))  # all-negative
    args = {"conv0_weight": nd.array(w),
            "conv0_bias": nd.array(onp.full(4, 5.0, "float32"))}
    data = rs.uniform(2.0, 3.0, (8, 3, 4, 4)).astype("float32")
    calib = io.NDArrayIter(data={"data": nd.array(data)}, batch_size=4)
    qsym, qargs, _ = quantize_model(net, args, {}, calib_mode="naive",
                                    calib_data=calib, ctx=mx.cpu())
    xs = nd.array(data[:4])
    ref = net.bind(mx.cpu(), {"data": xs, **args}).forward()[0].asnumpy()
    got = qsym.bind(mx.cpu(), {"data": xs, **qargs}).forward()[0].asnumpy()
    spread = max(ref.max() - ref.min(), 1e-6)
    assert onp.abs(got - ref).max() / spread < 0.1
    # the folded int32 bias replaced the fp32 bias variable
    assert "conv0_bias_quant" in qargs and "conv0_bias" not in qargs
    assert str(qargs["conv0_bias_quant"].dtype) == "int32"


def test_quantized_graph_json_roundtrip():
    """A rewritten int8 graph must survive tojson/load_json (the
    deployment path: qsym.save -> SymbolBlock/Module load)."""
    import mxnet_tpu as mx
    from mxnet_tpu import io, sym
    from mxnet_tpu.contrib.quantization import quantize_model
    from mxnet_tpu.symbol.symbol import load_json

    rs = onp.random.RandomState(0)
    x = sym.var("data")
    net = sym.FullyConnected(
        sym.Activation(sym.Convolution(x, name="c", kernel=(3, 3),
                                       num_filter=4, pad=(1, 1)),
                       act_type="relu"), name="f", num_hidden=3)
    args = {"c_weight": nd.array(rs.randn(4, 3, 3, 3)
                                 .astype("float32") * 0.3),
            "c_bias": nd.zeros((4,)),
            "f_weight": nd.array(rs.randn(3, 64).astype("float32") * 0.1),
            "f_bias": nd.zeros((3,))}
    data = rs.uniform(-1, 1, (8, 3, 4, 4)).astype("float32")
    calib = io.NDArrayIter(data={"data": nd.array(data)}, batch_size=4)
    qsym, qargs, _ = quantize_model(net, args, {}, calib_mode="naive",
                                    calib_data=calib, ctx=mx.cpu())
    q2 = load_json(qsym.tojson())
    xs = nd.array(data[:4])
    o1 = qsym.bind(mx.cpu(), {"data": xs, **qargs}).forward()[0].asnumpy()
    o2 = q2.bind(mx.cpu(), {"data": xs, **qargs}).forward()[0].asnumpy()
    assert onp.allclose(o1, o2)


def test_quantize_model_requires_calib_data():
    from mxnet_tpu import sym
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.contrib.quantization import quantize_model
    net = sym.FullyConnected(sym.var("data"), name="fc", num_hidden=2)
    with pytest.raises(MXNetError, match="calib_data"):
        quantize_model(net, {}, {}, calib_mode="entropy")


def test_misc_contrib():
    x = nd.array([1.0, 2.0])
    q = nd.contrib.quadratic(x, a=1, b=2, c=3)
    assert q.asnumpy().tolist() == [6.0, 11.0]
    al = nd._contrib_arange_like(nd.zeros((3, 2)), start=0, axis=0)
    assert al.asnumpy().tolist() == [0, 1, 2]
    ds = nd._contrib_div_sqrt_dim(nd.ones((2, 4)))
    assert ds.asnumpy()[0, 0] == pytest.approx(0.5)
    # gradientmultiplier: identity forward, scaled backward
    y = nd.array([3.0])
    y.attach_grad()
    with mx.autograd.record():
        out = nd._contrib_gradientmultiplier(y, scalar=0.5)
    out.backward()
    assert y.grad.asscalar() == pytest.approx(0.5)
    # fft/ifft roundtrip
    sig = nd.array(onp.random.randn(2, 8).astype("float32"))
    fz = nd._contrib_fft(sig)
    assert fz.shape == (2, 16)
    back = nd._contrib_ifft(fz) / 8
    assert_almost_equal(back.asnumpy(), sig.asnumpy(), atol=1e-4)


def test_contrib_legacy_autograd():
    """ref: contrib/autograd.py — the pre-1.0 grad/grad_and_loss API."""
    from mxnet_tpu.contrib import autograd as cag

    def f(x):
        return (x * x).sum()

    x = nd.array(onp.array([1.0, 2.0, 3.0], "float32"))
    grads, loss = cag.grad_and_loss(f)(x)
    assert onp.allclose(grads[0].asnumpy(), [2.0, 4.0, 6.0])
    assert float(loss.asscalar()) == pytest.approx(14.0)
    g = cag.grad(f)(x)
    assert onp.allclose(g[0].asnumpy(), [2.0, 4.0, 6.0])
    with cag.train_section():
        from mxnet_tpu import autograd as ag
        assert ag.is_recording()
        with cag.test_section():
            assert not ag.is_recording()


def test_contrib_dataloader_iter():
    """ref: contrib/io.py DataLoaderIter — gluon DataLoader feeding a
    Module."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, sym
    from mxnet_tpu.contrib.io import DataLoaderIter
    rs = onp.random.RandomState(0)
    x = nd.array(rs.rand(32, 6).astype("float32"))
    y = nd.array((rs.rand(32) > 0.5).astype("float32"))
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(x, y),
                                   batch_size=8)
    it = DataLoaderIter(loader)
    assert it.provide_data[0].shape == (8, 6)
    batches = list(it)
    assert len(batches) == 4
    it.reset()
    assert len(list(it)) == 4
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.var("data"), num_hidden=2), name="softmax")
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    it.reset()
    mod.fit(it, num_epoch=1, optimizer="sgd")


def test_contrib_namespaces_and_tensorrt():
    import mxnet_tpu as mx
    from mxnet_tpu.contrib import ndarray as cnd, symbol as csym, tensorrt
    # alias namespaces resolve the same ops as nd/sym contrib
    assert cnd.quadratic is not None
    assert csym.MultiBoxPrior is not None
    tensorrt.set_use_fp16(True)
    assert tensorrt.get_use_fp16()
    with pytest.raises(mx.base.MXNetError, match="XLA"):
        tensorrt.init_tensorrt_params(None, {}, {})


def test_contrib_dataloader_iter_pads_short_final_batch():
    from mxnet_tpu import gluon
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.contrib.io import DataLoaderIter
    rs = onp.random.RandomState(0)
    x = nd.array(rs.rand(30, 6).astype("float32"))  # 30 % 8 != 0
    y = nd.array(rs.rand(30).astype("float32"))
    loader = gluon.data.DataLoader(gluon.data.ArrayDataset(x, y),
                                   batch_size=8)
    it = DataLoaderIter(loader)
    batches = list(it)
    assert [b.pad for b in batches] == [0, 0, 0, 2]
    assert all(b.data[0].shape == (8, 6) for b in batches)
    empty = gluon.data.DataLoader(
        gluon.data.ArrayDataset(nd.zeros((0, 6)), nd.zeros((0,))),
        batch_size=4)
    with pytest.raises(MXNetError, match="empty"):
        DataLoaderIter(empty)


def test_quantized_conv_chain_one_jit():
    """VERDICT r3 item 3: quantize -> int8 conv -> requantize ->
    dequantize as ONE jitted XLA program, numerically close to the fp32
    conv, with the compiled HLO actually convolving in s8 (the MXU int8
    path) rather than upcasting."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.quantization import (dequantize, quantize_v2,
                                            quantized_conv, requantize)

    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.uniform(-1, 1, (2, 3, 16, 16)), jnp.float32)
    w = jnp.asarray(rs.randn(8, 3, 3, 3) * 0.2, jnp.float32)

    # offline weight quantization (what quantize_model does)
    w_lo, w_hi = float(w.min()), float(w.max())
    q8, wmin, wmax = quantize_v2(w, min_calib_range=w_lo,
                                 max_calib_range=w_hi)

    def chain(x, w8, wmin, wmax):
        qx, dmin, dmax = quantize_v2(x, min_calib_range=-1.0,
                                     max_calib_range=1.0)
        acc, omin, omax = quantized_conv(
            qx, w8, None, dmin, dmax, wmin, wmax, None, None,
            kernel=(3, 3), pad=(1, 1), num_filter=8, no_bias=True)
        r8, rmin, rmax = requantize(acc, omin, omax,
                                    min_calib_range=-4.0,
                                    max_calib_range=4.0)
        return dequantize(r8, rmin, rmax)

    jitted = jax.jit(chain)
    hlo = jitted.lower(x, q8, wmin, wmax).compile().as_text()
    # the convolution must be the INTEGER one (s32 accumulator) and no
    # float convolution may exist anywhere — i.e. the chain never
    # regressed to dequantize-then-conv-in-float. Operand-level s8
    # can't be asserted on CPU (the backend folds the s8->s32 convert
    # into the operand fusions — it has no int8 conv kernels); the
    # int8 product's speed on the TPU is not measured.
    import re
    assert re.search(r"=\s*s32\[[^\]]*\]\S*\s+convolution\(", hlo), \
        "no s32-accumulator convolution in compiled HLO"
    assert not re.search(r"=\s*(f32|f16|bf16)\[[^\]]*\]\S*\s+convolution\(",
                         hlo), "a float convolution crept into the chain"

    got = onp.asarray(jitted(x, q8, wmin, wmax))
    ref = onp.asarray(jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))
    err = onp.abs(got - ref).max()
    assert err < 0.08, f"int8 chain error {err} vs fp32 conv"
