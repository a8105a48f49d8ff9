"""gluon.contrib parity tier
(ref: python/mxnet/gluon/contrib/ — nn basic layers, conv/variational
RNN cells, deformable conv, IntervalSampler, Estimator;
tests/python/unittest/test_gluon_contrib.py is the reference model)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import contrib, nn


def test_hybrid_concurrent_concats_branches():
    from mxnet_tpu.gluon.contrib.nn import HybridConcurrent, Identity
    c = HybridConcurrent(axis=1)
    c.add(nn.Dense(4, flatten=False), Identity(), nn.Dense(3,
                                                           flatten=False))
    c.initialize()
    x = nd.array(onp.random.RandomState(0).rand(2, 5).astype("float32"))
    out = c(x)
    assert out.shape == (2, 4 + 5 + 3)
    # the identity branch is the input itself
    assert onp.allclose(out.asnumpy()[:, 4:9], x.asnumpy())


def test_concurrent_block_variant():
    from mxnet_tpu.gluon.contrib.nn import Concurrent, Identity
    c = Concurrent(axis=-1)
    c.add(Identity(), Identity())
    out = c(nd.ones((2, 3)))
    assert out.shape == (2, 6)


def test_pixel_shuffle_2d_matches_numpy():
    from mxnet_tpu.gluon.contrib.nn import PixelShuffle2D
    f1, f2 = 2, 3
    x = onp.arange(1 * 2 * f1 * f2 * 4 * 5, dtype="float32").reshape(
        (1, 2 * f1 * f2, 4, 5))
    want = x.reshape((1, 2, f1, f2, 4, 5)).transpose(
        (0, 1, 4, 2, 5, 3)).reshape((1, 2, 4 * f1, 5 * f2))
    layer = PixelShuffle2D((f1, f2))
    got = layer(nd.array(x)).asnumpy()
    assert got.shape == want.shape and onp.allclose(got, want)


def test_pixel_shuffle_1d_3d_shapes():
    from mxnet_tpu.gluon.contrib.nn import PixelShuffle1D, PixelShuffle3D
    assert PixelShuffle1D(3)(nd.zeros((2, 6, 8))).shape == (2, 2, 24)
    assert PixelShuffle3D(2)(
        nd.zeros((1, 16, 2, 3, 4))).shape == (1, 2, 4, 6, 8)


def test_sparse_embedding_grad_flows():
    from mxnet_tpu.gluon.contrib.nn import SparseEmbedding
    emb = SparseEmbedding(10, 4)
    emb.initialize()
    tok = nd.array(onp.array([[1, 2], [3, 1]]), dtype="int32")
    with autograd.record():
        out = emb(tok)
        loss = out.sum()
    loss.backward()
    g = emb.weight.grad().asnumpy()
    assert out.shape == (2, 2, 4)
    assert onp.abs(g[1]).sum() > 0 and onp.abs(g[9]).sum() == 0


def test_sync_batch_norm_forward():
    from mxnet_tpu.gluon.contrib.nn import SyncBatchNorm
    bn = SyncBatchNorm(in_channels=3, num_devices=2)
    bn.initialize()
    x = nd.array(onp.random.RandomState(0).rand(4, 3, 5, 5)
                 .astype("float32"))
    with autograd.record():
        out = bn(x)
    got = out.asnumpy()
    assert got.shape == x.shape
    assert abs(got.mean()) < 1e-2  # normalized


def test_variational_dropout_mask_fixed_across_steps():
    from mxnet_tpu.gluon.contrib.rnn import VariationalDropoutCell
    from mxnet_tpu.gluon.rnn import RNNCell
    cell = VariationalDropoutCell(RNNCell(8, input_size=8),
                                  drop_outputs=0.5)
    cell.base_cell.initialize()
    x = nd.ones((20, 3, 8))  # TNC steps
    states = cell.begin_state(batch_size=3)
    with autograd.record():
        out1, states = cell(x[0], states)
        out2, states = cell(x[1], states)
    # the same output mask is applied at every step: zeros line up
    z1 = out1.asnumpy() == 0.0
    z2 = out2.asnumpy() == 0.0
    assert z1.any(), "dropout produced no zeros at p=0.5"
    assert (z1 == z2).all()
    # reset samples a fresh mask
    cell.reset()
    assert cell._output_mask is None


def test_lstmp_cell_projection_shapes():
    from mxnet_tpu.gluon.contrib.rnn import LSTMPCell
    cell = LSTMPCell(hidden_size=16, projection_size=6, input_size=5)
    cell.initialize()
    x = nd.zeros((4, 5))
    states = cell.begin_state(batch_size=4)
    assert states[0].shape == (4, 6) and states[1].shape == (4, 16)
    out, new_states = cell(x, states)
    assert out.shape == (4, 6)
    assert new_states[0].shape == (4, 6) and new_states[1].shape == (4, 16)
    outs, _ = cell.unroll(3, nd.zeros((4, 3, 5)), merge_outputs=True)
    assert outs.shape == (4, 3, 6)


@pytest.mark.parametrize("cls,states_n", [("Conv2DRNNCell", 1),
                                          ("Conv2DLSTMCell", 2),
                                          ("Conv2DGRUCell", 1)])
def test_conv_rnn_cells(cls, states_n):
    cell_cls = getattr(contrib.rnn, cls)
    cell = cell_cls(input_shape=(4, 8, 8), hidden_channels=6,
                    i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    cell.initialize()
    x = nd.array(onp.random.RandomState(0).rand(2, 4, 8, 8)
                 .astype("float32"))
    states = cell.begin_state(batch_size=2)
    assert len(states) == states_n
    out, new_states = cell(x, states)
    assert out.shape == (2, 6, 8, 8)
    assert all(s.shape == (2, 6, 8, 8) for s in new_states)
    # spatial dims stable across steps
    out2, _ = cell(x, new_states)
    assert out2.shape == out.shape


def test_conv1d_3d_cells_shapes():
    c1 = contrib.rnn.Conv1DLSTMCell((2, 10), 4, 3, 3, i2h_pad=1)
    c1.initialize()
    out, st = c1(nd.zeros((2, 2, 10)), c1.begin_state(batch_size=2))
    assert out.shape == (2, 4, 10)
    c3 = contrib.rnn.Conv3DGRUCell((2, 4, 4, 4), 3, 3, 3, i2h_pad=1)
    c3.initialize()
    out, st = c3(nd.zeros((1, 2, 4, 4, 4)), c3.begin_state(batch_size=1))
    assert out.shape == (1, 3, 4, 4, 4)


def test_deformable_convolution_zero_offsets_match_plain_conv():
    from mxnet_tpu.gluon.contrib.cnn import DeformableConvolution
    layer = DeformableConvolution(5, kernel_size=3, padding=1,
                                  in_channels=4)
    layer.initialize()
    x = nd.array(onp.random.RandomState(0).rand(2, 4, 7, 7)
                 .astype("float32"))
    out = layer(x)
    assert out.shape == (2, 5, 7, 7)
    # offsets are zero-init -> result equals the plain convolution
    w = layer.weight.data()
    b = layer.bias.data()
    ref = nd.Convolution(x, w, b, kernel=(3, 3), pad=(1, 1), stride=(1, 1),
                         num_filter=5)
    assert onp.allclose(out.asnumpy(), ref.asnumpy(), atol=1e-4)


def test_interval_sampler():
    from mxnet_tpu.gluon.contrib.data import IntervalSampler
    assert list(IntervalSampler(10, 3)) == [0, 3, 6, 9, 1, 4, 7,
                                            2, 5, 8]
    assert list(IntervalSampler(10, 3, rollover=False)) == [0, 3, 6, 9]
    assert len(IntervalSampler(10, 3)) == 10
    assert len(IntervalSampler(10, 3, rollover=False)) == 4


def _toy_data(n=64):
    rs = onp.random.RandomState(0)
    x = rs.rand(n, 8).astype("float32")
    y = (x.sum(axis=1) > 4).astype("float32")
    return nd.array(x), nd.array(y)


def test_estimator_fit_and_early_stopping(tmp_path):
    from mxnet_tpu import gluon, metric
    from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                                   EarlyStoppingHandler,
                                                   Estimator)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize()
    x, y = _toy_data()
    loader = gluon.data.DataLoader(
        gluon.data.ArrayDataset(x, y), batch_size=16)
    acc = metric.Accuracy()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics=[acc])
    ckpt = CheckpointHandler(str(tmp_path), monitor=est.loss_metric,
                             epoch_period=1)
    est.fit(loader, epochs=3, event_handlers=[ckpt])
    assert acc.get()[1] > 0.5
    assert any(f.endswith(".params") for f in os.listdir(tmp_path))

    # early stopping on a never-improving metric stops before max_epoch
    stopper = EarlyStoppingHandler(monitor=est.loss_metric, mode="max",
                                   patience=1)
    est2 = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     train_metrics=[metric.Accuracy()])
    est2.fit(loader, epochs=50, event_handlers=[stopper])
    assert stopper.stopped_epoch is not None and stopper.stopped_epoch < 50


@pytest.mark.parametrize("name,size,classes", [
    ("inceptionv3", 299, 13), ("mobilenetv2_0.75", 224, 7),
    ("mobilenetv2_0.25", 224, 7)])
def test_model_zoo_inception_and_mobilenetv2_variants(name, size,
                                                      classes):
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    net = get_model(name, classes=classes)
    net.initialize()
    out = net(nd.array(onp.random.RandomState(0)
                       .rand(1, 3, size, size).astype("float32")))
    assert out.shape == (1, classes)
