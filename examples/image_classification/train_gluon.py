#!/usr/bin/env python
"""Gluon imperative training with a model-zoo network.

Mirrors the reference's example/gluon/image_classification.py: pick any
model_zoo architecture, train with Trainer + autograd on (synthetic by
default) image batches, evaluate accuracy. `--hybridize` compiles the
whole forward to one XLA program.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

if "--tpu" not in sys.argv:
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.model_zoo.vision import get_model


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet18_v1",
                   help="any model_zoo name (get_model)")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--hybridize", action="store_true")
    p.add_argument("--tpu", action="store_true")
    args = p.parse_args(argv)

    net = get_model(args.model, classes=args.classes,
                    **({"thumbnail": True}
                       if args.model.startswith("resnet") else {}))
    net.initialize(mx.initializer.Xavier())
    S = args.image_size
    if args.hybridize:
        net.hybridize()
        # resolve the deferred shapes outside record(): the first call
        # runs op by op, and under record() so would its backward pass,
        # one small program an operator and shape
        net(nd.zeros((args.batch_size, 3, S, S)))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    rs = onp.random.RandomState(0)
    # synthetic but learnable: class k brightens a k-dependent stripe
    def batch():
        x = rs.rand(args.batch_size, 3, S, S).astype("float32") * 0.3
        y = rs.randint(0, args.classes, args.batch_size)
        for i, cls in enumerate(y):
            x[i, :, (cls * S // args.classes):(cls * S // args.classes)
              + 3, :] += 0.5
        return nd.array(x), nd.array(y.astype("float32"))

    t0 = time.time()
    for step in range(args.steps):
        x, y = batch()
        with autograd.record():
            out = net(x)
            loss = loss_fn(out, y)
        loss.backward()
        trainer.step(args.batch_size)
        metric.update(y, out)
        if step % 10 == 0:
            name, acc = metric.get()
            print(f"step {step}: loss {float(loss.mean().asscalar()):.3f} "
                  f"{name} {acc:.3f}")
    name, acc = metric.get()
    dt = time.time() - t0
    print(f"{args.model}: {name} {acc:.3f} after {args.steps} steps, "
          f"{args.steps * args.batch_size / dt:.1f} img/s")
    return acc


if __name__ == "__main__":
    main()
