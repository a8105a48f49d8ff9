#!/usr/bin/env python
"""Captcha OCR: a conv encoder over digit-strip images decoded with
CTC (ref capability: example/captcha — CNN + CTCLoss sequence
recognition without per-position alignment).

Synthetic captchas: each image is a horizontal strip of 4 "digits",
each digit an 8x8 intensity glyph drawn from 5 classes. The conv
encoder reads the strip into per-column logits; CTCLoss aligns them to
the unpadded label sequence. Asserts the CTC loss falls.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

if "--tpu" not in sys.argv:
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as onp

from mxnet_tpu import gluon, nd

N_DIGIT, N_CLS, G = 4, 5, 8  # digits per strip, classes, glyph size


def _glyphs(rs):
    # five fixed random glyphs, the "font"
    return rs.uniform(0.2, 1.0, (N_CLS, G, G)).astype("float32")


def make_batch(rs, glyphs, n):
    imgs = onp.zeros((n, 1, G, N_DIGIT * G), "float32")
    labels = rs.randint(0, N_CLS, (n, N_DIGIT))
    for i in range(n):
        for j, d in enumerate(labels[i]):
            imgs[i, 0, :, j * G:(j + 1) * G] = glyphs[d]
    imgs += 0.05 * rs.randn(*imgs.shape).astype("float32")
    # CTC labels are 1-based (0 is blank)
    return nd.array(imgs), nd.array((labels + 1).astype("float32"))


class CaptchaNet(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.conv = gluon.nn.HybridSequential()
            self.conv.add(
                gluon.nn.Conv2D(8, 3, padding=1, activation="relu"),
                gluon.nn.MaxPool2D((2, 2)),
                gluon.nn.Conv2D(16, 3, padding=1, activation="relu"),
                gluon.nn.MaxPool2D((G // 2, 1)))  # collapse height
            self.out = gluon.nn.Dense(N_CLS + 1, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.conv(x)                       # (B, C, 1, W)
        h = h.squeeze(axis=2).transpose((0, 2, 1))  # (B, W, C)
        return self.out(h)                     # (B, W, N_CLS+1)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--tpu", action="store_true")
    args = p.parse_args(argv)

    rs = onp.random.RandomState(0)
    glyphs = _glyphs(rs)
    net = CaptchaNet()
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 2e-3})

    # one compiled program a step (forward, CTC, backward, Adam). Run
    # op by op, every step compiles the CTC's scan again: a `lax.scan`
    # outside a jit is a new program each call. CTCLoss takes the
    # (B, T, C) logits with the blank at 0
    fused = trainer.fuse_step(net, gluon.loss.CTCLoss(layout="NTC"))

    first = last = None
    for step in range(args.steps):
        x, y = make_batch(rs, glyphs, args.batch)
        val = float(nd.mean(fused.step(x, y)).asscalar())
        if first is None:
            first = val
        last = val
    print(f"first_ctc={first:.4f} last_ctc={last:.4f}")
    return first, last


if __name__ == "__main__":
    main()
