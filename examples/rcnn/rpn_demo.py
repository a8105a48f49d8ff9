#!/usr/bin/env python
"""Two-stage detection demo: backbone -> RPN -> Proposal -> ROIPooling
-> per-region classifier (the reference's example/rcnn capability in
miniature; ops: src/operator/contrib/proposal.cc, roi_pooling.cc).

Synthetic task: each image contains one bright square on a dark
background. The RPN objectness head learns where it is; `Proposal`
decodes + NMS-filters anchors into regions; `ROIPooling` crops
features for a classifier that predicts the square's class (its
brightness band). Both losses must fall.
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

from mxnet_tpu import autograd, gluon, nd


S, FEAT = 32, 8           # image size, feature-map size (stride 4)
N_ANCHOR = 1              # one square anchor per feature cell
N_CLS = 2                 # brightness band of the square


def make_batch(rs, n):
    imgs = onp.zeros((n, 1, S, S), "float32")
    centers = onp.zeros((n, 2), "int64")
    cls = rs.randint(0, N_CLS, n)
    for i in range(n):
        cy, cx = rs.randint(6, S - 6, 2)
        bright = 0.5 if cls[i] == 0 else 1.0
        imgs[i, 0, cy - 4:cy + 4, cx - 4:cx + 4] = bright
        centers[i] = (cy, cx)
    # RPN objectness target: 1 at the feature cell holding the center
    obj = onp.zeros((n, FEAT * FEAT), "float32")
    obj[onp.arange(n), (centers[:, 0] // 4) * FEAT + centers[:, 1] // 4] = 1
    return (nd.array(imgs), nd.array(obj),
            nd.array(cls.astype("float32")))


class RPNDemo(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.backbone = gluon.nn.HybridSequential()
            self.backbone.add(
                gluon.nn.Conv2D(8, 3, strides=2, padding=1,
                                activation="relu"),
                gluon.nn.Conv2D(8, 3, strides=2, padding=1,
                                activation="relu"))
            # 2 channels per anchor: background/foreground scores
            self.rpn_cls = gluon.nn.Conv2D(2 * N_ANCHOR, 1)
            self.rpn_bbox = gluon.nn.Conv2D(4 * N_ANCHOR, 1)
            self.head = gluon.nn.Dense(N_CLS)

    def hybrid_forward(self, F, x, im_info):
        """Objectness scores of every feature cell, and class logits of
        the four regions `Proposal` keeps of each image (rows 4*i ..
        4*i+3 belong to image i)."""
        feat = self.backbone(x)
        rpn_cls, rpn_bbox = self.rpn_cls(feat), self.rpn_bbox(feat)
        scores = rpn_cls.reshape((0, 2, -1)).transpose((0, 2, 1))
        # decode proposals from the RPN outputs and pool
        cls_prob = F.softmax(rpn_cls.reshape((0, 2, FEAT, FEAT)), axis=1)
        rois = F.Proposal(
            cls_prob, rpn_bbox, im_info, feature_stride=4,
            scales=(2,), ratios=(1.0,), rpn_pre_nms_top_n=16,
            rpn_post_nms_top_n=4, threshold=0.7, rpn_min_size=4)
        pooled = F.ROIPooling(feat, rois, pooled_size=(4, 4),
                              spatial_scale=0.25)
        logits = self.head(pooled.reshape((-1, 8 * 4 * 4)))
        return scores.reshape((-1, 2)), logits


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--tpu", action="store_true")
    args = p.parse_args(argv)

    rs = onp.random.RandomState(0)
    net = RPNDemo()
    net.initialize()
    # one program for the forward pass and one for its backward: run op
    # by op, `Proposal` and `ROIPooling` (a vmap over regions, a Python
    # loop over pooling bins) are traced and compiled again every step
    net.hybridize()
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 2e-3})
    im_info = nd.array(onp.tile([S, S, 1.0], (args.batch, 1))
                       .astype("float32"))

    first = last = None
    for step in range(args.steps):
        x, obj, cls = make_batch(rs, args.batch)
        with autograd.record():
            scores, logits = net(x, im_info)
            # objectness loss over feature cells, class loss over regions
            rpn_loss = sce(scores, obj.reshape((-1,)))
            cls_loss = sce(logits, nd.repeat(cls, repeats=4))
            loss = rpn_loss.mean() + cls_loss.mean()
        loss.backward()
        trainer.step(args.batch)
        val = float(loss.asscalar())
        if first is None:
            first = val
        last = val
    print(f"first_loss={first:.4f} last_loss={last:.4f}")
    return first, last


if __name__ == "__main__":
    main()
