"""Smoke tier for the detection, segmentation and capsule examples (ref:
the reference's example/rcnn, example/ssd, example/fcn-xs,
example/capsnet, example/stochastic-depth). Each runs end to end with
tiny settings and asserts its learning signal."""
import numpy as onp


def test_stochastic_depth_example(load_example):
    acc, skipped, total = load_example("stochastic_depth/sd_resnet.py").main(
        ["--steps", "150"])
    assert skipped > 0, "no blocks were ever dropped in train mode"
    assert acc > 0.45  # 4-way chance is 0.25


def test_fcn_segmentation_example(load_example):
    miou = load_example("fcn_xs/fcn_seg.py").main(["--steps", "120"])
    assert miou > 0.3  # untrained fg-IoU ~0


def test_capsnet_example_routing_trains(load_example):
    acc = load_example("capsnet/capsnet.py").main(["--steps", "80"])
    assert acc > 0.8


def test_ssd_map_metric(load_example):
    """MApMetric / VOC07MApMetric (ref: example/ssd/evaluate/
    eval_metric.py) on a constructed case with a known answer."""
    m = load_example("ssd/eval_metric.py")
    import numpy as onp
    from mxnet_tpu import nd

    # image 0: one gt of class 0; detections: one perfect hit (0.9),
    # one false positive (0.8). image 1: one gt class 1, missed.
    labels = nd.array(onp.array([
        [[0, 0.1, 0.1, 0.5, 0.5], [-1, 0, 0, 0, 0]],
        [[1, 0.2, 0.2, 0.6, 0.6], [-1, 0, 0, 0, 0]],
    ], "float32"))
    preds = nd.array(onp.array([
        [[0, 0.9, 0.1, 0.1, 0.5, 0.5], [0, 0.8, 0.6, 0.6, 0.9, 0.9]],
        [[-1, 0, 0, 0, 0, 0], [-1, 0, 0, 0, 0, 0]],
    ], "float32"))

    met = m.MApMetric(ovp_thresh=0.5)
    met.update([labels], [preds])
    name, value = met.get()
    # class 0: AP=1.0 (tp at rank 1 covers the only gt; the later fp
    # does not reduce the envelope), class 1: AP=0 -> mAP=0.5
    assert name == "mAP" and abs(value - 0.5) < 1e-6, (name, value)

    voc = m.VOC07MApMetric(ovp_thresh=0.5)
    voc.update([labels], [preds])
    _, v7 = voc.get()
    assert abs(v7 - 0.5) < 0.05  # 11-point AP of the same case


def test_ssd_map_difficult_gts_ignored(load_example):
    """Detections matching a difficult gt are ignored (not fp, gt not
    consumed) — the VOC protocol (ref: eval_metric.py difficult path)."""
    m = load_example("ssd/eval_metric.py")
    import numpy as onp
    from mxnet_tpu import nd

    labels = nd.array(onp.array([[
        [0, 0.1, 0.1, 0.5, 0.5, 1.0],   # difficult
        [0, 0.6, 0.6, 0.9, 0.9, 0.0],
    ]], "float32"))
    preds = nd.array(onp.array([[
        [0, 0.9, 0.1, 0.1, 0.5, 0.5],   # on difficult -> ignored
        [0, 0.8, 0.1, 0.1, 0.5, 0.5],   # also on difficult -> ignored
        [0, 0.7, 0.6, 0.6, 0.9, 0.9],   # tp on the normal gt
    ]], "float32"))
    met = m.MApMetric(ovp_thresh=0.5)
    met.update([labels], [preds])
    _, value = met.get()
    assert abs(value - 1.0) < 1e-6, value
    met.get_global()  # base-class contract intact after reset override


def test_rcnn_rpn_demo_trains(load_example):
    """Two-stage detection: RPN objectness + Proposal + ROIPooling +
    region classifier (ref: example/rcnn). Also regression-guards the
    ROIPooling clip fix (out-of-bounds rois used to pool -inf)."""
    first, last = load_example("rcnn/rpn_demo.py").main(["--steps", "80"])
    assert onp.isfinite(last) and last < first * 0.8
