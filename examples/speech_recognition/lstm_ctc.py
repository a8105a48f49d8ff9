#!/usr/bin/env python
"""Speech recognition demo: bi-LSTM acoustic model trained with CTC
over synthetic spectrograms (ref capability: example/speech_recognition
— deepspeech-style LSTM + warp-CTC training).

Each utterance is a sequence of frame vectors where "phoneme" k emits
frames drawn around one of 6 template vectors; the label is the
phoneme sequence without alignments. Asserts the CTC loss falls.
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

N_PHONE, FRAMES_PER, N_IN, T_LABEL = 6, 3, 12, 4


def make_batch(rs, templates, n):
    T = T_LABEL * FRAMES_PER
    xs = onp.zeros((n, T, N_IN), "float32")
    labels = rs.randint(0, N_PHONE, (n, T_LABEL))
    for i in range(n):
        for j, ph in enumerate(labels[i]):
            for f in range(FRAMES_PER):
                xs[i, j * FRAMES_PER + f] = (
                    templates[ph] + 0.1 * rs.randn(N_IN))
    return nd.array(xs), nd.array((labels + 1).astype("float32"))


class AcousticModel(gluon.HybridBlock):
    def __init__(self, hidden=32, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.lstm = gluon.rnn.LSTM(hidden, bidirectional=True,
                                       layout="NTC")
            self.out = gluon.nn.Dense(N_PHONE + 1, flatten=False)

    def hybrid_forward(self, F, x):
        return self.out(self.lstm(x))  # (B, T, N_PHONE+1)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--tpu", action="store_true")
    args = p.parse_args(argv)

    rs = onp.random.RandomState(0)
    templates = rs.randn(N_PHONE, N_IN).astype("float32") * 2
    net = AcousticModel()
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})

    # one compiled program a step (forward, CTC, backward, Adam). Run
    # op by op, every step compiles the LSTM's and the CTC's scans
    # again: a `lax.scan` outside a jit is a new program each call
    fused = trainer.fuse_step(net, gluon.loss.CTCLoss(layout="NTC"))

    first = last = None
    for step in range(args.steps):
        x, y = make_batch(rs, templates, args.batch)
        val = float(nd.mean(fused.step(x, y)).asscalar())
        if first is None:
            first = val
        last = val
    print(f"first_ctc={first:.4f} last_ctc={last:.4f}")
    return first, last


if __name__ == "__main__":
    main()
