"""Kernels: device time a step, forward and backward, of everything
under an attention layer's ``sparse`` scope: the attention products over
the keys the indexer selected, the masked softmax, and their backward.
Source: the device trace, classed by the step's own HLO metadata.
Nothing where the HLO names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("attn", "sparse"))
