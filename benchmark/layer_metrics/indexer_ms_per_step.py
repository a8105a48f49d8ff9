"""Kernels: device time a step, forward and backward, of everything
under an attention layer's ``indexer`` scope: the lightning indexer's
projections, its key norm and rotation, its scores, the top-k and the
selection, and in the backward pass the scores again and the KL loss's
gradient. Source: the device trace, classed by the step's own HLO
metadata. Nothing where the HLO names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("attn", "indexer"))
