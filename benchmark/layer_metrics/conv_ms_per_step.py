"""Kernels: device time a step, forward and backward, of everything
under a short-convolution mixer's ``conv`` scope: the projection to
three times the width, the gated convolution itself and the output
projection. Source: the device trace, classed by the step's own HLO
metadata. Nothing where the HLO names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("conv",))
