"""Kernels: device time a step, forward and backward, of everything
under a Mamba-2 mixer's ``mamba`` scope: its two projections, the
filter with its bias and SiLU, the SSD with its ``D`` skip and the gated
norm, and in the backward pass their recomputation. Source: the device
trace, classed by the step's own HLO metadata. Nothing where the HLO
names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("mamba",))
