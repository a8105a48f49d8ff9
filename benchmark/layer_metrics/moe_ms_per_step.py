"""Kernels: device time a step, forward and backward, of everything
under an expert layer's ``moe`` scope: router, sort and gather, the
grouped products, the combine and the shared expert. Source: the device
trace, classed by the step's own HLO metadata."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("moe",))
