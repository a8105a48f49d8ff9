"""Kernels: device time a step, forward and backward, of the events
under ``mamba`` / ``scan``: the SSD alone (``ops.ssd``: the chunks'
products, the scan over the chunks, and in the backward pass their
recomputation) with the ``D`` skip, without the projections, the filter
and the gated norm around it. Source: the device trace, classed by the
step's own HLO metadata. Nothing where the HLO names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("mamba", "scan"))
