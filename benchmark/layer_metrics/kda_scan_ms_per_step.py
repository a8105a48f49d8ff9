"""Kernels: device time a step, forward and backward, of the events
under ``kda`` / ``scan``: the gated delta rule alone (``ops.kda``: the
chunks' products, the triangular solve, the scan over the chunks, and in
the backward pass their recomputation), without the projections, the
filters and the norms around it. Source: the device trace, classed by
the step's own HLO metadata. Nothing where the HLO names no such
scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("kda", "scan"))
