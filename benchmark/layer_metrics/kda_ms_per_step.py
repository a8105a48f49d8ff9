"""Kernels: device time a step, forward and backward, of everything
under a delta-rule mixer's ``kda`` scope: its six projections, the three
filters, the norms of q and k, the decay's gate, the recurrence itself
and the gated norm of its output. Source: the device trace, classed by
the step's own HLO metadata. Nothing where the HLO names no such
scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("kda",))
