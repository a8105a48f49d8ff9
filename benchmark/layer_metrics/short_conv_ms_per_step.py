"""Kernels: device time a step, forward and backward, of the events
under ``conv`` / ``mix``: the gated short convolution alone (the product
of two thirds of the projection, the three taps, the gate by the third),
without the two projections around it. Source: the device trace, classed
by the step's own HLO metadata. XLA may fuse part of the operator into a
neighbouring product, which then carries the neighbour's name: what is
left under ``mix`` is what costs a pass of its own. Nothing where the
HLO names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("conv", "mix"))
