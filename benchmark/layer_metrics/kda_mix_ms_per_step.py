"""Kernels: device time a step, forward and backward, of the events
under ``kda`` / ``mix``: what turns a delta-rule mixer's projections
into the recurrence's operands (the three causal filters with their
SiLU, the l2 norms of q and k, the decay's gate, beta), without the
projections and without the recurrence. With ``kda_scan_ms_per_step``
it splits ``kda_ms_per_step`` three ways: what is left under ``kda`` is
the seven projections and the gated norm of the output. Source: the
device trace, classed by the step's own HLO metadata. Nothing where the
HLO names no such scope."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("kda", "mix"))
