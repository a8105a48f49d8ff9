"""Kernels: device time a step, forward and backward, of the events
under ``attn`` / ``full``: the two attention products and the softmax of
the full (causal, unwindowed) layers. Source: the device trace, classed
by the step's own HLO metadata."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("attn", "full"))
