"""Kernels: device time a step, forward and backward, of the events
under ``attn`` / ``window``: the two attention products and the softmax
of the sliding layers (``models/laguna.py`` opens the scope around
``ops.banded_attention``; projections, rotary and the gate lie under
``attn`` alone). Source: the device trace, classed by the step's own HLO
metadata."""
from benchmark import scope_paths


def read(run):
    return scope_paths.ms_per_step(run, ("attn", "window"))
