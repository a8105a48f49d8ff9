"""Step program: device time a step of the traced events whose HLO
instruction lies under ``transpose(jvp(forward))``: the backward pass.
Source: the device trace, classed by the step's own HLO metadata
(``span_reduce.phase_seconds``)."""
from benchmark import span_reduce


def read(run):
    return span_reduce.phase_ms_per_step(run, "bwd")
