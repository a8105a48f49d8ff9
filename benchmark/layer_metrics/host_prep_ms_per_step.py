"""Trainer host work: wall time of the program's ``step.prep`` spans
(the hyper scalars, the gather of parameters and state, the step's key)
over the window's steps, in ms a step. Source: the program's own span
trees (``program_span``)."""
from benchmark import span_reduce


def read(run):
    return span_reduce.span_ms_per_step(span_reduce.step_trees(run),
                                        "step.prep")
