"""Set-up: the lowering of the fused step's jaxpr to MLIR, in s: the sum
of the program's histogram ``step_program_lower_seconds`` (the outermost
lowering under the dispatch that builds the step; the span
``step.lower``). Nothing where the program keeps no such histogram (an
older commit)."""
from benchmark.layer_metrics.step_trace_s import program_histogram


def read(run):
    found = program_histogram("step_program_lower_seconds")
    return None if found is None else found[1]
