"""Set-up: the program's eager set-up, in s: the sum of its histogram
``setup_eager_seconds``, the wall time of the outermost of its spans
``block.initialize``, ``block.forward`` (an outermost eager call of a
block: the shape-resolving forward), ``step.resolve`` and
``operator_tune.measure``. Nothing where the program keeps no such
histogram (an older commit).

The reader also puts on record, in the result's ``detail``, the programs
jax built under those spans (``eager_programs_built``: the counter
``eager_programs_built_total``), the seconds their outermost traces,
lowerings and compiles took (``eager_program_build_s``:
``eager_program_build_seconds``) and, where ``operator_tune`` measured,
its seconds (``operator_tune_s``: ``operator_tune_measure_seconds``)."""
from benchmark.layer_metrics.step_trace_s import program_histogram


def read(run):
    found = program_histogram("setup_eager_seconds")
    if found is None:
        return None
    from mxnet_tpu.telemetry import metrics
    detail = run.result["detail"]
    built = metrics.all_metrics().get("eager_programs_built_total")
    detail["eager_programs_built"] = built.value() if built else 0
    detail["eager_program_build_s"] = (
        program_histogram("eager_program_build_seconds") or (0, 0.0))[1]
    tune = program_histogram("operator_tune_measure_seconds")
    if tune and tune[0]:
        detail["operator_tune_s"] = tune[1]
    return found[1]
