"""Set-up: the jaxpr trace of the fused step's forward, backward and
update, in s: the sum of the program's histogram
``step_program_trace_seconds``, one observation a signature-cache miss
(the outermost trace jax reports under the dispatch that builds the
step; the span ``step.trace``). Nothing where the program keeps no such
histogram (an older commit).

The reader also puts on record, in the result's ``detail``, how many
misses built the step (``step_misses``)."""


def program_histogram(name):
    """``(count, sum)`` of the program's telemetry histogram ``name``;
    None where the program keeps none (an older commit) or has no
    telemetry at all. Read without creating it."""
    try:
        from mxnet_tpu.telemetry import metrics
    except ImportError:
        return None
    h = metrics.all_metrics().get(name)
    if h is None:
        return None
    return h.count, h.sum


def read(run):
    found = program_histogram("step_program_trace_seconds")
    if found is None:
        return None
    run.result["detail"]["step_misses"] = found[0]
    return found[1]
