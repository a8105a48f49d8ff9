"""Set-up: the XLA compile of the fused step, or its read and load from
the persistent compile cache, in s: the sum of the program's histogram
``step_program_compile_seconds`` (the outermost backend compile under
the dispatch that builds the step; the span ``step.compile``). Nothing
where the program keeps no such histogram (an older commit).

The reader also puts on record, in the result's ``detail``, where the
program came from (``step_compile_source``: ``cache`` where every build
of the step was a read of the cache, ``compiler`` where none was,
``mixed`` otherwise; the histogram ``step_program_cache_retrieval_seconds``
counts the reads) and the seconds of those reads
(``cache_retrieval_s``)."""
from benchmark.layer_metrics.step_trace_s import program_histogram


def read(run):
    found = program_histogram("step_program_compile_seconds")
    if found is None:
        return None
    reads, read_s = program_histogram(
        "step_program_cache_retrieval_seconds") or (0, 0.0)
    detail = run.result["detail"]
    detail["step_compile_source"] = (
        "compiler" if not reads else "cache" if reads == found[0]
        else "mixed")
    detail["cache_retrieval_s"] = read_s
    return found[1]
