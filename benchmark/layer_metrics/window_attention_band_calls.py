"""Kernels: the traced calls of the sliding-window layers that took the
repo's band kernel (``ops/banded_attention.py``): the program's counter
``attention_traced_total.band``, bumped by ``LagunaAttention`` once a
traced call beside ``.kernel`` (jax's splash kernel: the full layers,
and a window the band kernel does not take) and ``.dense`` (the XLA
composition). A layer is traced by the shape-resolving forward and by
the step: 6 in the cell (3 sliding layers). 0 where the program counts
its attention calls and none took the band kernel; nothing where it
keeps no such counter (the parent).

The reader also puts all three counters on record, in the result's
``detail`` (``attention_backend``)."""
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    traced = program_gauges("attention_traced_total.")
    if not traced:
        return None
    run.result["detail"]["attention_backend"] = traced
    return traced.get("band", 0)
