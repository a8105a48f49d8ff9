"""Kernels: the number of expert layers whose buffer of sorted rows was
too small for the run's first batch, so that the layer took further
passes of it: the program's own gauges ``moe_rows_overflow.<layer>``
(the rows routed to the experts held here past the buffer's rows),
filled by the expert layers in the set-up forward. 0 is the healthy
reading: the buffer holds twice what uniform routing sends here, and a
further pass costs a layer's whole expert computation again. Nothing
where the program keeps no such gauge (its buffer then holds the worst
case).

The reader also puts on record, in the result's ``detail``, how full
the fullest layer's buffer was on that batch (``moe_buffer_fill``: the
gauges ``moe_rows_routed.<layer>`` over ``moe_buffer_rows.<layer>``;
above 1 the layer overflowed)."""
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    overflow = program_gauges("moe_rows_overflow.")
    if not overflow:
        return None
    routed = program_gauges("moe_rows_routed.")
    fills = [routed[label] / rows for label, rows in
             program_gauges("moe_buffer_rows.").items()
             if rows and label in routed]
    if fills:
        run.result["detail"]["moe_buffer_fill"] = max(fills)
    return sum(1 for rows in overflow.values() if rows > 0)
