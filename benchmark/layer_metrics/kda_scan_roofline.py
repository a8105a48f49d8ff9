"""Kernels: the least time the chip could take over the gated delta rule
alone (the family's ``kda_scan_work``: the recurrence's own
multiply-adds a token a head, forward once and backward twice, against
the matrix unit's peak, or its five operands read and its result written
once forward, they and the result's gradient read and five gradients
written once backward, against the HBM rate, whichever is larger), over
the device time under ``kda`` / ``scan``, in percent. The work is the
same whatever implements the scan (token by token, in chunks, in a
kernel), and no implementation can do less, so the share cannot pass
100: the chunks' extra products, the triangular solve and the backward
pass's recomputation count as time and not as work.

The reader also puts on record, in the result's ``detail``, what the
program's own counters say of the cell's mechanisms: ``kda_backend``
(the counters under ``kda_traced_total.``, ``.chunked`` today, one bump
a traced mixer: a layer is traced by the shape-resolving forward and by
the step) and ``moe_group_limit_changed_choice`` (the gauges of that
name, a layer: the share of the first batch's tokens whose chosen
experts are not the k largest biased scores of all the experts). Nothing
of either where the program keeps none."""
from benchmark import scope_paths
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    for prefix, key in (("kda_traced_total.", "kda_backend"),
                        ("moe_group_limit_changed_choice.",
                         "moe_group_limit_changed_choice")):
        found = program_gauges(prefix)
        if found:
            run.result["detail"][key] = found
    work = getattr(run.family, "kda_scan_work", None)
    if work is None:
        return None
    flops, nbytes = work(run.sizes, run.traffic)
    return scope_paths.roofline_pct(run, ("kda", "scan"), flops, nbytes)
