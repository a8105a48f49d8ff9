"""Kernels: the least time the chip could take over the SSD alone,
whatever implements it (the family's ``ssd_scan_work``: the chunk
algebra's multiply-adds at the configuration's chunk, forward once and
backward twice, against the matrix unit's peak, or x, B, C and ``dt``
read and y written once forward, they and y's gradient read and four
gradients written once backward, against the HBM rate, whichever is
larger), over the device time under ``mamba`` / ``scan``, in percent.
The backward pass's recomputation and the ``D`` skip count as time and
not as work, so the share cannot pass 100.

The reader also puts on record, in the result's ``detail``, the
program's own counters ``ssd_backend`` (the counters under
``ssd_traced_total.``, ``.chunked`` today, one bump a traced mixer: a
mixer is traced by the shape-resolving forward and by the step).
Nothing of it where the program keeps none."""
from benchmark import scope_paths
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    found = program_gauges("ssd_traced_total.")
    if found:
        run.result["detail"]["ssd_backend"] = found
    work = getattr(run.family, "ssd_scan_work", None)
    if work is None:
        return None
    flops, nbytes = work(run.sizes, run.traffic)
    return scope_paths.roofline_pct(run, ("mamba", "scan"), flops, nbytes)
