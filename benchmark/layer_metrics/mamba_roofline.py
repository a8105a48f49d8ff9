"""Kernels: the least time the chip could take over the Mamba-2 mixers,
each taken whole as one unit (the family's ``mamba_block_work``: the two
projections' FLOPs forward once and backward twice against the matrix
unit's peak, or the bytes of the mixer's input, its output and its two
matrices once a pass against the HBM rate, whichever is larger), over
the device time under ``mamba``, in percent. The bound holds however the
compiler fuses the mixer and whatever implements the scan, so the share
cannot pass 100; the scan, the filter, the norms and the backward
pass's recomputation count as time and not as work."""
from benchmark import scope_paths


def read(run):
    work = getattr(run.family, "mamba_block_work", None)
    if work is None:
        return None
    flops, nbytes = work(run.sizes, run.traffic)
    return scope_paths.roofline_pct(run, ("mamba",), flops, nbytes)
