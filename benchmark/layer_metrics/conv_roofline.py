"""Kernels: the least time the chip could take over the short-convolution
mixers, each taken whole as one unit (the family's ``conv_block_work``:
the two projections' FLOPs forward once and backward twice against the
matrix unit's peak, or the bytes of the mixer's input, its output, its
filter and its two matrices once a pass against the HBM rate, whichever
is larger), over the device time under ``conv``, in percent. The bound
holds however the compiler fuses the operator into its neighbours, so
the share cannot pass 100; what the gated convolution costs beyond the
products, and the backward pass's recomputation, count as time and not
as work."""
from benchmark import scope_paths


def read(run):
    work = getattr(run.family, "conv_block_work", None)
    if work is None:
        return None
    flops, nbytes = work(run.sizes, run.traffic)
    return scope_paths.roofline_pct(run, ("conv",), flops, nbytes)
