"""Kernels: the least time the chip could take over the attention over
the selected keys alone, whatever implements it (the family's
``sparse_attention_work``: the two products over the selected pairs
only, forward once and backward twice, against the matrix unit's peak,
or q, k, v, o and the selected keys' indices once each way against the
HBM rate, whichever is larger), over the device time under ``attn`` /
``sparse``, in percent. Work over keys that were not selected, and the
recomputation, count as time and not as work, so the share cannot pass
100. The reader puts the program's counters on record as
``indexer_roofline`` does."""
from benchmark import scope_paths
from benchmark.layer_metrics.indexer_roofline import backend_on_record


def read(run):
    backend_on_record(run)
    work = getattr(run.family, "sparse_attention_work", None)
    if work is None:
        return None
    flops, nbytes = work(run.sizes, run.traffic)
    return scope_paths.roofline_pct(run, ("attn", "sparse"), flops, nbytes)
