"""Kernels: the least time the chip could take over the indexer's
scores, whatever implements them (the family's ``indexer_work``: the
heads' products over every causal pair forward and the KL loss's two
gradients over the selected pairs backward, against the matrix unit's
peak, or the indexer's queries, key and head weights read and the
selected keys' indices written forward, read again and their gradients
written backward, against the HBM rate, whichever is larger), over the
device time under ``attn`` / ``indexer``, in percent. The projections,
the top-k and the recomputation count as time and not as work, so the
share cannot pass 100.

The reader also puts on record, in the result's ``detail``, the
program's own counters ``sparse_attention_backend`` (the counters under
``sparse_attention_traced_total.``, one bump a traced layer: a layer is
traced by the shape-resolving forward and by the step). Nothing of it
where the program keeps none."""
from benchmark import scope_paths
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def backend_on_record(run):
    found = program_gauges("sparse_attention_traced_total.")
    if found:
        run.result["detail"]["sparse_attention_backend"] = found


def read(run):
    backend_on_record(run)
    work = getattr(run.family, "indexer_work", None)
    if work is None:
        return None
    flops, nbytes = work(run.sizes, run.traffic)
    return scope_paths.roofline_pct(run, ("attn", "indexer"), flops, nbytes)
