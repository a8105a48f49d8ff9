"""Kernels: device time a step, forward and backward, of the events
under ``attn`` / ``products``: the two attention products and the
softmax between them (``models/transformer.py MultiHeadAttention`` opens
the scope around its attention call; the q/k/v and output projections,
the relayouts of the heads and the block's dropout lie under ``attn``
alone). Source: the device trace, classed by the step's own HLO
metadata. Nothing where the HLO names no such scope (an older commit).

The reader also puts on record, in the result's ``detail``, how often
the program traced each backend (``attention_backend``: the program's
counters ``attention_traced_total.kernel`` / ``.dense``, bumped once a
traced call; a layer is traced more than once a run, by the
shape-resolving forward and by the step)."""
from benchmark import scope_paths
from benchmark.layer_metrics.moe_experts_roofline import program_gauges

NAMES = ("attn", "products")


def read(run):
    ms = scope_paths.ms_per_step(run, NAMES)
    if ms is None:
        return None
    run.result["detail"]["attention_backend"] = program_gauges(
        "attention_traced_total.")
    return ms
