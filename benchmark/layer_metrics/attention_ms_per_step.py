"""Kernels: device time a step of the forward and backward events that
lie under the attention block's scope. ``models/transformer.py`` hangs
its ``MultiHeadAttention`` on a layer under the attribute ``attn``, so
the scope holds the q/k/v and output projections, the two attention
products, the softmax between them and the block's dropout. Source: the
device trace, classed by the step's own HLO metadata."""
from benchmark import span_reduce

SCOPE = "attn"


def read(run):
    return span_reduce.scope_ms_per_step(run, SCOPE)
