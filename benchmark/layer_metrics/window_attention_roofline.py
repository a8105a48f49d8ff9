"""Kernels: the least time the chip could take over the sliding layers'
two attention products at the pairs the window allows (the family's
``attention_products``: FLOPs forward once and backward twice, the
least bytes; the larger of the two roofs), over the device time under
``attn`` / ``window``, in percent. Recomputation in the backward pass
counts as time and not as work."""
from benchmark import scope_paths


def read(run):
    products = getattr(run.family, "attention_products", None)
    if products is None:
        return None
    flops, nbytes = products(run.sizes, run.traffic, windowed=True)
    return scope_paths.roofline_pct(run, ("attn", "window"), flops, nbytes)
