"""Kernels: the least time the chip could take over the layers' two
attention products (the ``attn.products`` rows of the family's
``matrix_layers``: FLOPs forward once and backward twice, the bytes of
q, k, v and the result, since the algorithm need not write its T x T
scores; the larger of the two roofs), over the device time under
``attn`` / ``products``, in percent. Recomputation in the backward pass
counts as time and not as work."""
from benchmark import scope_paths
from benchmark.layer_metrics.attention_products_ms_per_step import NAMES


def read(run):
    rows = [(flops, nbytes) for name, flops, nbytes
            in run.family.matrix_layers(run.sizes, run.traffic)
            if name.endswith(".attn.products")]
    if not rows:
        return None
    return scope_paths.roofline_pct(run, NAMES, sum(f for f, _ in rows),
                                    sum(b for _, b in rows))
