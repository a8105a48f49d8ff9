"""Kernels: the least time the chip could take over the full (causal,
unwindowed) layers' two attention products (the family's
``attention_products``: FLOPs over the causal half forward once and
backward twice, the least bytes; the larger of the two roofs), over the
device time under ``attn`` / ``full``, in percent. Recomputation in the
backward pass, and the lanes a head narrower than 128 leaves empty,
count as time and not as work.

The reader also puts on record, in the result's ``detail``, what the
program's own counters say of the cell's mechanisms:
``attention_backend`` (the counters ``attention_traced_total.kernel`` /
``.dense``, one bump a traced attention call: a layer is traced by the
shape-resolving forward and by the step) and ``moe_bias_changed_choice``
(the gauges of that name, a layer: the share of the first batch's tokens
whose chosen experts are not the largest scores alone). Nothing of
either where the program keeps none."""
from benchmark import scope_paths
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    for prefix, key in (("attention_traced_total.", "attention_backend"),
                        ("moe_bias_changed_choice.",
                         "moe_bias_changed_choice")):
        found = program_gauges(prefix)
        if found:
            run.result["detail"][key] = found
    products = getattr(run.family, "attention_products", None)
    if products is None:
        return None
    flops, nbytes = products(run.sizes, run.traffic, windowed=False)
    return scope_paths.roofline_pct(run, ("attn", "full"), flops, nbytes)
