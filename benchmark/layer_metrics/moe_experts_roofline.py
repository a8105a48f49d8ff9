"""Kernels: the least time the chip could take over the expert layers'
three grouped products (gate, up, down) at the rows really routed to the
experts held here, over the device time under ``moe`` / ``experts``, in
percent. The rows are the program's own count on the run's first batch
(the gauges ``moe_rows_routed.<layer>`` that the expert layers fill in
the set-up forward); FLOPs and bytes from them by the family's
``expert_products``. Nothing where the program keeps no such gauge.

The time is that of the events under ``moe`` / ``experts`` (the gated
activation between the products) and of the grouped-matmul kernels
themselves: on a TPU XLA puts kernels named ``ragged-dot...`` in the
ragged dots' place, and they carry no scope of their own (they take
their consumer's, which lies under ``moe``), so they are found by
name."""
from benchmark import scope_paths

KERNELS = (("moe",), "ragged-dot")


def program_gauges(prefix):
    """label -> value of the program's telemetry gauges named
    ``<prefix><label>``; empty where the program keeps none (an older
    commit) or has no telemetry at all."""
    try:
        from mxnet_tpu.telemetry import metrics
    except ImportError:
        return {}
    return {name[len(prefix):]: m.value()
            for name, m in metrics.all_metrics().items()
            if name.startswith(prefix)}


def read(run):
    products = getattr(run.family, "expert_products", None)
    rows = program_gauges("moe_rows_routed.")
    if products is None or not rows:
        return None
    flops = nbytes = 0.0
    for n in rows.values():
        f, b = products(run.sizes, n)
        flops, nbytes = flops + f, nbytes + b
    return scope_paths.roofline_pct(run, ("moe", "experts"), flops, nbytes,
                                    also=KERNELS)
