"""Kernels: the traced delta-rule mixers whose operands (the three
filters with their SiLU, q's and k's l2 norms, the decay's gate) took
the repo's Pallas pair (``ops/kda_operands.py``): the program's counter
``kda_mix_traced_total.kernel`` (``.fused`` where an XLA ``custom_vjp``
on the projections' layout stands in its place), bumped by ``LingKDA``
once a traced mixer beside ``.xla`` (the composition on heads). A mixer
is traced by the shape-resolving forward and by the step: 12 in Ling's
cell (6 mixers). 0 where the program counts and none took it; nothing
where it keeps no such counter (the parent).

The reader also puts every label on record, in the result's ``detail``
(``kda_mix_backend``)."""
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    traced = program_gauges("kda_mix_traced_total.")
    if not traced:
        return None
    run.result["detail"]["kda_mix_backend"] = traced
    return traced.get("kernel", 0) + traced.get("fused", 0)
