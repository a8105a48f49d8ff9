"""Kernels: the fullest held expert's rows over the mean held expert's,
the worst expert layer's, on the run's first batch: the program's own
gauges ``moe_load_max_over_mean.<layer>``, filled by the expert layers
in the set-up forward. 1 is perfect balance; the grouped products' time
follows the rows, so imbalance costs nothing here and a whole chip's
wait in the deployment. Nothing where the program keeps no such gauge.

The reader also puts on record, in the result's ``detail``, the share of
(token, layer) rows whose top-k set differs between the program and the
reference on that batch (``top_k_sets_differ_share``; the family's
``routing_disagreement``): near ties flip under bfloat16, and no limit
is set on a number the flips decide."""
from benchmark.layer_metrics.moe_experts_roofline import program_gauges


def read(run):
    found = program_gauges("moe_load_max_over_mean.").values()
    if not found:
        return None
    disagreement = getattr(run.family, "routing_disagreement", None)
    if disagreement is not None:
        share = disagreement(run.sizes, run.policy, run.traffic, run.seed)
        if share is not None:
            run.result["detail"]["top_k_sets_differ_share"] = share
    return max(found)
